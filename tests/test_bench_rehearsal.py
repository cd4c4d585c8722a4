"""The trainer the driver measures, on the CPU: every cell of
``BENCHMARK.json`` through ``benchmark/run.py`` -> the launcher ->
``benchmark/worker.py`` at a tiny size, kernels interpreted, the kill and
the resume included.  A rehearsal's last line names ``cpu`` and carries
``"rehearsal": true``; without the ``rehearsal`` argument a CPU is refused.

``run.main`` will not run in a process that has imported jax (it would hold
the chip), and ``conftest.py`` has: a child makes the call
``benchmark/tests/test_rehearsal.py::test_cell_end_to_end`` makes."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmark"
M = json.loads((REPO / "BENCHMARK.json").read_text())
CPU_TRACE = {"device_plane": "^/host:CPU$", "op_lines": ["^tf_XLA"]}
#: rows of a rehearsal.  The width is the configuration's, so that at 2,000
#: features all sixteen feature tiles and the routing passes are rehearsed;
#: there an interpreted round is 4 s a row block on a CPU, and two blocks
#: (several row blocks a tile sweep) keep the case about a minute
ROWS = {"epsilon-400k": 2048}
#: the window of the case with a kill in it.  The second life has to come up
#: (the launcher's respawn, jax, the data, the restore, the round loaded from
#: the cache) and commit a round before the window closes, or nothing of it
#: can be compared.  Alone that takes 4 s; beside five other xdist workers on
#: eight cores it took 13.2 s, a 12 s window closed before the first resumed
#: round and the case failed on ``correct`` (``resume_mismatch``: no trees
#: digest at restore) at the driver and here alike (PR 30, PR 31)
KILL_WINDOW_S = 30

CALL = """
import json, sys
sys.path.insert(0, sys.argv[1])
import run
argv, rehearsal = map(json.loads, sys.argv[2:4])
try:
    sys.exit(run.main(argv, rehearsal=rehearsal))
except run.RunFailure as e:   # what the command line turns into exit 1
    sys.exit(f"RunFailure: {e}")
"""


def run_main(workload, rehearsal, seconds=6, env_extra=None, timeout=600):
    argv = ["--workload", workload, "--seed", "3000000019",
            "--seconds", str(seconds), "--trace", "0"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "-c", CALL, str(BENCH), json.dumps(argv),
         json.dumps(rehearsal)],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env)


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell_rehearsal(cell, tmp_path):
    kill = {"kill_after_commit": 5} if "kill" in cell["traffic"] else {}
    cache = tmp_path / "cache"
    r = run_main(cell["name"],
                 {"rows": ROWS.get(cell["config"], 6000), "traffic": kill,
                  "plant": {"trace_rules": CPU_TRACE}},
                 seconds=KILL_WINDOW_S if kill else 6,
                 env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu" and '"tpu"' not in r.stdout
    assert line["device"]["count"] == cell["chips"]
    assert line["attempted"] > 0 and line["failed"] == 0
    for v, lim in line["compared"].values():
        assert v <= lim
    summary = next(json.loads(s) for s in r.stderr.splitlines()
                   if s.startswith('{"rounds_in_window"'))
    if kill:
        # a real SIGKILL, exactly one restart, the forest byte-identical,
        # the second life's round loaded from the cache where it was told
        assert line["compared"]["resume_mismatch"] == [0, 0]
        assert len(summary["compile"]) == 2 and summary["compile"][1]["hit"]
        assert summary["cache_dir"] == str(cache) and any(cache.iterdir())
    if cell["traffic"] == "engine-hop":
        config = next(c for c in M["configs"] if c["name"] == cell["config"])
        depth = json.loads((REPO / config["file"]).read_text())["max_depth"]
        trees, _capacity = summary["trees_of_capacity"]
        assert summary["engine_hops"] == (depth + 1) * trees


def test_without_rehearsal_a_cpu_is_refused():
    r = run_main(M["workloads"][0]["name"], None, timeout=300)
    assert r.returncode == 1 and r.stdout.strip() == ""
    assert "RunFailure" in r.stderr and "refusing to run" in r.stderr


def test_parents_stay_off_jax():
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "import rabit_tpu, rabit_tpu.tracker.launcher, run; "
            "sys.exit('jax' in sys.modules)")
    r = subprocess.run([sys.executable, "-c", code, str(BENCH)], cwd=REPO,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr


@pytest.mark.parametrize("from_env", (True, False))
def test_persistent_cache_is_placed_from_outside(from_env, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set no directory is set in code;
    where it is not, the cache is <checkout>/.jax_cache."""
    code = ("import jax; from rabit_tpu._platform import "
            "enable_persistent_cache as e; "
            "before = jax.config.jax_compilation_cache_dir; e(); "
            "print(before); print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    before, after = r.stdout.split()
    if from_env:
        assert before == after == str(tmp_path)
    else:
        assert before == "None" and after == str(REPO / ".jax_cache")
