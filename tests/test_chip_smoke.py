"""chip_smoke.py on the CPU: the rehearsal holds every comparison the chip
run makes, and the script proper refuses to run without a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SMOKE = str(REPO / "chip_smoke.py")


def run_smoke(tmp_path, *args, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    r = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"), *args],
        capture_output=True, text=True, timeout=timeout, cwd=REPO, env=env)
    lines = []
    for s in r.stdout.splitlines():
        try:
            lines.append(json.loads(s))
        except json.JSONDecodeError:
            pass
    return r, lines


def assert_names_cpu_never_tpu(r, lines):
    assert '"tpu"' not in r.stdout
    for line in lines:
        if "device" in line:
            assert line["device"]["platform"] == "cpu"
    assert not any(line.get("ok") for line in lines)


def test_rehearsal_phases_hold_and_cache_goes_where_told(tmp_path):
    """Phases A-C at a tiny size, kernels interpreted: margins against the
    numpy round, byte-identical resume after a real SIGKILL with exactly
    one restart, the engine hop's forest against phase A's — and the
    compile cache under JAX_COMPILATION_CACHE_DIR when that is set."""
    cache = tmp_path / "cache"
    r, lines = run_smoke(tmp_path, "--rehearse",
                         env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    by_phase = {line["phase"]: line for line in lines if "phase" in line}
    assert set(by_phase) == {"setup", "A", "B", "C"}
    a, b, c = by_phase["A"], by_phase["B"], by_phase["C"]
    assert a["compare"]["ok"] and len(a["compare"]["per_round"]) == a["rounds"]
    assert b["restarts"] == 1 and b["life"] == 1
    assert b["resumed_at_version"] == b["killed_after_commit"] == 3
    assert b["compare"]["ok"] and b["forest_sha256"] == a["forest_sha256"]
    assert b["compile_cache_hit"] is True
    assert b["kill_to_first_resumed_round_s"] > 0
    assert "died (code -9)" in (tmp_path / "out" / "B.log").read_text()
    assert c["compare"]["ok"] and c["compare"]["splits_equal"]
    assert c["engine_hops"] == c["rounds"] * (c["depth"] + 1)
    assert_names_cpu_never_tpu(r, lines)
    assert lines[-1] == {"rehearsal": True, "comparisons_held": True,
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}
    for line in (a, b, c):
        assert line["cache_dir"] == str(cache)
    assert any(cache.iterdir())


def test_rehearsal_four_devices_runs_only_the_sharded_phase(tmp_path):
    r, lines = run_smoke(tmp_path, "--rehearse", "--chips", "4")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert [line.get("phase") for line in lines[:-1]] == ["setup", "dp"]
    dp = lines[1]
    assert dp["shards"] == {"devices": 4, "rows_per_device": 1500,
                            "blocks_per_device": 2}
    assert dp["compare"]["ok"] and dp["compare"]["splits_equal"]
    assert_names_cpu_never_tpu(r, lines)
    assert lines[-1]["device"]["count"] == 4


def test_without_rehearsal_argument_a_cpu_is_a_failure(tmp_path):
    r, lines = run_smoke(tmp_path)
    assert r.returncode != 0
    assert not any("ok" in line or "device" in line for line in lines)
    assert "not a TPU" in r.stderr + r.stdout


def test_bench_without_a_tpu_exits_nonzero_and_prints_no_record():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "bench.py")],
                       capture_output=True, text=True, timeout=120,
                       cwd=REPO, env=env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_parents_stay_off_jax():
    code = ("import sys; import rabit_tpu, rabit_tpu.tracker.launcher, "
            "chip_smoke, bench; sys.exit('jax' in sys.modules)")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
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
