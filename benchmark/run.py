"""One run of one cell: ``python3 benchmark/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout.

This process never imports jax.  It reads the cell from ``BENCHMARK.json``
and the cell's configuration and traffic from their own files (the
configuration names its data's generator, its reference and its program's
switches: ``harness/deployment.py``), builds the
native engine (``make -C native``), and starts ONE device process,
``benchmark/worker.py``, through ``python -m rabit_tpu.tracker.launcher
-n 1`` — the entry a user's trainer takes.  When the window has closed and
the worker has gone it runs the plain reference over the trees that the
worker's first rounds produced, decides ``correct``, lets each metric's own
reader (``benchmark/metrics/<name>.py``) read the evidence, and prints the
result's line last.  No TPU, fewer chips than the cell asks for, a compile
inside the window or a worker that fails: a non-zero exit and no line.
"""

from __future__ import annotations

import time

T_CMD = time.time()   # the start of the command, before the heavier imports

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

from harness import compare, data as bdata, deployment, work  # noqa: E402


class RunFailure(Exception):
    pass


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_cell(workload: str, manifest_path: Path | None = None):
    """The cell with its configuration and traffic, each from its own file,
    found by the names in the manifest."""
    manifest = json.loads((manifest_path or ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise RunFailure(f"no workload {workload!r}; there are {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    for key in deployment.NAMES:   # a missing one fails before a worker starts
        deployment.named(config, key)
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads((ROOT / entry["file"]).with_suffix(".limits.json").read_text())
    return manifest, cell, config, traffic, limits


def load_reader(name: str):
    return deployment.module_at(HERE / "metrics" / f"{name}.py")


def build_native() -> float:
    """``make -C native`` from the committed sources."""
    t = time.time()
    if not (ROOT / "rabit_tpu").is_dir() or not (ROOT / "native").is_dir():
        raise RunFailure("this directory holds no program to measure "
                         "(rabit_tpu/, native/)")
    r = subprocess.run(["make", "-C", str(ROOT / "native"), "-j4"],
                       capture_output=True, text=True)
    if r.returncode != 0:
        raise RunFailure(f"native build failed:\n{r.stdout}\n{r.stderr}")
    return time.time() - t


def start_worker(out: Path, spec: dict, traffic: dict, env: dict):
    (out / "spec.json").write_text(json.dumps(spec))
    budget = spec["seconds"] + 1100
    cmd = [sys.executable, "-m", "rabit_tpu.tracker.launcher", "-n", "1",
           "--max-restarts", str(traffic.get("max_restarts", 0)),
           "--timeout", str(budget), "--",
           sys.executable, str(HERE / "worker.py"), f"spec={out / 'spec.json'}"]
    log = open(out / "worker.log", "w")
    return subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                            stderr=subprocess.STDOUT), log, budget + 30


def wait_worker(out: Path, proc, log, limit: float) -> None:
    try:
        rc = proc.wait(timeout=limit)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise RunFailure("the worker did not finish in time")
    finally:
        log.close()
    if rc != 0:
        say((out / "worker.log").read_text()[-6000:])
        raise RunFailure(f"the launcher exited {rc}")


def gather(out: Path, cell, config, traffic, seconds, extra) -> dict:
    lives = [json.loads(p.read_text()) for p in sorted(out.glob("life*.json"))]
    if not lives:
        raise RunFailure("the worker left no result")
    rounds = [r for life in lives for r in life["rounds"]]
    return {"cell": cell, "config": config, "traffic": traffic,
            "seconds": seconds, "t_cmd": T_CMD, "lives": lives,
            "rounds": rounds, "window": lives[0]["window"],
            "device": lives[-1]["device"], "trace": lives[-1].get("trace"),
            **extra}


def check_window(ev: dict) -> None:
    """Nothing compiles inside the window; a restarted life loads."""
    for life in ev["lives"]:
        n = life.get("window_compiles", {}).get("backend_compiles", 0)
        if n:
            raise RunFailure(f"{n} compilations inside the window (life "
                             f"{life['life']})")
    for life in ev["lives"][1:]:
        if not life["compile"]["hit"]:
            raise RunFailure("the restarted life compiled the round anew: "
                             "the persistent cache missed inside the window")


def follow_reference(ev: dict, codes, y):
    c = ev["config"]
    first = ev["lives"][0].get("first")
    if not first:
        raise RunFailure("the first life recorded no first rounds")
    forest = tuple(np.asarray(a) for a in first["forest"])
    return deployment.reference_of(c).follow(
        c, codes, y, forest, ev["traffic"]["check_rounds"])


def setup_split(ev: dict) -> dict:
    s = ev["lives"][0]["stamps"]
    return {"native_build_s": ev["native_build_s"],
            "launch_to_main_s": s["main"] - ev["t_launch"],
            "reach_chip_s": s["devices"] - s["main"],
            "data_s": s["data"] - s["devices"],
            "place_s": s["placed"] - s["data"],
            "init_load_s": s["restored"] - s["placed"],
            "compile_or_load_s": s["compiled"] - s["restored"],
            "first_rounds_s": s["warm"] - s["compiled"]}


def main(argv=None, rehearsal: dict | None = None, manifest: Path | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if "jax" in sys.modules:
        raise RunFailure("the parent imported jax: it would hold the chip")
    mani, cell, config, traffic, limits = load_cell(args.workload, manifest)
    env = dict(os.environ)
    if rehearsal is not None:
        # tests only: a CPU, a tiny size, kernels interpreted
        config = {**config, "rows": rehearsal["rows"]}
        traffic = {**traffic, **rehearsal.get("traffic", {})}
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{cell['chips']}")
    out = ROOT / ".bench_runs" / cell["name"]
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    native_s = build_native()
    spec = {"out": str(out), "config": config, "traffic": traffic,
            "chips": cell["chips"], "seed": args.seed,
            "seconds": args.seconds, "trace": args.trace,
            "rehearse": None if rehearsal is None else
            {"interpret": True, **rehearsal.get("plant", {})}}
    t_launch = time.time()
    proc, log, limit = start_worker(out, spec, traffic, env)
    try:
        # the reference's copy of the data, made while the worker sets up
        codes, y = bdata.draw(config, args.seed)
    finally:
        wait_worker(out, proc, log, limit)
    ev = gather(out, cell, config, traffic, args.seconds,
                {"native_build_s": native_s, "t_launch": t_launch})
    check_window(ev)
    if not ev["rounds"]:
        raise RunFailure("no round was committed inside the window")

    t = time.time()
    followed = follow_reference(ev, codes, y)
    compared = compare.numbers(ev, followed, limits)
    ok = compare.correct(compared)
    ref_s = time.time() - t

    listed = mani["per_layer"] if args.trace else mani["end_to_end"]
    metrics = {}
    for m in listed:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        try:
            value = load_reader(m["name"]).read(ev)
        except KeyError as e:
            if rehearsal is None:   # a device with no row of peaks is an error
                raise
            say(f"rehearsal: {m['name']} left out: {e}")
            continue
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(ev["device"])
    # the allocator's peak on the fullest chip: live arrays plus what it
    # reserved for executables' temporaries (worker.py); XLA's own reckoning
    # of the round's executable beside it
    device["memory_peak_bytes"] = max(
        life.get("memory_peak_bytes", 0) for life in ev["lives"])
    device["memory_program_peak_bytes"] = ev["lives"][-1].get(
        "program_bytes", {}).get("peak_memory", 0)
    result = {"correct": ok, "attempted": len(ev["rounds"]), "failed": 0,
              "metrics": metrics, "device": device}
    trace = ev["trace"]
    if args.trace:
        if not trace or trace["busy_s"] <= 0:
            raise RunFailure("the traced rounds show no operation on a device")
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        top = sorted(trace["ops"].items(), key=lambda kv: -kv[1][1])[:10]
        result["breakdown"] = {
            "device_ops": [[name, v[1]] for name, v in top],
            "idle_gaps": trace["idle_gaps"][:10]}
        result["traced_rounds"] = trace["rounds"]
    if rehearsal is not None:
        result["rehearsal"] = True
    result["compared"] = {name: [v, lim] for name, v, lim in compared}

    c = ev["config"]
    took = sorted(r[3] - r[0] for r in ev["rounds"])
    say(json.dumps({
        "rounds_in_window": len(took), "round_ms_median": 1e3 * took[len(took) // 2],
        "rounds_per_s_whole_window": len(took) / (ev["rounds"][-1][3] - ev["window"]["start"]),
        "setup_split": setup_split(ev), "reference_s": ref_s,
        "compile": [life["compile"] for life in ev["lives"]],
        "cache_dir": ev["lives"][-1].get("cache_dir"),
        "trees_of_capacity": [ev["lives"][-1]["version"], c["num_trees"]],
        "engine_hops": sum(life["hops"][1] for life in ev["lives"]),
        "issued_onehot_flops_a_round": work.issued_onehot_flops(
            c["rows"], c["features"], c["max_bin"], c["max_depth"]),
        "split_differs_from_reference": followed.split_differs}))
    for name, v, lim in compared:
        say(f"compared {name} {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except (RunFailure, deployment.ConfigError, subprocess.TimeoutExpired) as e:
        say(f"benchmark/run.py: FAILED after {time.time() - T_CMD:.0f}s: {e}")
        sys.exit(1)
