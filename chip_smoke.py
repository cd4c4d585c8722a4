"""Quickest proof that the system still starts on the chip: train, kill,
resume — the GBDT round with recovery armed, through the launcher, at the
flagship size (bench.py: 1,000,000 rows x 28 features, 256 bins, depth 6).

    python chip_smoke.py              one chip: phases A, B, C
    python chip_smoke.py --chips 4    four chips: the sharded round only
    python chip_smoke.py --rehearse   a CPU rehearsal at a tiny size

This process never imports jax.  It builds the native engine from the
committed sources, then runs the phases one after another, each as ONE
device process started through ``python -m rabit_tpu.tracker.launcher``,
so the chip is never asked for by two processes at once:

  A  the round with recovery armed: ``init``, ``load_checkpoint``, then per
     round one ``train_round_fused`` step on the chip and one
     ``checkpoint`` (forest global, margin local).  Compared here with the
     plain numpy round ``bench.cpu_round`` on the same data: margins and
     log-loss, round by round.
  B  preemption and resume: the same worker with a durable checkpoint
     directory under ``--max-restarts 1``; it SIGKILLs itself after commit
     KILL_AFTER, the launcher restarts it, it resumes off the disk and must
     finish with a forest byte-identical to phase A's.
  C  the engine hop inside the program: ``train_round_hybrid``, which on
     the chip is phase A's fused kernels on codes it blocks in the graph,
     with a host callback into ``rabit_tpu.allreduce`` between a level's
     histogram and the next level's routing and one more for the leaves'
     masses (depth + 1 hops a round).  Compared with phase A's first
     trees: equal splits, leaves within tolerance.
  dp (``--chips 4`` only) rows sharded over a ("dp",) mesh of every device,
     ``train_round_dp_fused`` under ``shard_map`` with the per-level psum.
     Compared in the same process with the same rounds on one device.

One JSON line per phase on stdout; the last line is
``{"ok": true, "device": {...}}`` with the device as the worker's
``jax.devices()`` reported it — only when every phase ran on a TPU and
every comparison held.  Anything else exits non-zero without that line.
A rehearsal's lines name ``cpu`` and its last line carries no ``"ok"``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
import threading
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

import bench  # noqa: E402  (numpy only)
import rabit_tpu  # noqa: E402,F401
import rabit_tpu.tracker.launcher  # noqa: E402,F401
from chip_smoke_worker import compare_forests  # noqa: E402

ROUNDS = 6
KILL_AFTER = 3          # phase B: SIGKILL after this commit of the first life
HYBRID_ROUNDS = 3       # phase C: "a few rounds" against A's first trees
REHEARSAL_ROWS = 6000   # 4 x 1500: pads inside each shard like 1M does
#: A cold compile of the flagship round is two minutes and a restarted life
#: compiles again; the launcher's default of 300 s is shorter than that.
PHASE_TIMEOUT = 900.0

# -- phase A's tolerance against the float64 numpy round -------------------
# The kernels contract hi/lo-bf16 planes (~2^-16 relative per product, far
# less on a sum), so histograms agree with numpy's to float32 round-off and
# margins to ~1e-5.  A near-tied split may still go the other way and move
# the rows of one bin to other leaves; those rows leave the band.
MARGIN_BAND = 1e-3          # |margin - reference| that counts as "same"
MAX_SHARE_OUTSIDE = 0.01    # of rows, any round
MAX_LOGLOSS_DIFF = 1e-3     # |log-loss - reference log-loss|, any round


class SmokeFailure(Exception):
    pass


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def build_native() -> str:
    """``make -C native`` from the committed sources.  Without a compiler
    the library on disk must be newer than every source file — the engine
    would load any ``libtpurabit.so`` it finds, however old."""
    lib = ROOT / "native" / "libtpurabit.so"
    if shutil.which("make") and shutil.which(os.environ.get("CXX", "g++")):
        r = subprocess.run(["make", "-C", str(ROOT / "native"), "-j4"],
                           capture_output=True, text=True)
        if r.returncode != 0:
            raise SmokeFailure(f"native build failed:\n{r.stdout}\n{r.stderr}")
        return "built"
    sources = [p for d in ("src", "include")
               for p in (ROOT / "native" / d).rglob("*") if p.is_file()]
    if not lib.exists() or any(p.stat().st_mtime > lib.stat().st_mtime
                               for p in sources):
        raise SmokeFailure("no compiler here and native/libtpurabit.so is "
                           "missing or older than its sources")
    return "no compiler; library on disk is newer than every source"


def run_phase(tag: str, out: Path, worker_args: list[str], env: dict,
              max_restarts: int = 0) -> tuple[list[dict], str]:
    """One device process (and its restarts) under the launcher.  Returns
    the worker's result lines, one per life, and the launcher's output."""
    cmd = [sys.executable, "-m", "rabit_tpu.tracker.launcher", "-n", "1",
           "--max-restarts", str(max_restarts),
           "--timeout", str(PHASE_TIMEOUT), "--",
           sys.executable, str(ROOT / "chip_smoke_worker.py"),
           f"tag={tag}", f"out={out}", *worker_args]
    r = subprocess.run(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                       stderr=subprocess.STDOUT, text=True,
                       timeout=PHASE_TIMEOUT + 60)
    (out / f"{tag}.log").write_text(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stdout[-8000:])
        raise SmokeFailure(f"phase {tag}: launcher exited {r.returncode}")
    path = out / f"{tag}.jsonl"
    if not path.exists():
        raise SmokeFailure(f"phase {tag}: the worker left no result")
    return [json.loads(s) for s in path.read_text().splitlines()], r.stdout


def logloss(margin: np.ndarray, y: np.ndarray) -> float:
    m = np.asarray(margin, np.float64)
    return float(np.mean(np.logaddexp(0.0, m) - y * m))


def reference_margins(rows: int, seed: int, rounds: int):
    """The plain numpy round on the same data, on the host."""
    xb, y = bench.make_data(rows, seed)
    margin = np.zeros(rows, np.float32)
    out = []
    for _ in range(rounds):
        margin = bench.cpu_round(xb, y, margin)
        out.append(margin)
    return y, out


def compare_with_reference(margins: np.ndarray, ref) -> dict:
    y, ref_margins = ref
    per_round = []
    for got, want in zip(margins, ref_margins):
        diff = np.abs(got.astype(np.float64) - want)
        ll = logloss(got, y)
        per_round.append({
            "logloss": ll,
            "logloss_diff": abs(ll - logloss(want, y)),
            "share_outside_band": float(np.mean(diff > MARGIN_BAND)),
            "median_margin_diff": float(np.median(diff)),
        })
    ok = (len(margins) == len(ref_margins)
          and bool(np.all(np.isfinite(margins)))
          and all(r["logloss_diff"] <= MAX_LOGLOSS_DIFF
                  and r["share_outside_band"] <= MAX_SHARE_OUTSIDE
                  for r in per_round))
    return {"tolerance": {"margin_band": MARGIN_BAND,
                          "max_share_outside": MAX_SHARE_OUTSIDE,
                          "max_logloss_diff": MAX_LOGLOSS_DIFF},
            "per_round": per_round, "ok": ok}


def load_forest(out: Path, tag: str):
    with np.load(out / f"{tag}_forest.npz") as z:
        return z["feature"], z["threshold"], z["leaf"]


def check_device(line: dict, rehearse: bool, count: int) -> dict:
    dev = line["device"]
    want = "cpu" if rehearse else "tpu"
    if dev["platform"] != want or dev["count"] != count:
        raise SmokeFailure(f"phase {line['phase']} ran on {dev}, wanted "
                           f"{count} x {want}")
    return dev


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU, kernels interpreted")
    ap.add_argument("--out", type=Path,
                    default=ROOT / "chiprun_out" / "chip_smoke")
    args = ap.parse_args(argv)
    if "jax" in sys.modules:
        raise SmokeFailure("the parent imported jax: it would hold the chip")

    rows = REHEARSAL_ROWS if args.rehearse else bench.N_ROWS
    out = args.out.resolve()
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    env = dict(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.chips}")
    common = [f"rows={rows}", f"seed={args.seed}",
              f"rehearse={int(args.rehearse)}"]
    emit({"phase": "setup", "native": build_native(), "rows": rows,
          "rehearsal": args.rehearse, "chips": args.chips, "out": str(out)})

    if args.chips == 4:
        (line,), _ = run_phase("dp", out, [*common, "mode=dp",
                                           f"rounds={ROUNDS}"], env)
        device = check_device(line, args.rehearse, 4)
        emit(line)
        if not line["compare"]["ok"]:
            raise SmokeFailure("dp: the sharded forest differs from the "
                               "one-device forest")
        return finish(device, args.rehearse)

    # The numpy reference runs here while phase A compiles over there (a
    # daemon thread: a failed phase A must not wait for it).
    ref = {}
    ref_thread = threading.Thread(
        target=lambda: ref.update(
            value=reference_margins(rows, args.seed, ROUNDS)),
        daemon=True)
    ref_thread.start()
    (a,), _ = run_phase("A", out, [*common, "mode=fused", "margins=1",
                                   f"rounds={ROUNDS}"], env)
    device = check_device(a, args.rehearse, 1)
    ref_thread.join()
    if "value" not in ref:
        raise SmokeFailure("A: the numpy reference did not finish")
    a["compared_with"] = "bench.cpu_round (numpy, float64) on the host"
    a["compare"] = compare_with_reference(
        np.load(out / "A_margins.npy"), ref["value"])
    emit(a)
    if not a["compare"]["ok"]:
        raise SmokeFailure("A: margins or log-loss left the stated band")

    ckpt = out / "B_ckpt"
    lives, log = run_phase(
        "B", out, [*common, "mode=fused", f"rounds={ROUNDS}",
                   f"kill_after={KILL_AFTER}", f"rabit_checkpoint_dir={ckpt}"],
        env, max_restarts=1)
    restarts = log.count("[launcher] worker 0 died (code -9); restart")
    if len(lives) != 2 or restarts != 1 or lives[1]["life"] != 1:
        raise SmokeFailure(f"B: wanted one SIGKILL and one restart, got "
                           f"{len(lives)} lives, {restarts} restarts")
    first, b = lives
    check_device(first, args.rehearse, 1)
    check_device(b, args.rehearse, 1)
    b.update(
        restarts=restarts,
        killed_after_commit=first["killed_after_commit"],
        first_life={k: first[k] for k in ("compile_s", "compile_cache_hit",
                                          "run_s", "checkpoint_s")},
        kill_to_first_resumed_round_s=(b["first_round_done_at"]
                                       - first["killed_at"]),
        compared_with="phase A's forest, byte for byte",
        compare={"phase_a_sha256": a["forest_sha256"],
                 "ok": all(x.tobytes() == y.tobytes() for x, y in
                           zip(load_forest(out, "A"),
                               load_forest(out, "B")))})
    emit(b)
    if b["resumed_at_version"] != KILL_AFTER:
        raise SmokeFailure(f"B: resumed at version {b['resumed_at_version']}"
                           f", killed after commit {KILL_AFTER}")
    if not b["compare"]["ok"]:
        raise SmokeFailure("B: the resumed forest is not byte-identical to "
                           "phase A's")

    (c,), _ = run_phase("C", out, [*common, "mode=hybrid",
                                   f"rounds={HYBRID_ROUNDS}"], env)
    check_device(c, args.rehearse, 1)
    c["compared_with"] = f"phase A's first {HYBRID_ROUNDS} trees"
    c["compare"] = compare_forests(
        load_forest(out, "C"),
        tuple(x[:HYBRID_ROUNDS] for x in load_forest(out, "A")))
    want_hops = HYBRID_ROUNDS * (bench.DEPTH + 1)
    c["compare"]["ok"] = bool(c["compare"]["ok"]
                              and c["engine_hops"] == want_hops)
    emit(c)
    if not c["compare"]["ok"]:
        raise SmokeFailure(f"C: forest differs from phase A's, or the engine "
                           f"hop fired {c['engine_hops']} times, not "
                           f"{want_hops}")
    return finish(device, args.rehearse)


def finish(device: dict, rehearse: bool) -> int:
    if rehearse:
        emit({"rehearsal": True, "comparisons_held": True, "device": device})
    else:
        emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    t0 = time.time()
    try:
        sys.exit(main())
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        print(f"chip_smoke: FAILED after {time.time() - t0:.0f}s: {e}",
              file=sys.stderr, flush=True)
        sys.exit(1)
