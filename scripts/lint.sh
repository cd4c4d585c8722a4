#!/usr/bin/env bash
# Static checks (the reference's lint step): bytecode-compile every Python
# file, run the project-specific analyzer, and run the native build with
# warnings-as-errors.
set -euo pipefail
cd "$(dirname "$0")/.."

# rabit_tpu covers its subpackages (engine/, tracker/, parallel/, models/,
# ops/, obs/, compress/); the explicit obs/, compress/, trace, chaos and
# tool entries guard against those pieces being moved out of the tree
# without their checks following.
python -m compileall -q rabit_tpu rabit_tpu/obs rabit_tpu/compress rabit_tpu/elastic rabit_tpu/sched rabit_tpu/quorum rabit_tpu/relay rabit_tpu/ha rabit_tpu/service rabit_tpu/obs/stream.py rabit_tpu/obs/top.py rabit_tpu/obs/trace.py rabit_tpu/obs/diagnose.py rabit_tpu/obs/critical.py rabit_tpu/chaos.py rabit_tpu/engine/fused.py tests guide tools rabit_tpu/delivery tools/trace_tool.py tools/obs_top.py tools/service_bench.py tools/delivery_bench.py __graft_entry__.py

# tpulint (doc/static_analysis.md): lock discipline, event-kind registry,
# config-key discipline, wire-protocol symmetry, the interprocedural
# v2 families (reactor-blocking, journal-coverage, lock-order,
# thread-ownership), and the dataflow-substrate v3 families (resources,
# determinism, serving-parity).  Fails on any finding not carried (with
# a justification) in tools/tpulint/baseline.json — and on blowing the
# wall-time budget, which keeps the whole-repo pass honest as the tree
# grows; --timings attributes the budget per family.
python - <<'EOF'
import sys, time
from tools.tpulint.__main__ import main

BUDGET_SEC = 15.0
t0 = time.monotonic()
rc = main(["--timings"])
dt = time.monotonic() - t0
print(f"tpulint wall time: {dt:.2f}s (budget {BUDGET_SEC:.0f}s)")
if rc == 0 and dt > BUDGET_SEC:
    print(f"tpulint: exceeded the {BUDGET_SEC:.0f}s runtime budget",
          file=sys.stderr)
    rc = 3
sys.exit(rc)
EOF

make -C native clean > /dev/null
make -C native CXXFLAGS="-O2 -std=c++17 -fPIC -Wall -Wextra -Wno-unused-parameter -Werror" > /dev/null

# TPULINT_SANITIZE=1 extends the concurrency story to the native side from
# the same entry point: the tsan and asan-ubsan targets build instrumented
# libtpurabit + unit tests from sources and run them (doc/static_analysis.md
# "Sanitizer targets") — the C++ analog of the Python lock/ownership rules.
if [ "${TPULINT_SANITIZE:-0}" = "1" ]; then
  make -C native tsan
  make -C native asan-ubsan
fi

echo "lint OK"
