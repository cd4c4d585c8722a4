"""The benchmark's own tests: ``python -m pytest benchmark/tests -q`` from
the root of the checkout, on the CPU.  They are not among the repository's
tier-1 tests (``tests/``) and share no conftest with them: nothing here
imports jax into the test process, because the rehearsals start device
processes of their own."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


from harness.deployment import module_at as load_file  # noqa: E402,F401


def bench_bytes() -> dict:
    """Every file under ``benchmark/`` with its bytes: a test that adds a
    cell, a configuration or a deployment has edited none of them."""
    return {p: p.read_bytes() for p in BENCH.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}
