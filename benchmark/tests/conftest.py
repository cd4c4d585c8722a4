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


def load_file(path: Path):
    """A module by its file, for directories that are no packages."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_" + path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
