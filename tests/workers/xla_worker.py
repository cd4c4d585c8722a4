"""basic_worker on the XLA engine: pin the CPU platform (the test's
workers share one host and could not share one chip), then run the same
self-verifying matrix.  The jax.distributed bootstrap happens inside
XlaEngine.init from the JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
JAX_PROCESS_ID environment exported by tests/test_xla_engine.py."""

import sys
from pathlib import Path

from rabit_tpu._platform import force_cpu_platform

force_cpu_platform(1)

sys.path.insert(0, str(Path(__file__).parent))
import basic_worker  # noqa: E402

if __name__ == "__main__":
    basic_worker.main()
