"""recover_worker on the XLA engine: pin the CPU platform first (same
reason xla_worker.py pins), then run the self-verifying recovery
workload.  Used by the durable-resume test."""

import sys
from pathlib import Path

from rabit_tpu._platform import force_cpu_platform

force_cpu_platform(1)

sys.path.insert(0, str(Path(__file__).parent))
import recover_worker  # noqa: E402

if __name__ == "__main__":
    sys.exit(recover_worker.main())
