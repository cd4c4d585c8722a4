"""Test configuration.

Sharding/mesh tests run on a virtual 8-device CPU platform, pinned by the
shared helper; spawned workers inherit a PYTHONPATH with the repo root.
"""

import os

from rabit_tpu._platform import force_cpu_platform
from rabit_tpu.tracker.launcher import cpu_worker_env

force_cpu_platform(8)
os.environ.update(cpu_worker_env())

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_engine():
    """Each test starts with an uninitialized engine singleton."""
    yield
    import rabit_tpu

    rabit_tpu.api._engine = None
