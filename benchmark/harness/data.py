"""The benchmark's data: quantised features and labels from ``--seed``.

Which data is the configuration's to say: its ``data`` group names a
generator's file (``harness/datagen/``) and that generator's parameters, and
``draw`` is the one way ``run.py``, ``worker.py`` and ``tools/control.py``
come by codes and labels — numpy only, so the parent and the device worker
make the same bytes without sharing a file.  ``make_data`` is the uniform
generator under the name it had while it was the only one.
"""

from __future__ import annotations

import numpy as np

from harness import deployment
from harness.datagen.uniform import make as make_data  # noqa: F401


def draw(config: dict, seed: int):
    """``(codes[rows, features], y[rows])`` of the configuration, from its own
    generator: ``make(rows, features, bins, seed, **parameters)``."""
    parameters = dict(deployment.named(config, "data"))
    make = deployment.module_at(parameters.pop("file")).make
    return make(config["rows"], config["features"], config["max_bin"], seed,
                **parameters)


def block_host(x: np.ndarray, block: int) -> np.ndarray:
    """Rows padded with zeros to whole blocks, ``(blocks, block, width)``:
    the layout ``rabit_tpu.ops.boost.block_rows`` gives, made on the host so
    that a sharded matrix never passes through one device on its way to
    four."""
    n = x.shape[0]
    x = x.reshape(n, -1)
    pad = -n % block
    if pad:
        x = np.concatenate([x, np.zeros((pad, x.shape[1]), x.dtype)])
    return x.reshape(-1, block, x.shape[1])
