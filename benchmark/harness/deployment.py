"""What a configuration's file names beside its sizes.

``data``       ``{"file": <generator>, ...parameters}``: a module with
               ``make(rows, features, bins, seed, **parameters) -> (codes, y)``
               (``harness/data.py:draw`` calls it),
``reference``  the file of its plain reference: a module with ``follow``,
               ``free`` and ``first_numbers`` (``harness/reference.py`` says
               what each is),
``program``    ``block_rows`` and the switches of ``GBDTConfig`` it runs with.

A file is found as ``configs[].file`` is: a path relative to the checkout,
an absolute one taken as it is.  So a deployment with another schema brings
a generator, a copy of the reference and a configuration, and edits nothing.
"""

from __future__ import annotations

import importlib.util
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
#: the keys every configuration has to have; ``run.py`` looks before it
#: starts a worker
NAMES = ("data", "reference", "program")


class ConfigError(ValueError):
    """The configuration's file lacks a key, or names what is not there."""


def named(config: dict, key: str):
    if key not in config:
        raise ConfigError(f"the configuration names no {key!r} "
                          f"(benchmark/README.md, 'A configuration'); it has "
                          f"{sorted(config)}")
    return config[key]


def module_at(path):
    """The module in the file at ``path``, which is no package's."""
    path = ROOT / path
    if not path.is_file():
        raise ConfigError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", path.stem), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_of(config: dict):
    return module_at(named(config, "reference"))


def gbdt_config(config: dict, cls):
    """``cls`` (the program's ``GBDTConfig``) from the configuration's sizes
    and every key of its ``program`` group but ``block_rows``, which is the
    layout's and not the model's."""
    switches = {k: v for k, v in named(config, "program").items()
                if k != "block_rows"}
    unknown = sorted(set(switches) - set(cls._fields))
    if unknown:
        raise ConfigError(f"'program' names {unknown}, which {cls.__name__} "
                          f"lacks; its fields are {list(cls._fields)}")
    return cls(n_features=config["features"], n_trees=config["num_trees"],
               depth=config["max_depth"], n_bins=config["max_bin"],
               learning_rate=config["eta"], reg_lambda=config["lambda"],
               min_child_weight=config["min_child_weight"], **switches)
