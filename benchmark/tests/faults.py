"""Faults planted under the timed path, for ``test_faults.py`` alone.

The worker calls in here only where ``run.main`` was given a rehearsal that
names a fault, which the command line cannot do.  Each breaks the program
the way a wrong optimisation would, and the run has to come out with
``correct`` false."""

from __future__ import annotations


def before_trace(fault: str, gbdt) -> None:
    """Patch the program before the round is traced."""
    import jax.numpy as jnp

    if fault == "half_batch":
        # the second half of the rows never reaches a histogram
        whole = gbdt.gradients

        def half(cfg, margin, y):
            keep = jnp.arange(margin.shape[0]) < margin.shape[0] // 2
            return tuple(jnp.where(keep, a, 0.0) for a in whole(cfg, margin, y))

        gbdt.gradients = half
    elif fault == "no_exchange":
        # every shard keeps its own histogram: the psum a level left out
        real = gbdt.lax

        class NoPsum:
            def __getattr__(self, name):
                if name == "psum":
                    return lambda a, axis_name: a
                return getattr(real, name)

        gbdt.lax = NoPsum()


def wrap_step(fault: str, compiled):
    if fault != "state_unchanged":
        return compiled
    calls = [0]

    def step(state, *data):
        calls[0] += 1
        return compiled(state, *data) if calls[0] == 1 else state

    return step


def after_restore(fault: str, margin):
    if fault == "restore_altered" and margin is not None:
        margin = margin.copy()
        margin[0] += 1.0
    return margin
