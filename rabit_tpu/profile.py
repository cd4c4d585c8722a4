"""Tracing / profiling (SURVEY §5 aux subsystems): the historical stats
facade, and ``xla_trace``, the operator's way to take a profiler trace.

The reference's observability is (a) ``rabit_debug=1`` per-op latency log
lines (allreduce_robust.cc:214-217,289-294) and (b) the mock engine's
per-checkpoint-interval timing totals (allreduce_mock.h:56-77).  The TPU
build's observability now lives in :mod:`rabit_tpu.obs` — a thread-safe
metrics registry (counters/gauges/latency histograms) plus a flight
recorder of structured events.  This module keeps the historical surface:

* ``CollectiveStats`` / ``OpStats`` / ``GLOBAL_STATS`` — now thin views
  over the process-wide :data:`rabit_tpu.obs.GLOBAL_REGISTRY`, so existing
  callers (``rt.collective_stats().report()``) keep working and gain
  thread safety + histogram percentiles for free.

The deprecated stdout-line parsers (``parse_stats_line`` /
``is_recovery_stats_line``) reached their removal horizon and are gone:
the tracker converts the robust engine's ``recover_stats`` /
``failure_detected`` prints — and the recovery workloads'
``recovered_at=`` / ``resumed from disk`` stamps — into structured events
(``LocalCluster.events``, ``telemetry.json``), which every in-repo
consumer reads; the undecorated line parser for that ingest lives in
``rabit_tpu.obs.events``.

Usage:

    import rabit_tpu as rt
    ... rt.allreduce(...) ...
    print(rt.collective_stats().report())   # counts/bytes/latency per op

    from rabit_tpu.profile import xla_trace
    with xla_trace("/tmp/tb"):              # open in TensorBoard / xprof
        run_tpu_step()
"""

from __future__ import annotations

import contextlib

from rabit_tpu.obs.metrics import GLOBAL_REGISTRY, MetricsRegistry, OpStats


class CollectiveStats:
    """Per-operation accumulated timing — the historical facade, now backed
    by a thread-safe :class:`rabit_tpu.obs.MetricsRegistry`.  A bare
    ``CollectiveStats()`` gets its own private registry; ``GLOBAL_STATS``
    shares the process-wide one that ``rabit_tpu.api`` times into."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self._registry = registry if registry is not None else MetricsRegistry()

    @property
    def registry(self) -> MetricsRegistry:
        return self._registry

    @property
    def ops(self) -> dict[str, OpStats]:
        return self._registry.ops

    def timed(self, op: str, nbytes: int):
        """Context manager timing one collective (delegates to the
        registry; also feeds the per-op latency histogram)."""
        return self._registry.timed(op, nbytes)

    def reset(self) -> None:
        self._registry.reset()

    def report(self) -> str:
        """One line per op: count, volume, mean/max latency, bandwidth,
        and latency percentiles."""
        return self._registry.report()


#: process-wide collector used by rabit_tpu.api
GLOBAL_STATS = CollectiveStats(registry=GLOBAL_REGISTRY)


@contextlib.contextmanager
def xla_trace(logdir: str):
    """Take a profiler trace of the enclosed steps into ``logdir`` (open
    ``<logdir>/plugins/profile/*/*.xplane.pb`` in TensorBoard/xprof, or
    read it with ``jax.profiler.ProfileData``).  The program's own spans
    (:func:`rabit_tpu.obs.span`: ``rabit.checkpoint``, ``rabit.spill.*``,
    ``rabit.allreduce``, ``gbdt.cross``) land in it on the profiler's
    clock, beside the device's operations.  The tracer levels are the ones
    the benchmark's worker sets: ``host_tracer_level=2`` (the
    TraceAnnotations and the runtime's own host events) and
    ``python_tracer_level=0`` (no per-call Python events, which would
    swamp a round)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 2
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
