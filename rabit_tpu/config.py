"""Layered key=value configuration.

Capability parity with the reference's ``SetParam`` layering (built-in
defaults <- watched env vars <- argv ``k=v`` overrides, see
``/root/reference/src/allreduce_base.cc:49-64`` and ``doc/parameters.md``)
re-expressed as a plain dataclass-free dict with typed accessors instead of
strcmp chains.
"""

from __future__ import annotations

import os
from typing import Iterable, Mapping


# Environment variables consulted at init time (reference: the ``env_vars``
# watch list in allreduce_base.cc / allreduce_robust.cc).  Both the legacy
# DMLC_* spellings and RABIT_TPU_* spellings are honoured; the latter wins.
_ENV_KEYS = [
    "DMLC_TRACKER_URI",
    "DMLC_TRACKER_PORT",
    "DMLC_TASK_ID",
    "DMLC_ROLE",
    "DMLC_NUM_ATTEMPT",
    "DMLC_WORKER_CONNECT_RETRY",
    "RABIT_OBS_DIR",
    "rabit_global_replica",
    "rabit_local_replica",
]

# Mapping from env-var name to canonical config key.
_ENV_TO_KEY = {
    "DMLC_TRACKER_URI": "rabit_tracker_uri",
    "DMLC_TRACKER_PORT": "rabit_tracker_port",
    "DMLC_TASK_ID": "rabit_task_id",
    "DMLC_ROLE": "rabit_role",
    "DMLC_NUM_ATTEMPT": "rabit_num_trial",
    "DMLC_WORKER_CONNECT_RETRY": "rabit_connect_retry",
    "RABIT_OBS_DIR": "rabit_obs_dir",
}

_UNIT = {"B": 1, "K": 1 << 10, "M": 1 << 20, "G": 1 << 30}

#: Built-in defaults — the performance envelope knobs of the reference
#: (allreduce_base.cc:18-46, allreduce_robust.cc:26-40) with identical
#: semantics and defaults.
DEFAULTS: dict[str, str] = {
    "rabit_engine": "auto",           # auto | empty | xla | native | mock
    # XLA engine multi-process bootstrap (engine/xla.py): empty means
    # "fall back to the standard JAX cluster env vars" (the engine reads
    # these with an `or` chain, so the empty default never shadows
    # JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID).
    "rabit_xla_coordinator": "",
    "rabit_xla_num_processes": "",
    "rabit_xla_process_id": "",
    "rabit_tracker_uri": "NULL",
    "rabit_tracker_port": "9091",
    "rabit_task_id": "NULL",
    "rabit_num_trial": "0",
    "rabit_connect_retry": "5",
    "rabit_reduce_ring_mincount": str(32 << 10),
    "rabit_tree_reduce_minsize": str(1 << 20),
    "rabit_reduce_buffer": "256M",
    "rabit_global_replica": "5",
    "rabit_local_replica": "2",
    "rabit_timeout": "1",
    "rabit_timeout_sec": "1800",
    # rabit_stall_timeout_sec is deliberately NOT defaulted here: its
    # default is engine-dependent (robust: 300s, base: off — see
    # Comm::SetDefaultStallSec), and a value here would be serialized into
    # RabitInit argv and override that.
    "rabit_bootstrap_cache": "0",
    # Durable checkpoint spill: when set, every committed checkpoint is
    # also written here and a FRESH cluster resumes from the newest disk
    # version (whole-job preemption durability; rabit_tpu/store.py).
    "rabit_checkpoint_dir": "",
    # Compressed collectives (rabit_tpu/compress, doc/compression.md).
    # rabit_compress_allreduce: default codec for api.allreduce payloads
    # (identity|bf16|bf16x2|i8|i8x2; empty = exact f32).  Applies only to
    # float32 non-BITOR payloads of at least rabit_compress_min_bytes
    # bytes; a per-call codec= argument always wins.
    # rabit_compress_wire_deflate: lossless deflate stage on the host
    # transport's wire bytes (the in-graph XLA path ships raw planes).
    # rabit_compress_broadcast: byte codec (zlib) for api.broadcast
    # payloads.  rabit_checkpoint_compress: the codec the durable store
    # MAY apply, a frame at a time, where a probe of the blob says it
    # pays (store.py; old frames stay readable; empty = uncompressed).
    "rabit_compress_allreduce": "",
    "rabit_compress_min_bytes": "1024",
    "rabit_compress_wire_deflate": "1",
    "rabit_compress_broadcast": "",
    "rabit_checkpoint_compress": "zlib",
    # Fused in-XLA quantized collectives (rabit_tpu/engine/fused.py;
    # doc/compression.md "Fused in-XLA path").  rabit_fused_allreduce:
    # auto (default — ON for the XLA engine, meaningless elsewhere: the
    # host transport is the only compressed path off-XLA) | 1 | 0.  When
    # on, XlaEngine.allreduce_compressed lowers encode -> chunked
    # ppermute ring (the PR 7 planned schedule order) -> rank-order
    # decode-fold into ONE jitted graph, bitwise identical to the host
    # reference fold.  rabit_fused_chunk_kib tunes the per-ppermute hop
    # sub-chunk size (KiB; 0 = one ppermute per hop) for comm/compute
    # overlap.
    "rabit_fused_allreduce": "auto",
    "rabit_fused_chunk_kib": "256",
    "rabit_debug": "0",
    # Observability (rabit_tpu/obs, doc/observability.md): when
    # rabit_obs_dir (or the RABIT_OBS_DIR env var) is set, each rank dumps
    # its flight recorder there on SIGTERM or when a collective is stuck
    # longer than rabit_obs_hang_sec, and the tracker writes the job-level
    # telemetry.json there.  rabit_obs_heartbeat_sec > 0 additionally
    # ships periodic metric snapshots to the tracker (shutdown always
    # ships one).
    "rabit_obs_dir": "",
    "rabit_obs_capacity": "2048",
    "rabit_obs_hang_sec": "300",
    "rabit_obs_heartbeat_sec": "0",
    # Live telemetry plane (doc/observability.md "Live telemetry
    # plane").  rabit_obs_spill_sec > 0: each rank periodically spills
    # its flight ring into the obs dir so `trace_tool export --follow`
    # can emit a growing Perfetto file mid-run.  rabit_obs_max_files
    # caps the obs dir's flight-dump count (oldest-first eviction,
    # obs_evicted event; 0 disables).  rabit_obs_scrape names the
    # task id CMD_OBS scrape clients identify as (obs_top, benches).
    "rabit_obs_spill_sec": "0",
    "rabit_obs_max_files": "256",
    "rabit_obs_scrape": "obs",
    # Liveness layer (doc/fault_tolerance.md).  rabit_heartbeat_sec > 0:
    # renew a CMD_HEARTBEAT lease with the tracker every N seconds; the
    # tracker suspects this worker (lease_expired event + on_suspect
    # callback, which the launcher wires to SIGKILL-and-restart) after
    # 2 x N seconds of silence — the failure detector for SILENT deaths
    # (frozen process, preempted VM) that raise no exit code and no TCP
    # error.  rabit_hang_abort_sec > 0: a collective stuck in flight this
    # long makes the rank dump its flight recorder and abort itself
    # (exit 11, dump-then-die) so the launcher restarts it — the
    # worker-side belt to the tracker lease's suspenders.
    "rabit_heartbeat_sec": "0",
    "rabit_hang_abort_sec": "0",
    # Elastic worlds (rabit_tpu/elastic, doc/elasticity.md).
    # rabit_spare=1 marks a worker as a HOT SPARE: it checks in with
    # CMD_SPARE, receives the cached compressed bootstrap blob, and parks
    # on a warm socket until the tracker promotes it into a dead rank's
    # slot.  rabit_shrink_after_sec > 0 lets a recovery wave close SHRUNK
    # when no spare arrives within the deadline (0 keeps the legacy
    # block-until-full contract); rabit_min_world floors the shrink.
    # rabit_spare_promote_sec is the grace before a short wave steals a
    # parked spare — a slow-but-live worker's own check-in wins the slot
    # inside the grace.
    "rabit_spare": "0",
    "rabit_shrink_after_sec": "0",
    "rabit_min_world": "1",
    "rabit_spare_promote_sec": "0.25",
    # Collective schedules (rabit_tpu/sched, doc/scheduling.md).
    # rabit_schedule picks the per-epoch ring layout the tracker plans
    # (auto|tree|ring|swing); rabit_sched_mesh pins the mesh-model dims
    # ("RxC[:nowrap]", empty = near-square auto); rabit_sched_repair
    # lets degraded-link reports trigger a repair replan at the next
    # epoch boundary; rabit_sched_wait_share is the executor's
    # wait-share threshold for indicting its incoming link.
    "rabit_schedule": "auto",
    "rabit_sched_mesh": "",
    "rabit_sched_repair": "1",
    "rabit_sched_wait_share": "0.25",
    # Partial (quorum) allreduce (rabit_tpu/quorum,
    # doc/partial_allreduce.md).  rabit_quorum: a fraction in (0,1]
    # ("0.67" = two thirds of the current world) or an integer count —
    # a collective round completes once that many contributions have
    # folded; stragglers' late blocks land as exact correction terms at
    # the next round boundary after delivery.  Empty (default) keeps
    # the legacy exact lockstep collective; "1.0" runs the quorum wire
    # but never excludes (bitwise identical to legacy).
    # rabit_quorum_wait_sec is the executor's per-round deadline before
    # it reports a partial quorum (and before a silent upstream rank is
    # skipped around); rabit_quorum_flag_after feeds a rank excluded
    # that many consecutive rounds into the schedule-repair avoid set
    # (0 disables the feed).
    "rabit_quorum": "",
    "rabit_quorum_wait_sec": "0.35",
    "rabit_quorum_flag_after": "3",
    # Cross-rank tracing (rabit_tpu/obs/trace.py, tools/trace_tool.py).
    # rabit_trace_exit=1: dump the flight ring as flight-*-exit.jsonl at
    # finalize, so CLEAN runs leave the per-rank evidence the job-wide
    # trace merger joins.  rabit_trace_clock_pings: timestamped
    # round-trips at shutdown that (re)estimate this rank's clock offset
    # against the tracker before the final snapshot ships it.
    "rabit_trace_exit": "0",
    "rabit_trace_clock_pings": "2",
    # Serving at scale (doc/scaling.md).  rabit_tracker_backlog: the
    # tracker's listen(2) backlog — a bootstrap wave is world_size nearly
    # simultaneous connects, and a short backlog turns the overflow into
    # 1s+ SYN-retransmit latency; raise toward the world size for
    # O(10^3)+ direct worlds (relayed deployments keep the root's accept
    # count at O(relays) instead).
    "rabit_tracker_backlog": "1024",
    # HA control plane (rabit_tpu/ha, doc/ha.md).  rabit_tracker_addrs:
    # comma-separated "host:port" tracker addresses (the primary first,
    # then its warm standby) — every tracker_rpc rotates through them on
    # failure, so a primary tracker death fails over client-side.
    # rabit_ha_journal: path of the durable control-plane journal the
    # tracker appends every mutation to (empty = journaling off);
    # rabit_ha_snapshot_every: records between compacted snapshots (the
    # replay-cost bound); rabit_ha_takeover_sec: the standby's takeover
    # lease — how long the primary may be unreachable/silent before the
    # standby promotes itself; rabit_ha_tick_sec: the primary's journal
    # keepalive cadence (the liveness signal that lease watches).
    # Multi-tenant collective service (rabit_tpu/service, doc/service.md).
    # rabit_job_key: the job this worker belongs to — it prefixes the
    # wire task id ("<job>/<task>"; empty = the legacy single-job
    # namespace, byte-identical on the wire) so a CollectiveService
    # routes the worker to its job's control-plane partition.
    # rabit_service_max_jobs / rabit_service_max_jobs_per_tenant /
    # rabit_service_max_ranks: the service's admission quotas
    # (concurrent jobs service-wide, concurrent jobs per tenant — the
    # job key up to its first "." — and the fd budget as the sum of
    # admitted world sizes; 0 = unlimited).  rabit_service_auto_world:
    # world size for jobs admitted straight from the wire (an unknown
    # job key's first check-in); 0 refuses unknown keys — programmatic
    # admission only.
    "rabit_job_key": "",
    "rabit_service_max_jobs": "0",
    "rabit_service_max_jobs_per_tenant": "0",
    "rabit_service_max_ranks": "0",
    "rabit_service_auto_world": "0",
    "rabit_tracker_addrs": "",
    "rabit_ha_journal": "",
    "rabit_ha_snapshot_every": "256",
    "rabit_ha_takeover_sec": "1.0",
    "rabit_ha_tick_sec": "0.25",
    # Default ON, matching the native engine (see comm.cc Configure): with
    # Nagle on, every cold-direction header write stalls ~40ms behind the
    # peer's delayed ACK — measured 44ms/op on loopback object broadcasts.
    "rabit_enable_tcp_no_delay": "1",
    # Diagnosis plane (rabit_tpu/obs/diagnose.py, doc/observability.md).
    # rabit_diag_enable: run the HealthMonitor on the tracker (and every
    # service partition); rabit_diag_window_sec: detection-window cadence;
    # rabit_diag_open_windows / rabit_diag_resolve_windows: hysteresis —
    # consecutive firing windows before an incident opens / quiet windows
    # before it resolves; rabit_diag_min_wait_sec: ignore windows whose
    # total link wait is below this (clean-run noise floor);
    # rabit_diag_link_share: the degraded-link concentration threshold
    # (top link's share of the window's wait); rabit_diag_hole_ratio:
    # the compute-straggler hole threshold (the quiet link's wait vs the
    # per-link mean); rabit_diag_storm_leases: lease expiries across the
    # recent windows that count as a preemption storm, not one death.
    "rabit_diag_enable": "1",
    "rabit_diag_window_sec": "0.5",
    "rabit_diag_open_windows": "2",
    "rabit_diag_resolve_windows": "4",
    "rabit_diag_min_wait_sec": "0.05",
    "rabit_diag_link_share": "0.5",
    "rabit_diag_hole_ratio": "0.25",
    "rabit_diag_storm_leases": "3",
    # Model-delivery plane (rabit_tpu/delivery, doc/delivery.md).
    # rabit_delivery_publish=1: rank 0 publishes every checkpoint commit
    # as a content-addressed snapshot (version line + digest-deduped
    # bytes) through the tracker.  rabit_delivery_poll_sec: subscriber
    # poll/retry cadence.  rabit_relay_cache_bytes: each relay's
    # digest-keyed snapshot cache budget (LRU beyond it; live jobs'
    # newest digests are never evicted).  rabit_checkpoint_keep: the
    # durable store's retention window (versions beyond the newest N
    # prune after each commit; the published version stays pinned).
    "rabit_delivery_publish": "0",
    "rabit_delivery_poll_sec": "0.5",
    "rabit_relay_cache_bytes": "256M",
    "rabit_checkpoint_keep": "2",
}


def parse_unit(value: str) -> int:
    """Parse ``"256M"``-style sizes (reference: ParseUnit,
    allreduce_base.cc:150-170)."""
    value = value.strip()
    if value and value[-1].upper() in _UNIT:
        return int(float(value[:-1]) * _UNIT[value[-1].upper()])
    return int(value)


class Config:
    """Merged configuration with typed accessors."""

    def __init__(
        self,
        args: Iterable[str] | None = None,
        overrides: Mapping[str, str] | None = None,
    ):
        self._cfg = dict(DEFAULTS)
        # layer 2: environment
        for env_name in _ENV_KEYS:
            val = os.environ.get(env_name)
            if val is not None:
                self._cfg[_ENV_TO_KEY.get(env_name, env_name)] = val
        for env_name, val in os.environ.items():
            if env_name.startswith("RABIT_TPU_"):
                self._cfg[env_name[len("RABIT_TPU_"):].lower()] = val
        # layer 3: argv "k=v" pairs
        for arg in args or []:
            if "=" in arg:
                key, val = arg.split("=", 1)
                self._cfg[key] = val
        # layer 4: explicit kwargs
        for key, val in (overrides or {}).items():
            self._cfg[key] = str(val)

    def get(self, key: str, default: str | None = None) -> str | None:
        return self._cfg.get(key, default)

    def get_int(self, key: str, default: int = 0) -> int:
        val = self._cfg.get(key)
        return default if val is None else int(val)

    def get_size(self, key: str, default: int = 0) -> int:
        val = self._cfg.get(key)
        return default if val is None else parse_unit(val)

    def get_bool(self, key: str, default: bool = False) -> bool:
        val = self._cfg.get(key)
        if val is None:
            return default
        return val.strip().lower() not in ("0", "false", "no", "off", "")

    def __getitem__(self, key: str) -> str:
        return self._cfg[key]

    def __contains__(self, key: str) -> bool:
        return key in self._cfg

    def as_dict(self) -> dict[str, str]:
        return dict(self._cfg)

    @property
    def timeout_sec(self) -> int:
        """Watchdog bound; 0 when the watchdog is disabled."""
        if not self.get_bool("rabit_timeout"):
            return 0
        return self.get_int("rabit_timeout_sec", 1800)
