"""mxtpu.mxlint.families — THE one home of the counter-family tables.

Before this module the schema-stability contract lived in NINE
hand-maintained ``*_FAMILIES`` dicts inside ``tools/trace_check.py``,
while the producers (healthmon, perfscope, commscope, ...) spelled the
same names a second time at their ``counter()``/``set_gauge()`` call
sites — nothing but review discipline kept the two from drifting, and
every PR's review-hardening list paid for it.  Now there is ONE table
per family here, and every consumer derives from it:

* ``tools/trace_check.py`` builds its ``*_FAMILIES`` module globals by
  loading this file (by path — this module is pure stdlib data, so the
  validator stays importable without jax);
* mxlint's ``unregistered-counter`` rule checks every statically
  resolvable ``counter``/``set_gauge``/``observe``/``histogram`` call
  against these tables;
* ``tests/test_mxlint.py`` carries the drift test: the tables
  trace_check exports must BE these tables.

Adding a metric to a governed family is therefore one edit, here —
the validator and the linter cannot disagree with it.

IMPORTANT: this module must import NOTHING beyond the stdlib (and no
sibling modules): trace_check loads it standalone, before any backend
exists.
"""
from __future__ import annotations

__all__ = ["FAMILY_TABLES", "family_table", "family_domains",
           "known_metric", "metric_kind"]

# Every table maps "domain/metric.name" -> kind
# ("counter" | "gauge" | "histogram"), the exact shape trace_check's
# validators consume. Docs per family: docs/observability.md points at
# each subsystem's page.
FAMILY_TABLES = {
    # docs/observability.md — cross-rank training health (PR 5)
    "healthmon": {
        "healthmon/healthmon.steps": "counter",
        "healthmon/healthmon.exchanges": "counter",
        "healthmon/healthmon.nan_alerts": "counter",
        "healthmon/healthmon.stall_alerts": "counter",
        "healthmon/healthmon.step_time_regressions": "counter",
        "healthmon/healthmon.straggler_flags": "counter",
        "healthmon/healthmon.exchange_errors": "counter",
        "healthmon/healthmon.recovery_hook_errors": "counter",
        "healthmon/healthmon.collective_skew_ms": "gauge",
        "healthmon/healthmon.slowest_rank": "gauge",
        "healthmon/healthmon.step_ms_ewma": "gauge",
        "healthmon/healthmon.grad_global_norm": "gauge",
    },
    # docs/io.md — staged ingest pipeline (PR 6 prefetcher, PR 17
    # reader/decode-pool/transfer stages + sharded record reader)
    "io": {
        "io/io.batches_prefetched": "counter",
        "io/io.batches_skipped": "counter",
        "io/io.wait_ms": "counter",
        "io/io.put_ms": "counter",
        "io/io.read_ms": "counter",
        "io/io.decode_ms": "counter",
        "io/io.stage_ms": "counter",
        "io/io.records_read": "counter",
        "io/io.depth": "gauge",
        "io/io.buffer_fill": "gauge",
        "io/io.workers": "gauge",
        "io/io.shard_rank": "gauge",
        "io/io.shard_ranks": "gauge",
        "io/io.shard_records": "gauge",
    },
    # docs/trainloop.md — whole-loop executor (PR 6)
    "trainloop": {
        "trainloop/trainloop.chunks": "counter",
        "trainloop/trainloop.steps": "counter",
        "trainloop/trainloop.dispatch_ms": "counter",
        "trainloop/trainloop.k": "gauge",
        "trainloop/trainloop.chunk_ms": "gauge",
        "trainloop/trainloop.in_program_lr": "gauge",
    },
    # docs/sharding.md — mesh-native GSPMD layout (PR 8)
    "sharding": {
        "sharding/sharding.resolves": "counter",
        "sharding/sharding.fallback_replicated": "counter",
        "sharding/sharding.mesh_devices": "gauge",
        "sharding/sharding.mesh_dp": "gauge",
        "sharding/sharding.mesh_mp": "gauge",
        "sharding/sharding.params_total": "gauge",
        "sharding/sharding.params_model_sharded": "gauge",
        "sharding/sharding.params_data_sharded": "gauge",
        "sharding/sharding.params_replicated": "gauge",
        "sharding/sharding.fsdp": "gauge",
        "sharding/sharding.param_bytes_per_device": "gauge",
        "sharding/sharding.state_bytes_per_device": "gauge",
    },
    # docs/perfscope.md — roofline attribution (PR 7)
    "perfscope": {
        "perfscope/perfscope.programs_analyzed": "counter",
        "perfscope/perfscope.compute_bound": "counter",
        "perfscope/perfscope.hbm_bound": "counter",
        "perfscope/perfscope.trivial": "counter",
        "perfscope/perfscope.unknown": "counter",
        "perfscope/perfscope.step_ms": "gauge",
        "perfscope/perfscope.device_compute_ms": "gauge",
        "perfscope/perfscope.collective_ms": "gauge",
        "perfscope/perfscope.input_wait_ms": "gauge",
        "perfscope/perfscope.host_gap_ms": "gauge",
        "perfscope/perfscope.other_ms": "gauge",
        "perfscope/perfscope.mfu": "gauge",
        "perfscope/perfscope.device_step_ms": "histogram",
    },
    # docs/commscope.md — collective & resharding observability (PR 9)
    "commscope": {
        "commscope/commscope.programs_analyzed": "counter",
        "commscope/commscope.collectives": "counter",
        "commscope/commscope.payload_bytes": "counter",
        "commscope/commscope.resharding_collectives": "counter",
        "commscope/commscope.all_reduce": "counter",
        "commscope/commscope.all_gather": "counter",
        "commscope/commscope.reduce_scatter": "counter",
        "commscope/commscope.all_to_all": "counter",
        "commscope/commscope.collective_permute": "counter",
        "commscope/commscope.other": "counter",
        "commscope/commscope.step_collective_est_ms": "gauge",
        "commscope/commscope.step_collective_bytes": "gauge",
    },
    # docs/devicescope.md — measured device timeline (PR 10)
    "devicescope": {
        "devicescope/devicescope.windows": "counter",
        "devicescope/devicescope.steps_captured": "counter",
        "devicescope/devicescope.declined": "counter",
        "devicescope/devicescope.ingest_errors": "counter",
        "devicescope/devicescope.drift_warnings": "counter",
        "devicescope/devicescope.busy_fraction": "gauge",
        "devicescope/devicescope.device_busy_ms": "gauge",
        "devicescope/devicescope.collective_ms": "gauge",
        "devicescope/devicescope.idle_ms": "gauge",
    },
    # docs/servescope.md — request-lifecycle tracing (PR 11)
    "servescope": {
        "servescope/servescope.requests_traced": "counter",
        "servescope/servescope.rejections_traced": "counter",
        "servescope/servescope.sampled_out": "counter",
        "servescope/servescope.device_drift_warnings": "counter",
        "servescope/servescope.sample_every": "gauge",
        "servescope/servescope.e2e_ms": "histogram",
        "servescope/servescope.queue_wait_ms": "histogram",
        "servescope/servescope.coalesce_delay_ms": "histogram",
        "servescope/servescope.pad_overhead_ms": "histogram",
        "servescope/servescope.device_exec_ms": "histogram",
        "servescope/servescope.respond_ms": "histogram",
    },
    # docs/resilience.md — elastic self-healing training (PR 12)
    "resilience": {
        "resilience/resilience.checkpoints_saved": "counter",
        "resilience/resilience.checkpoints_pruned": "counter",
        "resilience/resilience.saves_skipped": "counter",
        "resilience/resilience.save_errors": "counter",
        "resilience/resilience.corrupt_checkpoints": "counter",
        "resilience/resilience.recoveries_total": "counter",
        "resilience/resilience.rollbacks": "counter",
        "resilience/resilience.resumes": "counter",
        "resilience/resilience.steps_lost_total": "counter",
        "resilience/resilience.retries_exhausted": "counter",
        "resilience/resilience.restarts_requested": "counter",
        "resilience/resilience.rank_departures": "counter",
        "resilience/resilience.rank_joins": "counter",
        "resilience/resilience.last_checkpoint_step": "gauge",
        "resilience/resilience.rollback_in_progress": "gauge",
        "resilience/resilience.steps_lost_last": "gauge",
        "resilience/resilience.copy_ms": "histogram",
        "resilience/resilience.save_ms": "histogram",
    },
    # docs/memscope.md — memory footprints, watermarks, OOM forensics
    "memscope": {
        "memscope/memscope.programs_captured": "counter",
        "memscope/memscope.capture_unknown": "counter",
        "memscope/memscope.capture_errors": "counter",
        "memscope/memscope.samples": "counter",
        "memscope/memscope.samples_unavailable": "counter",
        "memscope/memscope.stats_unavailable": "counter",
        "memscope/memscope.oom_events": "counter",
        "memscope/memscope.drift_warnings": "counter",
        "memscope/memscope.bytes_in_use": "gauge",
        "memscope/memscope.peak_bytes_in_use": "gauge",
        "memscope/memscope.host_rss_bytes": "gauge",
        "memscope/memscope.bytes_p50": "gauge",
        "memscope/memscope.bytes_p95": "gauge",
        "memscope/memscope.headroom_fraction": "gauge",
    },
    # docs/serving.md — continuous batching + replica fleet (PR 16)
    "fleet": {
        "fleet/fleet.routed": "counter",
        "fleet/fleet.routed_errors": "counter",
        "fleet/fleet.retries": "counter",
        "fleet/fleet.no_replica_available": "counter",
        "fleet/fleet.health_polls": "counter",
        "fleet/fleet.health_poll_errors": "counter",
        "fleet/fleet.drains": "counter",
        "fleet/fleet.readmits": "counter",
        "fleet/fleet.swaps": "counter",
        "fleet/fleet.compile_cache_hits": "counter",
        "fleet/fleet.compile_cache_misses": "counter",
        "fleet/fleet.compile_cache_stores": "counter",
        "fleet/fleet.compile_cache_errors": "counter",
        "fleet/fleet.replicas": "gauge",
        "fleet/fleet.replicas_healthy": "gauge",
        "fleet/fleet.forward_ms": "histogram",
    },
    # docs/embedding.md — sharded tables, dedup lookup, row-sparse
    # updates (PR 19)
    "embedding": {
        "embedding/embedding.lookups": "counter",
        "embedding/embedding.dedup_lookups": "counter",
        "embedding/embedding.oor_ids": "counter",
        "embedding/embedding.sparse_updates": "counter",
        "embedding/embedding.sparse_rows_updated": "counter",
        "embedding/embedding.tables": "gauge",
        "embedding/embedding.table_bytes_logical": "gauge",
        "embedding/embedding.table_bytes_per_device": "gauge",
        "embedding/embedding.ids_per_step": "gauge",
        "embedding/embedding.rows_touched_per_step": "gauge",
        "embedding/embedding.dedup_rate": "gauge",
    },
    # docs/fleetscope.md — cross-process trace context + clock-aligned
    # telemetry collection (PR 20)
    "fleetscope": {
        "fleetscope/fleetscope.ctx_minted": "counter",
        "fleetscope/fleetscope.ctx_accepted": "counter",
        "fleetscope/fleetscope.ctx_malformed": "counter",
        "fleetscope/fleetscope.ctx_propagated": "counter",
        "fleetscope/fleetscope.pulls": "counter",
        "fleetscope/fleetscope.pull_errors": "counter",
        "fleetscope/fleetscope.telem_reports": "counter",
        "fleetscope/fleetscope.telem_errors": "counter",
        "fleetscope/fleetscope.processes": "gauge",
        "fleetscope/fleetscope.pull_ms": "histogram",
    },
    # docs/mxlint.md — static analyzer + strict-mode jit auditor (PR 14)
    "mxlint": {
        "mxlint/mxlint.strict": "gauge",
        "mxlint/mxlint.findings": "gauge",
        "mxlint/mxlint.guarded_dispatches": "counter",
        "mxlint/mxlint.transfer_guard_trips": "counter",
        "mxlint/mxlint.allowed_syncs": "counter",
        "mxlint/mxlint.recompiles": "counter",
        "mxlint/mxlint.donation_violations": "counter",
    },
}


def family_table(*domains) -> dict:
    """The merged ``{"domain/name": kind}`` table for one or more
    families (trace_check's IO_TRAINLOOP_FAMILIES merges two)."""
    out = {}
    for d in domains:
        out.update(FAMILY_TABLES[d])
    return out


def family_domains() -> tuple:
    """Every governed counter domain (the mxlint unregistered-counter
    rule only judges metrics whose domain appears here)."""
    return tuple(FAMILY_TABLES)


def known_metric(full_name: str) -> bool:
    """Is ``domain/name`` registered in its family table? Metrics in
    ungoverned domains (``mxtpu``, ``bulk``, ...) return True — only a
    governed family constrains its namespace."""
    domain = full_name.split("/", 1)[0]
    table = FAMILY_TABLES.get(domain)
    return True if table is None else full_name in table


def metric_kind(full_name: str):
    """The declared kind for a governed metric, or None."""
    domain = full_name.split("/", 1)[0]
    return FAMILY_TABLES.get(domain, {}).get(full_name)
