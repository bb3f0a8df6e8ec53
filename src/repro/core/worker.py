"""Serverless query-worker function (the Lambda handler, section 3.3).

Deserializes the invocation payload into a fragment plan, executes it, and
returns the response message the coordinator polls from its queue: result
location plus execution statistics used for adaptive behavior and billing.

One handler is shared by every fragment of a session (the "code package"),
so the SPAX footer cache it owns is session-scoped: F fragments scanning G
partitions parse each footer once per object version.

The response's ``stats`` carry the fragment's host spans as ``(name,
start_ns, end_ns)`` on the worker's ``time.perf_counter_ns``, with the
``decode_ns``, ``h2d_bytes``, ``d2h_bytes``, ``agg_rows_in``,
``agg_groups_out``, ``join_merge_rows`` and ``join_search_rows``
counters. In a real
deployment each worker's clock is its own, so only durations compare
across workers; in this single-process simulation every worker runs on
the coordinator's host, and the spans share its clock (and a profiler
trace's, through one anchor).
"""

from __future__ import annotations

from repro.core.cost import PIPELINE_OVERLAP_EFFICIENCY, CostModel
from repro.exec.fragment import execute_fragment
from repro.storage.io_handlers import FooterCache
from repro.storage.object_store import ObjectStore


def make_worker_handler(store: ObjectStore,
                        footer_cache: FooterCache | None = None,
                        cost_model: CostModel | None = None):
    # cost_model (optional): enables hedged reads — workers re-trigger
    # tail-latency GETs at the tier's break-even timeout instead of the
    # constant straggler timeout
    cache = footer_cache if footer_cache is not None else FooterCache()

    def handler(payload: dict) -> tuple[dict, float]:
        result = execute_fragment(store, payload, footer_cache=cache,
                                  cost_model=cost_model)
        stats = result.stats
        if stats.pipelined:
            # Double-buffered consumption: only the first available
            # batch's read time is exposed; later top-up batches hide
            # behind kernel compute at the model's overlap efficiency.
            eff_io, saved = CostModel.overlapped_io_s(
                stats.sim_io_s, stats.first_input_s,
                PIPELINE_OVERLAP_EFFICIENCY)
            stats.overlap_saved_s = saved
            sim_runtime = eff_io + stats.compute_s
        else:
            sim_runtime = stats.sim_io_s + stats.compute_s
        response = {
            "fragment": payload["fragment"],
            "output_keys": result.output_keys,
            # per-destination (rows, bytes, distinct-key sketch) — the
            # exchange-manifest statistics the adaptive re-optimizer
            # consumes at the next stage barrier
            "partition_stats": result.partition_stats,
            # build-side semi-join filter shard (Bloom words) — the
            # coordinator OR-merges these across the fleet and publishes
            # the merged filter through the partial-manifest protocol
            "bloom": result.bloom,
            "stats": {
                "rows_in": stats.rows_in,
                "rows_out": stats.rows_out,
                "sim_io_s": stats.sim_io_s,
                "compute_s": stats.compute_s,
                "requests": stats.requests,
                "retriggers": stats.retriggers,
                "bytes_read": stats.bytes_read,
                "bytes_written": stats.bytes_written,
                "footer_cache_hits": stats.footer_cache_hits,
                "kernel": stats.kernel,
                "kernel_mode": stats.kernel_mode,
                "tier_ops": stats.tier_ops,
                "pipelined": stats.pipelined,
                "first_input_s": stats.first_input_s,
                "topups": stats.topups,
                "overlap_saved_s": stats.overlap_saved_s,
                "semijoin_killed": stats.semijoin_killed,
                "spans": stats.spans,
                "decode_ns": stats.decode_ns,
                "h2d_bytes": stats.h2d_bytes,
                "d2h_bytes": stats.d2h_bytes,
                "agg_rows_in": stats.agg_rows_in,
                "agg_groups_out": stats.agg_groups_out,
                "join_merge_rows": stats.join_merge_rows,
                "join_search_rows": stats.join_search_rows,
            },
        }
        return response, sim_runtime

    handler.footer_cache = cache
    return handler
