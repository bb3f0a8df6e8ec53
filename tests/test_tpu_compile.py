"""Compile every dispatch kernel for a described TPU v5e (no chip needed).

Interpret mode cannot see what Mosaic refuses: block shapes off the
(8, 128) tiling, scalar stores to VMEM, gathers it has no lowering for.
Each case plans a real query, lowers its fused fragment program, and
compiles it as the fragment runs it (its output compacted) ahead of time
for one chip of a ``v5e:2x2`` topology at the capacities a deployment
hands a worker: 2^20-row streaming inputs, and the roofline's resident
caps for the build side and the sort kernels.

The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the one running
this file loads the TPU compiler. The kernel wrappers ask the backend
whether to interpret; the tests steer them to Mosaic by patching that
one predicate.
"""

from __future__ import annotations

import os

import jax
import numpy as np
import pytest

from repro.core import CoordinatorConfig, FaasPlatform, QueryCoordinator
from repro.exec import fragment, lower
from repro.kernels import bloom
from repro.kernels import common
from repro.sql.physical import PlannerConfig
from repro.sql.queries import QUERIES

STREAM_ROWS = 1 << 20

SQLS = {
    "filter_agg": QUERIES["q6"],
    "groupby_onehot": QUERIES["q1"],
    "segmented_minmax": ("select l_returnflag, min(l_quantity) as mq, "
                         "max(l_tax) as mt from lineitem "
                         "group by l_returnflag"),
    "sort_agg": ("select l_orderkey, sum(l_quantity) as s, "
                 "count(*) as c, min(l_extendedprice) as m "
                 "from lineitem group by l_orderkey"),
    "join_probe_agg": QUERIES["q12"],
    "topk": QUERIES["q3"],
    # a selective repartition join: the probe-side scan carries the
    # build side's Bloom filter
    "bloom_filter": ("select l_orderkey, sum(l_extendedprice) as rev "
                     "from lineitem, orders where l_orderkey = o_orderkey "
                     "and o_totalprice > 500000 group by l_orderkey"),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Lower as on the chip: kernels compiled, never interpreted."""
    monkeypatch.setattr(common, "interpret_mode", lambda: False)


def _pipeline(store, catalog, kernel: str):
    cfg = CoordinatorConfig(planner=PlannerConfig(
        broadcast_threshold_bytes=1, exchange_partitions=4),
        use_result_cache=False)
    coord = QueryCoordinator(store, catalog, platform=FaasPlatform(seed=1),
                             config=cfg)
    plan = coord.plan_sql(SQLS[kernel])
    pipes = list(plan.pipelines.values())
    if kernel == "bloom_filter":
        # the fragment driver wraps the probe-side scan in the Bloom probe
        # when the build side's filter is pushed down; the largest filter
        probe = next(p for p in pipes if p.params.semijoin)
        op = {"t": "semijoin_probe", "key": "l_orderkey",
              "bits": bloom.BLOOM_MAX_BITS, "k": bloom.BLOOM_K,
              "child": probe.op}
        return plan, op
    return plan, next(p.op for p in pipes if p.kernel == kernel)


def _leaf_shapes(plan, catalog, leaf_op, rows, sharding):
    """(columns, mask) ShapeDtypeStructs of one leaf block."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    if leaf_op["t"] == "bloom_words":
        return ({"words": sds((bloom.BLOOM_MAX_BITS // 32,), np.uint32)},
                sds((1,), bool))
    if leaf_op["t"] == "scan_table":
        meta = catalog.tables[leaf_op["table"]]
        types = {c.name: c.dtype for c in meta.schema}
        names = leaf_op["columns"]
    else:
        src = next(p for p in plan.pipelines.values()
                   if p.sem_hash == leaf_op["source"])
        types = {s["name"]: s["dtype"] for s in src.output_schema}
        names = list(types)
    return ({c: sds((rows,), np.dtype(types[c])) for c in names},
            sds((rows,), bool))


@pytest.mark.parametrize("kernel", list(SQLS))
def test_dispatch_kernel_compiles_for_v5e(kernel, tpch_store, one_chip,
                                          mosaic):
    store, catalog = tpch_store
    plan, op = _pipeline(store, catalog, kernel)
    lowered = lower.lower_fragment(op)
    assert lowered is not None and lowered.kernel == kernel
    tiling = lowered.tiling
    resident = kernel in ("sort_agg", "topk")
    blocks = {}
    for i, (leaf_id, leaf_op) in enumerate(lowered.leaves):
        build_side = kernel == "join_probe_agg" and i == 1
        rows = (tiling.resident_rows if resident or build_side
                else STREAM_ROWS)
        blocks[leaf_id] = _leaf_shapes(plan, catalog, leaf_op, rows,
                                       one_chip)
    # the program as the fragment runs it: its output compacted
    compiled = jax.jit(fragment._compacting(lowered.fn)).lower(
        blocks).compile()
    text = compiled.as_text()
    # the fused kernel is in the program, not the XLA fallback
    assert "tpu_custom_call" in text
    # the compaction is slices and selects: no emulated 64-bit loop
    assert " while(" not in text, kernel
