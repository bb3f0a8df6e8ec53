"""Public wrappers for the Pallas kernels the engine dispatches to.

On a TPU the kernels compile to Mosaic; on any other backend they run in
Pallas interpret mode — the kernel body runs as traced jnp on the host,
validating semantics. ``kernels.common.interpret_mode`` decides which,
so call sites never change.
"""

from __future__ import annotations

from repro.kernels import bloom as _bloom
from repro.kernels import filter_agg as _fa
from repro.kernels import groupby_onehot as _go
from repro.kernels import join_probe as _jp
from repro.kernels import sort_agg as _sa
from repro.kernels import topk as _tk
from repro.kernels import common


# -- generic fused kernels (engine dispatch targets) --------------------------
#
# Not jitted here: the expression closures aren't stable jit keys, and the
# call sites — the lowered fragment programs built by ``repro.exec.lower``
# — are already traced inside one jitted program per fragment op tree.

def fused_filter_agg(columns: dict, mask, *, pred, aggs,
                     block: int = _fa.BLOCK_ROWS):
    return _fa.fused_filter_agg(columns, mask, pred=pred, aggs=aggs,
                                block=block, interpret=common.interpret_mode())


def fused_groupby(columns: dict, mask, *, pred, gid_fn, aggs,
                  n_groups: int, block: int = _go.BLOCK_ROWS):
    return _go.fused_groupby(columns, mask, pred=pred, gid_fn=gid_fn,
                             aggs=aggs, n_groups=n_groups, block=block,
                             interpret=common.interpret_mode())


def fused_join_probe_agg(probe_cols: dict, probe_mask, sorted_keys,
                         sorted_payload: dict, *, probe_key: str, pred,
                         gid_fn, aggs, n_groups: int, block: int,
                         build_key: str | None = None):
    return _jp.fused_join_probe_agg(
        probe_cols, probe_mask, sorted_keys, sorted_payload,
        probe_key=probe_key, pred=pred, gid_fn=gid_fn, aggs=aggs,
        n_groups=n_groups, block=block, build_key=build_key,
        interpret=common.interpret_mode())


def fused_sort_agg(columns: dict, mask, *, group_cols, pred, aggs):
    return _sa.fused_sort_agg(columns, mask, group_cols=group_cols,
                              pred=pred, aggs=aggs,
                              interpret=common.interpret_mode())


def fused_bloom_filter(columns: dict, mask, *, pred, key: str, words,
                       bits: int, k: int, block: int):
    return _bloom.fused_bloom_filter(
        columns, mask, pred=pred, key=key, words=words, bits=bits, k=k,
        block=block, interpret=common.interpret_mode())


def fused_topk(columns: dict, mask, *, pred, sort_keys, limit: int):
    return _tk.fused_topk(columns, mask, pred=pred, sort_keys=sort_keys,
                          limit=limit, interpret=common.interpret_mode())


def join_key_dtype():
    """Key lane dtype the fused join/sort kernels will use on this
    backend — exposed so the XLA build-side prepass matches."""
    return common.key_dtype(common.interpret_mode())
