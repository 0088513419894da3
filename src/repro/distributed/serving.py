"""Mesh-sharded paged serving: head-parallel page pools (DESIGN.md §17).

Strategy — head-sharded tensor parallelism first:

* **Pools are partitioned over KV heads.** Every page-pool buffer carries
  its head axis at ``ndim - 3`` — ``(PP, H, g, ·)`` per-layer,
  ``(L, PP, H, g, ·)`` stacked, ``(S, H, g, d)`` residual — mirroring the
  "page axis at ``ndim - 4``" convention in ``copy_pool_pages``. That axis
  is sharded over the mesh's ``model`` axis; ``lengths`` (and anything
  below rank 4) is replicated.
* **PageAllocator and the page table stay host-side and shard-agnostic.**
  Page ids are identical on every shard, so the allocator's refcount /
  COW / adopt lifecycles never see the mesh; only pool *payload* is
  partitioned (asserted in tests/test_prefix_cache.py).
* **Kernels run per-shard under shard_map.** Per-KV-head attention is
  embarrassingly parallel: each shard walks the same page table over its
  head slice of the pools and produces its head slice of the output —
  bit-identical per head, no collectives. The GQA query→KV head mapping
  survives sharding because both head counts divide the axis, so each
  shard's contiguous query-head block maps onto its contiguous KV block.
* **GQA fallback.** When ``num_kv_heads`` (or ``num_heads``) does not
  divide the model axis, dispatch falls back to the replicated
  single-device path — same math, no partitioning.
* **Context-parallel decode** (:func:`context_parallel_decode`) is the
  complementary strategy from distributed/sharding.py's decode-cache
  notes: shard the *page-table columns* instead, score each shard's slice
  of the context locally, and merge the online-softmax ``m/l/acc``
  carries with the psum collectives in distributed/collectives.py (or
  all-gather the LUT score rows for a bit-identical merge). It is the
  reference/oracle for the stats-merge collectives; the serving hot path
  is head-sharded.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import kv_cache as kvc
from repro.core import paged_cache as pgc
from repro.core import quantizers as qz
from repro.distributed import ctx
from repro.distributed.collectives import (allgather_concat, finalize_softmax,
                                           merge_softmax_stats, softmax_stats)

Array = jax.Array

MODEL_AXIS = "model"


# ---------------------------------------------------------------------------
# Head-axis partition specs
# ---------------------------------------------------------------------------


def _pool_heads(cache: pgc.PagedKVCache) -> int:
    """KV head count read at the canonical pool head axis (``ndim - 3``) —
    works on both per-layer (PP, H, g, ·) and stacked (L, PP, H, g, ·)
    caches, unlike the ``num_kv_heads`` property (shape[1])."""
    kc = cache.key_codes
    return kc.shape[kc.ndim - 3]


def leaf_pspec(x: Array, num_kv_heads: int, axis: str = MODEL_AXIS) -> P:
    """PartitionSpec for one pool leaf: the head axis (``ndim - 3``) over
    ``axis`` when it is actually the head axis; everything else (lengths,
    scalars) replicated."""
    nd = x.ndim
    if nd >= 4 and x.shape[nd - 3] == num_kv_heads:
        spec: list = [None] * nd
        spec[nd - 3] = axis
        return P(*spec)
    return P()


def cache_pspecs(cache: pgc.PagedKVCache, axis: str = MODEL_AXIS) -> Any:
    """Pytree of PartitionSpecs matching ``cache`` (per-layer or stacked):
    pool head axes over ``axis``, slot-indexed state replicated."""
    h = _pool_heads(cache)
    return jax.tree_util.tree_map(lambda x: leaf_pspec(x, h, axis), cache)


def paged_state_shardings(state: Any, mesh: Mesh,
                          axis: str = MODEL_AXIS) -> Any:
    """NamedSharding tree for a (tuple of per-segment) stacked
    PagedKVCache state: head-partitioned pools where the KV head count
    divides the mesh axis, fully replicated otherwise."""
    model_n = mesh.shape.get(axis, 1)
    segs = state if isinstance(state, tuple) else (state,)

    def seg_shardings(c):
        h = _pool_heads(c)
        if h % model_n:
            return jax.tree_util.tree_map(
                lambda x: NamedSharding(mesh, P()), c)
        return jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, leaf_pspec(x, h, axis)), c)

    out = tuple(seg_shardings(c) for c in segs)
    return out if isinstance(state, tuple) else out[0]


def shard_paged_state(state: Any, mesh: Mesh, axis: str = MODEL_AXIS) -> Any:
    """Place a paged decode state on ``mesh`` with head-partitioned pools."""
    return jax.device_put(state, paged_state_shardings(state, mesh, axis))


# ---------------------------------------------------------------------------
# Head-sharded kernels (the serving hot path)
# ---------------------------------------------------------------------------


def _head_divisible(cache: pgc.PagedKVCache, q_heads: int, mesh: Mesh,
                    axis: str) -> bool:
    n = mesh.shape.get(axis, 0)
    return n > 0 and _pool_heads(cache) % n == 0 and q_heads % n == 0


def sharded_paged_decode_attention(cache: pgc.PagedKVCache, q: Array,
                                   page_table: Array, *, mesh: Mesh,
                                   axis: str = MODEL_AXIS,
                                   scale: float | None = None,
                                   backend: str = "jnp") -> Array:
    """Head-sharded :func:`pgc.paged_decode_attention`: each shard runs the
    full decode dispatch over its KV-head slice of the pools and the
    matching query-head block — bit-identical per head to the
    single-device path (no cross-head math anywhere in the kernel).
    Falls back to the replicated path when heads don't divide the axis.
    """
    if not _head_divisible(cache, q.shape[1], mesh, axis):
        return pgc.paged_decode_attention(cache, q, page_table, scale=scale,
                                          backend=backend)

    def body(c, qq, pt):
        return pgc.paged_decode_attention(c, qq, pt, scale=scale,
                                          backend=backend)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(cache_pspecs(cache, axis), P(None, axis, None),
                  P(None, None)),
        out_specs=P(None, axis, None), check_vma=False)
    return fn(cache, q, page_table)


def sharded_paged_prefill_attention(cache: pgc.PagedKVCache, q: Array,
                                    k_chunk: Array, v_chunk: Array,
                                    page_row: Array, start: Array,
                                    chunk_len: Array, *, mesh: Mesh,
                                    axis: str = MODEL_AXIS,
                                    scale: float | None = None,
                                    backend: str = "jnp") -> Array:
    """Head-sharded :func:`pgc.paged_prefill_attention` (the chunk-prefill
    twin of :func:`sharded_paged_decode_attention`)."""
    if not _head_divisible(cache, q.shape[1], mesh, axis):
        return pgc.paged_prefill_attention(cache, q, k_chunk, v_chunk,
                                           page_row, start, chunk_len,
                                           scale=scale, backend=backend)

    def body(c, qq, kk, vv, row, st, cl):
        return pgc.paged_prefill_attention(c, qq, kk, vv, row, st, cl,
                                           scale=scale, backend=backend)

    h4 = P(None, axis, None, None)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(cache_pspecs(cache, axis), h4, h4, h4, P(None), P(), P()),
        out_specs=h4, check_vma=False)
    return fn(cache, q, k_chunk, v_chunk, page_row,
              jnp.asarray(start, jnp.int32), jnp.asarray(chunk_len, jnp.int32))


def _active_head_axis(cache: pgc.PagedKVCache, q_heads: int):
    """(mesh, axis) when the installed sharding context maps ``kv_heads``
    onto a mesh axis that divides both head counts; (None, None) otherwise
    (no context, GQA fallback, or a non-Mesh test double)."""
    mesh = ctx.current_mesh()
    rules = ctx.current_rules() or {}
    if not isinstance(mesh, Mesh):
        return None, None
    axis = rules.get("kv_heads")
    if not isinstance(axis, str) or axis not in mesh.shape:
        return None, None
    if not _head_divisible(cache, q_heads, mesh, axis):
        return None, None
    return mesh, axis


def dispatch_paged_decode_attention(cache: pgc.PagedKVCache, q: Array,
                                    page_table: Array, *,
                                    scale: float | None = None,
                                    backend: str = "jnp") -> Array:
    """Context-aware decode dispatch: head-sharded shard_map when the
    engine installed a mesh whose ``kv_heads`` rule divides the heads,
    the plain single-device path otherwise. Model code calls this so it
    stays mesh-agnostic (same contract as ctx.shard)."""
    mesh, axis = _active_head_axis(cache, q.shape[1])
    if mesh is None:
        return pgc.paged_decode_attention(cache, q, page_table, scale=scale,
                                          backend=backend)
    return sharded_paged_decode_attention(cache, q, page_table, mesh=mesh,
                                          axis=axis, scale=scale,
                                          backend=backend)


def dispatch_paged_prefill_attention(cache: pgc.PagedKVCache, q: Array,
                                     k_chunk: Array, v_chunk: Array,
                                     page_row: Array, start: Array,
                                     chunk_len: Array, *,
                                     scale: float | None = None,
                                     backend: str = "jnp") -> Array:
    """Context-aware chunk-prefill dispatch (see
    :func:`dispatch_paged_decode_attention`)."""
    mesh, axis = _active_head_axis(cache, q.shape[1])
    if mesh is None:
        return pgc.paged_prefill_attention(cache, q, k_chunk, v_chunk,
                                           page_row, start, chunk_len,
                                           scale=scale, backend=backend)
    return sharded_paged_prefill_attention(cache, q, k_chunk, v_chunk,
                                           page_row, start, chunk_len,
                                           mesh=mesh, axis=axis, scale=scale,
                                           backend=backend)


# ---------------------------------------------------------------------------
# Context-parallel decode (page-table columns sharded; stats-merge oracle)
# ---------------------------------------------------------------------------


def _block_scores_values(cache: pgc.PagedKVCache, q: Array, pt_block: Array,
                         pos0: Array, scale: float | None):
    """Masked scores + value rows for a contiguous block of page-table
    columns whose first token sits at global position ``pos0`` (a page
    multiple).

    Per slot, a token position is scored from exactly one source: codec
    codes when it is flushed into a page (``pos < flushed``), the fp
    residual row when it is in the open group (``flushed <= pos < len``;
    the residual is slot-indexed and replicated, but only the shard owning
    the open group's page *column* scores it — value rows are token-major
    in that page, so values and scores stay co-located on one shard).
    Everything else is ``NEG_INF`` / zeroed, so a block with no live
    positions yields a degenerate (zero-weight) stats carry.
    """
    cfg, codec, lay = cache.cfg, cache.codec, cache.layout
    s, n = pt_block.shape
    hkv = cache.num_kv_heads
    hq, d = q.shape[1], q.shape[-1]
    qpk = hq // hkv
    g = lay.page_size
    t_loc = n * g
    scale = scale if scale is not None else d ** -0.5
    pvalid = (pt_block >= 0) & (pt_block < lay.num_pages)

    def masked(x):  # (PP, H, a, b) -> (S, H, N, a, b), invalid pages zeroed
        gathered = pgc._gather_pages(x, pt_block)
        return jnp.where(pvalid[:, None, :, None, None], gathered,
                         jnp.zeros((), x.dtype))

    def flat(x):  # (S, H, N, g, ·) -> (S, H, N*g, ·)
        return x.reshape(s, hkv, t_loc, x.shape[-1])

    qf = (q.astype(jnp.float32) * scale).reshape(s, hkv, qpk, d)
    key_codes = masked(cache.key_codes)
    key_scales = {kk: masked(vv) for kk, vv in cache.key_scales.items()}
    if not cache.grouped:
        key_codes = flat(key_codes)
        key_scales = {kk: flat(vv) for kk, vv in key_scales.items()}
    s_pages = codec.scores(cfg, qf, key_codes, key_scales)  # (S,Hkv,qpk,Tl)

    pos = pos0 + jnp.arange(t_loc, dtype=jnp.int32)          # (T_loc,)
    length = cache.lengths[:, None]                          # (S, 1)
    if cache.grouped:
        flushed = (cache.lengths // g * g)[:, None]
        res = cache.key_residual.astype(jnp.float32)         # (S, H, g, d)
        s_res = jnp.einsum("shqd,shgd->shqg", qf, res)       # (S,Hkv,qpk,g)
        s_res = jnp.tile(s_res, (1, 1, 1, n))                # row == pos % g
        in_page = (pos[None, :] < flushed)                   # (S, T_loc)
        in_res = (pos[None, :] >= flushed) & (pos[None, :] < length)
        scores = jnp.where(in_page[:, None, None, :], s_pages,
                           jnp.where(in_res[:, None, None, :], s_res,
                                     kvc.NEG_INF))
    else:
        live = pos[None, :] < length
        scores = jnp.where(live[:, None, None, :], s_pages, kvc.NEG_INF)

    if cfg.value_bits > 0:
        v_tilde = qz.decode_values(qz.QuantizedValues(
            codes=flat(masked(cache.value_codes)),
            scale=flat(masked(cache.value_scale)),
            zero=flat(masked(cache.value_zero)), bits=cfg.value_bits))
    else:
        v_tilde = flat(masked(cache.value_fp)).astype(jnp.float32)
    v_tilde = v_tilde.reshape(s, hkv, 1, t_loc, -1)          # qpk broadcast
    return scores, v_tilde


def context_parallel_decode(cache: pgc.PagedKVCache, q: Array,
                            page_table: Array, *, mesh: Mesh,
                            axis: str = MODEL_AXIS, merge: str = "psum",
                            scale: float | None = None) -> Array:
    """Context-parallel (page-column-sharded) decode reference.

    Each shard scores its contiguous slice of every slot's page-table row
    — quantized pages through the codec score path, the open group through
    the fp residual — and the per-shard online-softmax ``(m, l, acc)``
    partials are merged across the mesh axis:

    * ``merge="psum"`` — pmax/psum of the rescaled carries
      (:func:`merge_softmax_stats`); fp-tolerance vs the single-device
      path (reduction order differs), degenerate shards guarded.
    * ``merge="allgather"`` — LUT score rows + value rows all-gathered in
      mesh order, softmax computed on the reconstructed full row
      (:func:`allgather_concat`); bit-identical merge.

    Returns (S, Hq, d). This is the oracle for the stats-merge
    collectives; the serving hot path shards heads instead.
    """
    if merge not in ("psum", "allgather"):
        raise ValueError(f"unknown merge {merge!r}")
    world = mesh.shape[axis]
    s, n = page_table.shape
    n_pad = -(-n // world) * world
    if n_pad != n:
        pad = jnp.full((s, n_pad - n), -1, page_table.dtype)
        page_table = jnp.concatenate([page_table, pad], axis=1)
    g = cache.layout.page_size
    hq = q.shape[1]

    def body(c, qq, pt):
        r = jax.lax.axis_index(axis)
        pos0 = r * pt.shape[1] * g
        scores, values = _block_scores_values(c, qq, pt, pos0, scale)
        if merge == "allgather":
            scores = allgather_concat(scores, axis, axis=-1)
            values = allgather_concat(values, axis, axis=-2)
            _, l, acc = softmax_stats(scores, values)
        else:
            m, l, acc = softmax_stats(scores, values)
            _, l, acc = merge_softmax_stats(m, l, acc, axis)
        out = finalize_softmax(l, acc)                  # (S, Hkv, qpk, d)
        return out.reshape(s, hq, -1).astype(qq.dtype)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda x: P(), cache), P(),
                  P(None, axis)),
        out_specs=P(), check_vma=False)
    return fn(cache, q, page_table)
