"""Pallas TPU kernel: page-native fused PolarQuant decode attention.

The gathered path (``paged_cache.gather_view`` + the dense fused kernel)
re-materializes a dense copy of every slot's *entire capacity* — codes,
stats and values — in HBM on every decode step: an O(S·N·g·(P+d)) round
trip that grows with the pool capacity and dwarfs the LUT win at long
context. This kernel removes the copy entirely: its grid iterates
``(slot, kv_head, page)`` and the BlockSpec index maps dereference the
scalar-prefetched ``(S, N)`` page table, so every block load reads the
quantized page pools *in place* (vLLM-style paged attention):

    per (s, h) slot/KV head, for each page n of the slot's table row:
        codes/stats/values  <- pool[table[s, n]]        (index-map walk)
        K~     = rho~ (cos, sin)(theta~ - pi)           (page dequant, VPU)
        scores = q @ K~^T                               (MXU, fp32)
        m, l   = online-softmax update                  (VMEM scratch)
        acc   += exp(s - m) @ V_n                       (MXU)

The page is decoded once per grid step for all ``Qh`` query heads of the
KV head, by ``polar_attention._dequant_page_keys`` (the page decoder the
prefill kernel shares), so the VPU work of a step does not grow with the
query group. The score product runs at ``Precision.HIGHEST``: the MXU's
split passes keep the scores as accurate as fp32, the precision of the
angle LUT (``polar_attention._lut_scores_block``), which computes the
same sum per query head in a select tree of ``2**t_bits`` passes.

Per-slot lengths mask dead pages: grid steps past ``flushed[s] // g``
contribute nothing, and their index maps *clamp to the slot's last live
page* — consecutive grid steps then map to the same block, which the
Pallas pipeline recognizes and skips the redundant DMA. Clamping also
means the scratch page (stale masked-write garbage) is never read when a
slot has any live page at all; value rows are additionally zeroed under
the token mask so even a poisoned pool page cannot leak NaNs through a
zero-probability lane (``0 * NaN``).

Outputs are the unnormalized flash partials ``(acc, m, l)`` over the
grouped (flushed) tokens; the wrapper in ``kernels/ops.py`` merges the fp
residual segment exactly, fetching the residual value rows from the one
page currently being filled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.polar_attention import _dequant_page_keys, to_f32

Array = jax.Array
NEG_INF = -1e30


def _dot_t_highest(a, b):
    """a (M, K) @ b (N, K)^T -> (M, N) in fp32 at ``Precision.HIGHEST``:
    the MXU's split passes keep the product as accurate as fp32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _paged_attn_kernel(table_ref, flushed_ref, q_ref, codes_ref, rs_ref,
                       rz_ref, ts_ref, tz_ref, v_ref, vs_ref, vz_ref,
                       out_ref, m_out_ref, l_out_ref, m_ref, l_ref, acc_ref,
                       *, t_bits: int, quantized_values: bool,
                       page_size: int):
    s, n = pl.program_id(0), pl.program_id(2)
    g = page_size

    @pl.when(n == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                    # (Qh, d)
    kx, ky = _dequant_page_keys(
        codes_ref[0, 0],
        rs_ref[0, 0].astype(jnp.float32),
        rz_ref[0, 0].astype(jnp.float32),
        ts_ref[0, 0].astype(jnp.float32),
        tz_ref[0, 0].astype(jnp.float32), t_bits)         # (g, P) each
    p_half = q.shape[1] // 2
    scores = (_dot_t_highest(q[:, :p_half], kx)
              + _dot_t_highest(q[:, p_half:], ky))         # (Qh, g)

    flushed = flushed_ref[s]
    pos = n * g + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    mask = pos < flushed                                   # (Qh, g)
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_ref[...]                                    # (Qh, 1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)      # (Qh, g)
    corr = jnp.exp(m_prev - m_new)

    if quantized_values:
        v = (to_f32(v_ref[0, 0]) * vs_ref[0, 0].astype(jnp.float32)
             + vz_ref[0, 0].astype(jnp.float32))           # (g, d)
    else:
        v = v_ref[0, 0].astype(jnp.float32)
    # zero dead rows: a masked lane's p is exactly 0, but 0 * NaN (stale
    # scratch-page garbage) would still poison the MXU accumulation
    vpos = n * g + jax.lax.broadcasted_iota(jnp.int32, (g, 1), 0)
    v = jnp.where(vpos < flushed, v, 0.0)

    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    # Final carry lands in the (s, h)-indexed output tiles on the last page;
    # intermediate writes are overwritten (n is the innermost grid dim).
    out_ref[0, 0] = acc_ref[...]
    m_out_ref[0, 0] = m_ref[...]
    l_out_ref[0, 0] = l_ref[...]


@functools.partial(jax.jit, static_argnames=("r_bits", "t_bits", "interpret"))
def polar_paged_decode_grouped(
    q: Array, codes: Array, rs: Array, rz: Array, ts: Array, tz: Array,
    values, vscale, vzero, page_table: Array, flushed: Array, *,
    r_bits: int = 4, t_bits: int = 4, interpret: bool,
):
    """Fused flash-decode over the grouped segment, straight off the pools.

    q: (S, Hkv, Qh, d) — ALREADY scaled by the softmax scale.
    codes: (PP, Hkv, g, P) page pool; stats rs/rz/ts/tz: (PP, Hkv, 1, P).
    values: (PP, Hkv, g, d) fp rows, or uint8 codes with vscale/vzero
    (PP, Hkv, g, 1) (pass vscale=None for fp values).
    page_table: (S, N) int32 — N may be a *sliced* width covering the live
    pages only (the serve engines bucket it); flushed: (S,) int32 valid
    grouped tokens per slot (a multiple of the page size).

    Returns (out (S,Hkv,Qh,d), m (S,Hkv,Qh), l (S,Hkv,Qh)) — unnormalized
    flash partials (see module docstring).
    """
    s, hkv, qh, d = q.shape
    _, _, g, p = codes.shape
    n = page_table.shape[1]
    quantized_values = vscale is not None
    page_table = page_table.astype(jnp.int32)
    flushed = jnp.broadcast_to(
        jnp.asarray(flushed, jnp.int32).reshape(-1), (s,))

    def page_map(i, j, k, table_ref, flushed_ref):
        # clamp dead grid steps to the slot's last live page: repeated block
        # indices skip the DMA, and the scratch page is never dereferenced
        # while the slot has live pages at all
        live = jnp.maximum(flushed_ref[i] // g, 1)
        return (table_ref[i, jnp.minimum(k, live - 1)], j, 0, 0)

    kern = functools.partial(
        _paged_attn_kernel, t_bits=t_bits,
        quantized_values=quantized_values, page_size=g)

    codes_spec = pl.BlockSpec((1, 1, g, p), page_map)
    stat_spec = pl.BlockSpec((1, 1, 1, p), page_map)
    if quantized_values:
        v_in = (values, vscale, vzero)
        v_specs = [pl.BlockSpec((1, 1, g, d), page_map),
                   pl.BlockSpec((1, 1, g, 1), page_map),
                   pl.BlockSpec((1, 1, g, 1), page_map)]
    else:
        dummy = jnp.zeros((1, 1, 1, 1), jnp.float32)
        v_in = (values, dummy, dummy)
        zmap = lambda i, j, k, t, f: (0, 0, 0, 0)
        v_specs = [pl.BlockSpec((1, 1, g, d), page_map),
                   pl.BlockSpec((1, 1, 1, 1), zmap),
                   pl.BlockSpec((1, 1, 1, 1), zmap)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, hkv, n),
        in_specs=[
            pl.BlockSpec((1, 1, qh, d), lambda i, j, k, t, f: (i, j, 0, 0)),
            codes_spec,
            stat_spec, stat_spec, stat_spec, stat_spec,
            *v_specs,
        ],
        # m/l carry a trailing unit axis: a block's last two dims must
        # tile (8, 128) or span the array, which (1, qh) would not
        out_specs=[
            pl.BlockSpec((1, 1, qh, d), lambda i, j, k, t, f: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, qh, 1), lambda i, j, k, t, f: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, qh, 1), lambda i, j, k, t, f: (i, j, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((qh, 1), jnp.float32),
            pltpu.VMEM((qh, 1), jnp.float32),
            pltpu.VMEM((qh, d), jnp.float32),
        ],
    )
    out, m, l = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s, hkv, qh, d), jnp.float32),
            jax.ShapeDtypeStruct((s, hkv, qh, 1), jnp.float32),
            jax.ShapeDtypeStruct((s, hkv, qh, 1), jnp.float32),
        ],
        interpret=interpret,
    )(page_table, flushed, q, codes, rs, rz, ts, tz, *v_in)
    return out, m[..., 0], l[..., 0]
