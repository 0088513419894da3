"""Pallas TPU kernel: page-native fused PolarQuant chunk-prefill attention.

The jnp chunked-prefill path (``paged_cache.chunk_prefill_attention``)
gathers the slot's *entire page pool* per chunk (``pool[page_row]``,
O(capacity) HBM traffic) and spills full ``(Hq, Tc, N*g)`` fp32 score
tensors before one dense softmax — so long-prompt TTFT degrades with pool
capacity exactly like decode did before the page-native decode kernel.
This kernel is the prefill twin of ``kernels/paged_decode.py``: one
``pallas_call`` computes a whole chunk's attention directly against the
quantized prefix pages plus the chunk's own fp keys, with nothing dense
ever materialized:

    per kv head j, row block r of the folded queries, prefix page k:
        codes/stats/values <- pool[row[k]]            (index-map walk)
        K~ = rho~ (cos, sin)(theta~ - pi)             (page dequant, VPU)
        scores = q_fold[r] @ K~^T                     (MXU)
        m, l, acc online-softmax update               (VMEM carry)
    final grid step (k == N):
        s = q_fold[r] @ k_chunk^T, causal-masked      (MXU, fp)
        same m/l/acc update, then out = acc / l       (flash finish)

The chunk's ``Tc`` query rows ride folded onto the query-head axis
(``QT = (Hq/Hkv) * Tc`` rows per kv head, row = qh * Tc + t) — the same
folding ``chunk_prefill_attention`` uses — and a grid axis tiles those
rows into blocks of at most ``block_rows``, so VMEM use is bounded by the
block and not by the chunk. Each page is dequantized once into fp keys
by ``polar_attention._dequant_page_keys``, the page decoder the decode
kernel shares, and scored on the MXU. The angle LUT
(``polar_attention._lut_scores_block``) computes the same sum,
``sum_j rho_j (q_x[j] cos_j + q_y[j] sin_j)``, in a different order.

Causality needs no masking on the prefix steps (every chunk token sits
at position ``start + t`` ≥ ``start`` > any prefix position); within the
chunk the final step applies the standard triangular mask by
``t = row % Tc``. Because the fp causal tile shares the *same*
online-softmax carry as the prefix steps (``flash_prefill.py``'s m/l/acc
structure), the kernel emits the complete normalized chunk output in one
pass — no partial merge on the host.

Dead grid steps (pages at or past ``start // g``) clamp their index maps
to the last live prefix page, exactly as in ``paged_decode``: repeated
block indices skip the redundant DMA, and the scratch page is never
dereferenced while the slot has any live page. Masked lanes contribute
exact zeros (p == 0 and value rows zeroed under the mask), so stale pool
garbage cannot leak through ``0 * NaN``.

``start`` must be page-aligned (the chunked-prefill invariant): the
cached prefix ``[0, start)`` is fully flushed into pages, so there is no
fp-residual term.
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


def _dot_t(a, b):
    """a (M, K) @ b (N, K)^T -> (M, N) in fp32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _paged_prefill_kernel(row_ref, info_ref, q_ref, kc_ref, vc_ref,
                          codes_ref, rs_ref, rz_ref, ts_ref, tz_ref,
                          v_ref, vs_ref, vz_ref, out_ref,
                          m_ref, l_ref, acc_ref, *, t_bits: int,
                          quantized_values: bool, page_size: int,
                          chunk_tokens: int, n_pages: int, block_rows: int):
    r, k = pl.program_id(1), pl.program_id(2)
    g = page_size
    start = info_ref[0]
    clen = info_ref[1]

    @pl.when(k == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # (R, d), scaled
    p_half = q.shape[1] // 2

    def _online(scores, mask, v):
        # one flash update of the shared m/l/acc carry.
        # scores/mask: (R, L); v: (L, d) with dead rows already zeroed.
        m_prev = m_ref[...]                                # (R, 1)
        m_new = jnp.maximum(m_prev,
                            jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)  # (R, L)
        corr = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(k < n_pages)
    def _prefix_page():
        kx, ky = _dequant_page_keys(
            codes_ref[0, 0],
            rs_ref[0, 0].astype(jnp.float32),
            rz_ref[0, 0].astype(jnp.float32),
            ts_ref[0, 0].astype(jnp.float32),
            tz_ref[0, 0].astype(jnp.float32), t_bits)     # (g, P) each
        scores = _dot_t(q[:, :p_half], kx) + _dot_t(q[:, p_half:], ky)
        pos = k * g + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        mask = pos < start                                 # (R, g)
        scores = jnp.where(mask, scores, NEG_INF)
        if quantized_values:
            v = (to_f32(v_ref[0, 0]) * vs_ref[0, 0].astype(jnp.float32)
                 + vz_ref[0, 0].astype(jnp.float32))       # (g, d)
        else:
            v = v_ref[0, 0].astype(jnp.float32)
        vpos = k * g + jax.lax.broadcasted_iota(jnp.int32, (g, 1), 0)
        v = jnp.where(vpos < start, v, 0.0)
        _online(scores, mask, v)

    @pl.when(k == n_pages)
    def _chunk_tile():
        kc = kc_ref[0].astype(jnp.float32)                 # (Tc, d)
        s = _dot_t(q, kc)                                  # (R, Tc)
        row = r * block_rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        t_q = row % chunk_tokens
        t_k = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (t_q >= t_k) & (t_k < clen)
        s = jnp.where(mask, s, NEG_INF)
        vc = vc_ref[0].astype(jnp.float32)                 # (Tc, d)
        vrow = jax.lax.broadcasted_iota(jnp.int32, (chunk_tokens, 1), 0)
        vc = jnp.where(vrow < clen, vc, 0.0)
        _online(s, mask, vc)
        # the flash finish: every query row has at least its own diagonal
        # lane unmasked once the chunk tile lands, so l > 0 for real rows;
        # padded rows (t >= clen) stay fully masked and the l == 0 guard
        # keeps them finite. The output block stays resident across k and
        # is written back once, after this last step.
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out_ref[0] = acc_ref[...] / l_safe


def _row_block(qt: int, block_rows: int) -> int:
    """Rows per grid block: the largest divisor of ``qt`` within
    ``block_rows`` that is a multiple of 8 (the fp32 sublane tile), or all
    of ``qt`` when it has no such divisor."""
    for rb in range(min(block_rows, qt), 7, -1):
        if qt % rb == 0 and rb % 8 == 0:
            return rb
    return qt


@functools.partial(jax.jit, static_argnames=("r_bits", "t_bits",
                                             "block_rows", "interpret"))
def polar_paged_prefill_grouped(
    q: Array, k_chunk: Array, v_chunk: Array, codes: Array, rs: Array,
    rz: Array, ts: Array, tz: Array, values, vscale, vzero,
    page_row: Array, start: Array, chunk_len: Array, *,
    r_bits: int = 4, t_bits: int = 4, block_rows: int = 512,
    interpret: bool,
):
    """One prefill chunk's fused attention, straight off the pools.

    q: (Hkv, QT, d) — chunk queries folded onto the head axis
    (``QT = (Hq/Hkv) * Tc``, row = qh * Tc + t) and ALREADY scaled by the
    softmax scale. k_chunk/v_chunk: (Hkv, Tc, d) the chunk's own fp
    keys/values. codes: (PP, Hkv, g, P) page pool with stats
    (PP, Hkv, 1, P); values: (PP, Hkv, g, d) fp rows or uint8 codes with
    vscale/vzero (PP, Hkv, g, 1) (pass vscale=None for fp values).
    page_row: (N,) int32 — the slot's table row (may be width-sliced to
    the live pages); start: () int32 page-aligned chunk offset;
    chunk_len: () int32 real tokens in the chunk. ``block_rows`` bounds
    the folded query rows one grid step holds (see :func:`_row_block`).

    Returns (Hkv, QT, d) fp32 — the complete normalized chunk output.
    """
    hkv, qt, d = q.shape
    _, _, g, p = codes.shape
    tc = k_chunk.shape[1]
    n = page_row.shape[0]
    rb = _row_block(qt, block_rows)
    quantized_values = vscale is not None
    page_row = page_row.astype(jnp.int32)
    info = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(chunk_len, jnp.int32)])

    def page_map(j, r, k, row_ref, info_ref):
        # clamp dead grid steps (k >= start // g, incl. the chunk step) to
        # the last live prefix page: repeated block indices skip the DMA
        live = jnp.maximum(info_ref[0] // g, 1)
        return (row_ref[jnp.minimum(k, live - 1)], j, 0, 0)

    def head_map(j, r, k, row_ref, info_ref):
        return (j, 0, 0)

    def rows_map(j, r, k, row_ref, info_ref):
        return (j, r, 0)

    kern = functools.partial(
        _paged_prefill_kernel, t_bits=t_bits,
        quantized_values=quantized_values, page_size=g, chunk_tokens=tc,
        n_pages=n, block_rows=rb)

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
        zmap = lambda j, r, k, row_ref, info_ref: (0, 0, 0, 0)
        v_specs = [pl.BlockSpec((1, 1, g, d), page_map),
                   pl.BlockSpec((1, 1, 1, 1), zmap),
                   pl.BlockSpec((1, 1, 1, 1), zmap)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(hkv, qt // rb, n + 1),
        in_specs=[
            pl.BlockSpec((1, rb, d), rows_map),
            pl.BlockSpec((1, tc, d), head_map),
            pl.BlockSpec((1, tc, d), head_map),
            codes_spec,
            stat_spec, stat_spec, stat_spec, stat_spec,
            *v_specs,
        ],
        out_specs=pl.BlockSpec((1, rb, d), rows_map),
        scratch_shapes=[
            pltpu.VMEM((rb, 1), jnp.float32),
            pltpu.VMEM((rb, 1), jnp.float32),
            pltpu.VMEM((rb, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hkv, qt, d), jnp.float32),
        interpret=interpret,
    )(page_row, info, q, k_chunk, v_chunk, codes, rs, rz, ts, tz, *v_in)
