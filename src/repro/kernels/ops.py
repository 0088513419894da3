"""Public jit'd wrappers around the Pallas kernels with ref fallbacks.

``backend``:
  * ``"pallas"``    — pl.pallas_call targeting TPU (interpret=False)
  * ``"interpret"`` — same kernel body run by the Pallas interpreter (how
                       the CPU test suite covers the kernel bodies)
  * ``"ref"``       — pure-jnp oracle (fastest on CPU, used inside jitted
                       serving steps and the dry-run)

`polar_decode_attention_full` is the end-to-end decode-attention entry:
kernel partials over the grouped cache segment merged exactly with the fp
residual segment (associative online-softmax merge).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_mod
from repro.kernels.polar_qk import polar_qk_scores as _qk_pallas
from repro.kernels.polar_encode import polar_encode as _encode_pallas
from repro.kernels.polar_attention import (
    polar_decode_attention_grouped as _attn_pallas,
)
from repro.kernels.paged_decode import (
    polar_paged_decode_grouped as _paged_attn_pallas,
)
from repro.kernels.paged_prefill import (
    polar_paged_prefill_grouped as _paged_prefill_pallas,
)

Array = jax.Array
NEG_INF = -1e30
DEFAULT_BACKEND = "ref"
BACKENDS = ("ref", "interpret", "pallas")


def _check_backend(backend: str) -> None:
    if backend not in BACKENDS:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {BACKENDS}")


def polar_qk_scores(q, codes, rs, rz, ts, tz, *, r_bits=4, t_bits=4,
                    backend: str = DEFAULT_BACKEND, block_groups: int = 4):
    _check_backend(backend)
    if backend == "ref":
        return ref_mod.ref_polar_qk_scores(q, codes, rs, rz, ts, tz,
                                           r_bits=r_bits, t_bits=t_bits)
    return _qk_pallas(q, codes, rs, rz, ts, tz, r_bits=r_bits, t_bits=t_bits,
                      block_groups=block_groups,
                      interpret=(backend == "interpret"))


def polar_encode(k, *, r_bits=4, t_bits=4, group_size=128,
                 scale_dtype="float32", backend: str = DEFAULT_BACKEND):
    _check_backend(backend)
    if backend == "ref":
        return ref_mod.ref_polar_encode(k, r_bits=r_bits, t_bits=t_bits,
                                        group_size=group_size,
                                        scale_dtype=scale_dtype)
    return _encode_pallas(k, r_bits=r_bits, t_bits=t_bits,
                          group_size=group_size, scale_dtype=scale_dtype,
                          interpret=(backend == "interpret"))


def polar_decode_attention_grouped(q, codes, rs, rz, ts, tz, values, vscale,
                                   vzero, length, *, r_bits=4, t_bits=4,
                                   backend: str = DEFAULT_BACKEND,
                                   block_groups: int = 4):
    _check_backend(backend)
    if backend == "ref":
        if vscale is not None:
            values = (values.astype(jnp.float32) * vscale.astype(jnp.float32)
                      + vzero.astype(jnp.float32))
        return ref_mod.ref_polar_decode_attention(
            q, codes, rs, rz, ts, tz, values, length,
            r_bits=r_bits, t_bits=t_bits, softmax_scale=1.0)
    return _attn_pallas(q, codes, rs, rz, ts, tz, values, vscale, vzero,
                        length, r_bits=r_bits, t_bits=t_bits,
                        block_groups=block_groups,
                        interpret=(backend == "interpret"))


def polar_paged_decode_attention_grouped(q, codes, rs, rz, ts, tz, values,
                                         vscale, vzero, page_table, flushed,
                                         *, r_bits=4, t_bits=4,
                                         backend: str = DEFAULT_BACKEND):
    """Page-native fused flash-decode over the grouped segment: pool
    buffers + page table in, flash partials out (no dense gather copy)."""
    _check_backend(backend)
    if backend == "ref":
        return ref_mod.ref_polar_paged_decode_attention(
            q, codes, rs, rz, ts, tz, values, vscale, vzero, page_table,
            flushed, r_bits=r_bits, t_bits=t_bits)
    return _paged_attn_pallas(q, codes, rs, rz, ts, tz, values, vscale,
                              vzero, page_table, flushed, r_bits=r_bits,
                              t_bits=t_bits,
                              interpret=(backend == "interpret"))


def polar_paged_prefill_attention(q, k_chunk, v_chunk, codes, rs, rz, ts,
                                  tz, values, vscale, vzero, page_row,
                                  start, chunk_len, *, r_bits=4, t_bits=4,
                                  softmax_scale: float | None = None,
                                  backend: str = DEFAULT_BACKEND):
    """Page-native fused chunk-prefill attention: one chunk's queries
    against the slot's quantized prefix pages (LUT scores, in-place page
    walk) + the chunk's own fp causal tile, one online softmax.

    q: (1, Hq, Tc, d) UNscaled post-RoPE queries; k_chunk/v_chunk:
    (1, Hkv, Tc, d); pools as in :func:`polar_paged_decode_attention_grouped`;
    page_row: (N,) int32; start (page-aligned) / chunk_len: () int32.
    Returns (1, Hq, Tc, d) in q.dtype.
    """
    _check_backend(backend)
    if backend == "ref":
        return ref_mod.ref_polar_paged_prefill_attention(
            q, k_chunk, v_chunk, codes, rs, rz, ts, tz, values, vscale,
            vzero, page_row, start, chunk_len, r_bits=r_bits, t_bits=t_bits,
            softmax_scale=softmax_scale)
    _, hq, tc, d = q.shape
    hkv = codes.shape[1]
    qpk = hq // hkv
    scale = d ** -0.5 if softmax_scale is None else softmax_scale
    # fold chunk queries onto the head axis (row = qh * Tc + t) and
    # pre-scale — the kernel consumes one tall 2-D operand per kv head
    qf = (q.astype(jnp.float32) * scale).reshape(hkv, qpk * tc, d)
    out = _paged_prefill_pallas(
        qf, k_chunk[0], v_chunk[0], codes, rs, rz, ts, tz, values, vscale,
        vzero, page_row, start, chunk_len, r_bits=r_bits, t_bits=t_bits,
        interpret=(backend == "interpret"))
    return out.reshape(1, hq, tc, d).astype(q.dtype)


def merge_softmax_partials(parts: list[tuple[Array, Array, Array]]) -> Array:
    """Exactly merge flash partials [(acc, m, l), ...] -> normalized output.

    acc: (..., d) = sum exp(s - m) v;  m, l: (...,).
    """
    m_tot = functools.reduce(jnp.maximum, [m for _, m, _ in parts])
    l_tot = 0.0
    acc_tot = 0.0
    for acc, m, l in parts:
        corr = jnp.exp(m - m_tot)
        l_tot = l_tot + l * corr
        acc_tot = acc_tot + acc * corr[..., None]
    l_safe = jnp.where(l_tot == 0.0, 1.0, l_tot)
    return acc_tot / l_safe[..., None]


def _residual_flash_partials(q4: Array, key_residual: Array, n_res: Array,
                             v_res: Array):
    """Flash partials of the fp residual segment, shared by the dense and
    paged full-decode entries.

    q4: (B, Hkv, Qh, d) ALREADY scaled; key_residual: (B, Hkv, g, d);
    n_res: (B,) tokens in the residual window; v_res: (B, Hkv, g, d) fp32
    value rows for positions [flushed, flushed + g) — dead rows may hold
    garbage (clamped gathers, scratch pages) and are zeroed under the
    mask here, so ``p == 0`` lanes can never contribute ``0 * NaN``.
    Returns (acc_r, m_r, l_r).
    """
    res = key_residual.astype(jnp.float32)
    g = res.shape[2]
    s_res = jnp.einsum("bhqd,bhgd->bhqg", q4, res)
    slot = jnp.arange(g, dtype=jnp.int32)
    mask = slot[None, None, None, :] < n_res[:, None, None, None]
    s_res = jnp.where(mask, s_res, NEG_INF)
    m_r = jnp.max(s_res, axis=-1)
    p_r = jnp.where(mask, jnp.exp(s_res - m_r[..., None]), 0.0)
    l_r = jnp.sum(p_r, axis=-1)
    row_live = slot[None, :] < n_res[:, None]
    v_res = jnp.where(row_live[:, None, :, None], v_res, 0.0)
    acc_r = jnp.einsum("bhqg,bhgd->bhqd", p_r, v_res)
    return acc_r, m_r, l_r


def polar_decode_attention_full(
    q: Array, codes, rs, rz, ts, tz, key_residual, values, vscale, vzero,
    length: Array, *, r_bits=4, t_bits=4, softmax_scale: float | None = None,
    backend: str = DEFAULT_BACKEND, block_groups: int = 4,
) -> Array:
    """Full decode attention: grouped (quantized) segment via kernel +
    fp residual segment, merged exactly.

    q: (B, Hq, d); key_residual: (B, Hkv, g, d); values: (B, Hkv, T, d) or
    uint8 codes (+ vscale/vzero (B,Hkv,T,1)); length: () or (B,) total
    tokens — per-sequence lengths mask each continuous-batching slot at its
    own decode position. Returns (B, Hq, d) in q.dtype.
    """
    b, hq, d = q.shape
    hkv = codes.shape[1]
    g = codes.shape[3]
    qpk = hq // hkv
    scale = d ** -0.5 if softmax_scale is None else softmax_scale
    q4 = (q.astype(jnp.float32) * scale).reshape(b, hkv, qpk, d)
    len_b = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    flushed = (len_b // g) * g                                   # (B,)

    acc_g, m_g, l_g = polar_decode_attention_grouped(
        q4, codes, rs, rz, ts, tz, values, vscale, vzero, flushed,
        r_bits=r_bits, t_bits=t_bits, backend=backend,
        block_groups=block_groups)

    # residual V rows live token-major at [flushed, flushed + g) — gathered
    # per sequence (flushed differs across slots; clamp keeps the gather in
    # bounds when a full cache leaves no residual rows to read)
    t_cap = values.shape[2]
    slot = jnp.arange(g, dtype=jnp.int32)
    rows = jnp.minimum(flushed[:, None] + slot[None, :], t_cap - 1)
    idx = rows[:, None, :, None]                                 # (B,1,g,1)
    v_res = jnp.take_along_axis(values, idx, axis=2).astype(jnp.float32)
    if vscale is not None:
        vs_res = jnp.take_along_axis(vscale, idx, axis=2)
        vz_res = jnp.take_along_axis(vzero, idx, axis=2)
        v_res = (v_res * vs_res.astype(jnp.float32)
                 + vz_res.astype(jnp.float32))
    acc_r, m_r, l_r = _residual_flash_partials(q4, key_residual,
                                               len_b - flushed, v_res)

    out = merge_softmax_partials([(acc_g, m_g, l_g), (acc_r, m_r, l_r)])
    return out.reshape(b, hq, d).astype(q.dtype)


def polar_paged_decode_attention_full(
    q: Array, codes, rs, rz, ts, tz, key_residual, values, vscale, vzero,
    page_table: Array, lengths: Array, *, r_bits=4, t_bits=4,
    softmax_scale: float | None = None, backend: str = DEFAULT_BACKEND,
) -> Array:
    """End-to-end page-native decode attention: grouped segment via the
    page-table-walking kernel + fp residual segment, merged exactly.

    q: (S, Hq, d); pools as in :func:`polar_paged_decode_attention_grouped`;
    key_residual: (S, Hkv, g, d) per-slot partial group; page_table: (S, N)
    int32 (N may be width-sliced to the live pages); lengths: (S,) int32
    total tokens per slot. The residual's value rows live in the one page
    currently being filled (``table[s, flushed // g]``, rows
    ``[0, lengths - flushed)``), so the merge reads a single page per slot
    instead of a dense token-major copy. Returns (S, Hq, d) in q.dtype.
    """
    s, hq, d = q.shape
    hkv = codes.shape[1]
    g = codes.shape[2]
    qpk = hq // hkv
    scale = d ** -0.5 if softmax_scale is None else softmax_scale
    q4 = (q.astype(jnp.float32) * scale).reshape(s, hkv, qpk, d)
    len_b = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (s,))
    flushed = (len_b // g) * g                                   # (S,)

    acc_g, m_g, l_g = polar_paged_decode_attention_grouped(
        q4, codes, rs, rz, ts, tz, values, vscale, vzero, page_table,
        flushed, r_bits=r_bits, t_bits=t_bits, backend=backend)

    # residual V rows sit in the page being filled; empty slots clamp to
    # table entry 0 (possibly scratch) and every row is masked below
    gidx = jnp.minimum(flushed // g, page_table.shape[1] - 1)
    pv = jnp.take_along_axis(page_table.astype(jnp.int32),
                             gidx[:, None], axis=1)[:, 0]        # (S,)
    v_res = values[pv].astype(jnp.float32)                       # (S,H,g,d)
    if vscale is not None:
        v_res = (v_res * vscale[pv].astype(jnp.float32)
                 + vzero[pv].astype(jnp.float32))
    acc_r, m_r, l_r = _residual_flash_partials(q4, key_residual,
                                               len_b - flushed, v_res)

    out = merge_softmax_partials([(acc_g, m_g, l_g), (acc_r, m_r, l_r)])
    return out.reshape(s, hq, d).astype(q.dtype)
