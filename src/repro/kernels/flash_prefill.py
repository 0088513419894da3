"""Pallas TPU kernel: forward-only flash attention for serving prefill.

The §Roofline table shows every prefill cell is memory-bound, dominated by
the jnp-level flash attention spilling per-chunk fp32 score tiles to HBM.
This kernel keeps the (q_blk, k_blk) score tile and the online-softmax
carry in VMEM — HBM traffic reduces to Q/K/V/O streaming (the roofline
floor). Forward-only: prefill has no backward pass.

Grid: (B, H, Tq/q_blk, Tk/k_blk), k innermost; scratch carries (m, l, acc)
per q block across k steps. GQA via the K/V index map (h -> h // q_per_kv).
Causal masking by absolute block offsets; fully-masked tiles short-circuit
via @pl.when (no MXU work issued for the upper triangle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, q_blk: int, k_blk: int, nk: int,
            kv_len: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * q_blk
    k_start = ki * k_blk

    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (q_blk, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (k_blk, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_idx < kv_len
        if causal:
            mask = mask & (q_idx >= k_idx)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        v = v_ref[0, 0].astype(jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)

    if causal:
        # skip fully-masked tiles (first key index beyond last query index)
        pl.when(k_start <= q_start + q_blk - 1)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "q_blk",
                                             "k_blk", "interpret"))
def flash_prefill(q: Array, k: Array, v: Array, *, causal: bool = True,
                  scale: float | None = None, q_blk: int = 128,
                  k_blk: int = 128, interpret: bool) -> Array:
    """q: (B, H, Tq, d); k/v: (B, Hkv, Tk, d); returns (B, H, Tq, d).

    VMEM per step ~= (q_blk + 2*k_blk)*d*4 + q_blk*k_blk*4 + q_blk*d*4
    (~260 KiB at 128/128/d=128) << 16 MiB.
    """
    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    qpk = h // hkv
    scale = d ** -0.5 if scale is None else scale
    q_blk = min(q_blk, tq)
    k_blk = min(k_blk, tk)
    nq = -(-tq // q_blk)
    nk = -(-tk // k_blk)
    if tq % q_blk or tk % k_blk:
        # pad to block multiples; padded keys masked via kv_len
        qpad = nq * q_blk - tq
        kpad = nk * k_blk - tk
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qpad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, kpad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, kpad), (0, 0)))

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             q_blk=q_blk, k_blk=k_blk, nk=nk, kv_len=tk)
    out = pl.pallas_call(
        kern,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, k_blk, d),
                         lambda b_, h_, i, j: (b_, h_ // qpk, j, 0)),
            pl.BlockSpec((1, 1, k_blk, d),
                         lambda b_, h_, i, j: (b_, h_ // qpk, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nq * q_blk, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :tq]
