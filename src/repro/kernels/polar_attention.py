"""Pallas TPU kernel: fully-fused PolarQuant decode attention (beyond-paper).

The paper's Triton kernel fuses dequantization + QK only; scores round-trip
through HBM before softmax and the value matmul. On TPU the score spill is
the dominant extra traffic at 32K context, so this kernel carries the online
softmax across the group-block grid dimension in VMEM scratch and fuses the
value matmul (flash-decode structure):

    per (b, h) KV head, for each block n of gb groups:
        s     = LUT-scores(q, codes_n)            (VPU select-tree)
        m,l   = online-softmax update             (VMEM scratch carry)
        acc  += exp(s - m) @ V_n                  (MXU)

Outputs are the *unnormalized* flash partials (acc, m, l) over the grouped
tokens so the wrapper can merge the fp residual segment exactly (the merge
is associative). Values may be fp or token-wise uint8-quantized; dequant of
V happens in-register before the MXU matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def to_f32(x):
    """Widen a loaded tile to fp32. The TPU compiler has no direct cast from
    unsigned bytes to floats, so uint8 codes go through int32 first."""
    if x.dtype == jnp.uint8:
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def _dequant_page_keys(codes, rs, rz, ts, tz, t_bits: int):
    """One page's polar codes (g, P) -> fp32 key halves (k_x, k_y), each
    (g, P): ``rho~ * (cos, sin)(theta~ - pi)`` per channel pair."""
    codes = codes.astype(jnp.int32)     # the TPU compiler cannot shift uint8
    rho = ((codes >> t_bits).astype(jnp.float32) + 0.5) * rs + rz
    theta = ((codes & ((1 << t_bits) - 1)).astype(jnp.float32) + 0.5) * ts + tz
    return rho * jnp.cos(theta - jnp.pi), rho * jnp.sin(theta - jnp.pi)


def _lut_scores_block(q, codes, rs, rz, ts, tz, r_bits, t_bits):
    """Shared LUT score tile: q (Qh,d) fp32; codes (gb,g,P) -> (Qh, gb*g)."""
    qh, d = q.shape
    p = d // 2
    qx, qy = q[:, :p], q[:, p:]
    gb, g, _ = codes.shape
    # shifts and masks run on int32: the TPU compiler cannot shift uint8
    codes = codes.astype(jnp.int32)
    tc = codes & ((1 << t_bits) - 1)
    rc = (codes >> t_bits).astype(jnp.float32)
    rho = (rc + 0.5) * rs[:, None, :] + rz[:, None, :]
    gathered = jnp.zeros((qh, gb, g, p), jnp.float32)
    for a in range(1 << t_bits):
        theta = (a + 0.5) * ts + tz
        cos_t = jnp.cos(theta - jnp.pi)
        sin_t = jnp.sin(theta - jnp.pi)
        a_tab = qx[:, None, :] * cos_t[None] + qy[:, None, :] * sin_t[None]
        gathered = gathered + jnp.where((tc == a)[None], a_tab[:, :, None, :], 0.0)
    return jnp.sum(rho[None] * gathered, axis=-1).reshape(qh, gb * g)


def _attn_kernel(q_ref, codes_ref, rs_ref, rz_ref, ts_ref, tz_ref, v_ref,
                 vs_ref, vz_ref, len_ref, out_ref, m_out_ref, l_out_ref,
                 m_ref, l_ref, acc_ref, *, r_bits: int, t_bits: int,
                 quantized_values: bool, block_tokens: int):
    n = pl.program_id(2)
    qh, d = q_ref.shape[2], q_ref.shape[3]

    @pl.when(n == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)
    codes = codes_ref[0, 0]
    s = _lut_scores_block(
        q, codes,
        rs_ref[0, 0, :, 0].astype(jnp.float32),
        rz_ref[0, 0, :, 0].astype(jnp.float32),
        ts_ref[0, 0, :, 0].astype(jnp.float32),
        tz_ref[0, 0, :, 0].astype(jnp.float32),
        r_bits, t_bits)                                # (Qh, S)

    length = len_ref[0, 0]
    pos = n * block_tokens + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = pos < length
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (Qh, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # (Qh, S)
    corr = jnp.exp(m_prev - m_new)

    if quantized_values:
        v = (to_f32(v_ref[0, 0]) * vs_ref[0, 0].astype(jnp.float32)
             + vz_ref[0, 0].astype(jnp.float32))       # (S, d)
    else:
        v = v_ref[0, 0].astype(jnp.float32)

    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    # Final carry lands in the (b, h)-indexed output tiles on the last step;
    # intermediate writes are overwritten (n is the innermost grid dim).
    out_ref[0, 0] = acc_ref[...]
    m_out_ref[0, 0] = m_ref[...]
    l_out_ref[0, 0] = l_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "r_bits", "t_bits", "block_groups", "interpret"))
def polar_decode_attention_grouped(
    q: Array, codes: Array, rs: Array, rz: Array, ts: Array, tz: Array,
    values, vscale, vzero, length: Array, *, r_bits: int = 4,
    t_bits: int = 4, block_groups: int = 4, interpret: bool,
):
    """Fused flash-decode over the grouped cache segment.

    q: (B,Hkv,Qh,d) — ALREADY scaled by softmax scale.
    codes: (B,Hkv,G,g,P); stats: (B,Hkv,G,1,P).
    values: (B,Hkv,T,d) fp, or uint8 codes with vscale/vzero (B,Hkv,T,1)
    (pass vscale=None for fp values). length: () or (B,) int32 valid
    grouped tokens — per-sequence when batched (continuous batching slots
    at heterogeneous positions); the kernel reads its own row via the
    length BlockSpec, so the body is unchanged.

    Returns (out (B,Hkv,Qh,d), m (B,Hkv,Qh), l (B,Hkv,Qh)) — unnormalized
    partials (see module docstring).
    """
    b, hkv, qh, d = q.shape
    _, _, gcount, g, p = codes.shape
    quantized_values = vscale is not None
    gb = min(block_groups, gcount)
    while gcount % gb:
        gb -= 1
    nb = gcount // gb
    s_blk = gb * g

    kern = functools.partial(
        _attn_kernel, r_bits=r_bits, t_bits=t_bits,
        quantized_values=quantized_values, block_tokens=s_blk)
    stat_spec = pl.BlockSpec((1, 1, gb, 1, p), lambda i, j, n: (i, j, n, 0, 0))
    v_spec = pl.BlockSpec((1, 1, s_blk, d), lambda i, j, n: (i, j, n, 0))
    vstat_spec = pl.BlockSpec((1, 1, s_blk, 1), lambda i, j, n: (i, j, n, 0))
    len2 = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1, 1), (b, 1))

    if quantized_values:
        v_in = (values, vscale, vzero)
        v_specs = [v_spec, vstat_spec, vstat_spec]
    else:
        # dummy (1,1,1,1) stat inputs keep the kernel signature uniform
        dummy = jnp.zeros((1, 1, 1, 1), jnp.float32)
        v_in = (values, dummy, dummy)
        v_specs = [v_spec,
                   pl.BlockSpec((1, 1, 1, 1), lambda i, j, n: (0, 0, 0, 0)),
                   pl.BlockSpec((1, 1, 1, 1), lambda i, j, n: (0, 0, 0, 0))]

    out, m, l = pl.pallas_call(
        kern,
        grid=(b, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, qh, d), lambda i, j, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, gb, g, p), lambda i, j, n: (i, j, n, 0, 0)),
            stat_spec, stat_spec, stat_spec, stat_spec,
            *v_specs,
            pl.BlockSpec((1, 1), lambda i, j, n: (i, 0)),
        ],
        # m/l carry a trailing unit axis: a block's last two dims must
        # tile (8, 128) or span the array, which (1, qh) would not
        out_specs=[
            pl.BlockSpec((1, 1, qh, d), lambda i, j, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, qh, 1), lambda i, j, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, qh, 1), lambda i, j, n: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, qh, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, qh, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, qh, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qh, 1), jnp.float32),
            pltpu.VMEM((qh, 1), jnp.float32),
            pltpu.VMEM((qh, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, codes, rs, rz, ts, tz, *v_in, len2)
    return out, m[..., 0], l[..., 0]
