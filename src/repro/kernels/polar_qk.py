"""Pallas TPU kernel: fused dequantization + query-key scores for PolarQuant.

Paper-faithful analogue of the Triton kernel in Appendix A, adapted to the
TPU memory/compute hierarchy (DESIGN.md §3):

* the per-(group, channel-pair) angle LUT ``A[j, a]`` is built in VMEM from
  the (gb, P) theta scale/zero tiles (one fused cos/sin pass per angle state);
* the "gather" ``A[j, theta_code]`` is a compare/select tree over the 2^t
  angle states — fully lane-parallel on the VPU, no per-element gather;
* the radius is reconstructed with a single FMA (affine in its code), never
  a table;
* codes arrive packed ((rho << t) | theta, one uint8 per channel pair =
  (r+t)/2 bits per key element) and are unpacked with shift/mask in-kernel,
  so HBM traffic is ~4x lower than bf16 keys — the roofline win for
  memory-bound decode.

Grid: (B, Hkv, G/gb). Each step processes ``gb`` quantization groups
(gb*g tokens) for all ``Qh`` query heads of one KV head.
VMEM per step ~= gb*g*P (codes) + 4*gb*P*4 (scales) + Qh*d*4 (q)
             + Qh*gb*g*4 (out tile): gb=4, g=128, P=64, Qh=8, d=128
             => 32KiB + 4KiB + 4KiB + 128KiB ~ 170KiB  << 16MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _qk_kernel(q_ref, codes_ref, rs_ref, rz_ref, ts_ref, tz_ref, out_ref, *,
               r_bits: int, t_bits: int):
    qh, d = q_ref.shape[2], q_ref.shape[3]
    p = d // 2
    q = q_ref[0, 0].astype(jnp.float32)             # (Qh, d)
    qx, qy = q[:, :p], q[:, p:]                     # "half" pairing
    codes = codes_ref[0, 0]                         # (gb, g, P) uint8
    gb, g, _ = codes.shape
    tc = (codes & ((1 << t_bits) - 1)).astype(jnp.int32)
    rc = (codes >> t_bits).astype(jnp.float32)
    rs = rs_ref[0, 0, :, 0].astype(jnp.float32)     # (gb, P)
    rz = rz_ref[0, 0, :, 0].astype(jnp.float32)
    ts = ts_ref[0, 0, :, 0].astype(jnp.float32)
    tz = tz_ref[0, 0, :, 0].astype(jnp.float32)

    rho = (rc + 0.5) * rs[:, None, :] + rz[:, None, :]          # (gb, g, P)

    # Angle LUT + select-tree over the 2^t states.
    gathered = jnp.zeros((qh, gb, g, p), jnp.float32)
    for a in range(1 << t_bits):
        theta = (a + 0.5) * ts + tz                              # (gb, P)
        cos_t = jnp.cos(theta - jnp.pi)
        sin_t = jnp.sin(theta - jnp.pi)
        a_tab = (qx[:, None, :] * cos_t[None] +
                 qy[:, None, :] * sin_t[None])                   # (Qh, gb, P)
        gathered = gathered + jnp.where(
            (tc == a)[None], a_tab[:, :, None, :], 0.0)

    scores = jnp.sum(rho[None] * gathered, axis=-1)              # (Qh, gb, g)
    out_ref[0, 0] = scores.reshape(qh, gb * g)


@functools.partial(jax.jit, static_argnames=("r_bits", "t_bits",
                                             "block_groups", "interpret"))
def polar_qk_scores(q: Array, codes: Array, rs: Array, rz: Array, ts: Array,
                    tz: Array, *, r_bits: int = 4, t_bits: int = 4,
                    block_groups: int = 4, interpret: bool) -> Array:
    """LUT q.K scores. Shapes as in ref.ref_polar_qk_scores.

    q: (B, Hkv, Qh, d); codes: (B, Hkv, G, g, P); stats: (B, Hkv, G, 1, P).
    Returns (B, Hkv, Qh, G*g) fp32.
    """
    b, hkv, qh, d = q.shape
    _, _, gcount, g, p = codes.shape
    assert p * 2 == d, (p, d)
    gb = min(block_groups, gcount)
    while gcount % gb:
        gb -= 1
    nb = gcount // gb

    kern = functools.partial(_qk_kernel, r_bits=r_bits, t_bits=t_bits)
    stat_spec = pl.BlockSpec((1, 1, gb, 1, p), lambda i, j, n: (i, j, n, 0, 0))
    return pl.pallas_call(
        kern,
        grid=(b, hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, qh, d), lambda i, j, n: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, gb, g, p), lambda i, j, n: (i, j, n, 0, 0)),
            stat_spec, stat_spec, stat_spec, stat_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, qh, gb * g), lambda i, j, n: (i, j, 0, n)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, qh, gcount * g), jnp.float32),
        interpret=interpret,
    )(q, codes, rs, rz, ts, tz)
