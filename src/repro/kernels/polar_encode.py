"""Pallas TPU kernel: group-wise polar quantization of post-RoPE keys.

One grid step quantizes one (batch, kv-head, group) tile: loads a (g, d)
key tile from HBM into VMEM, computes the polar transform, reduces per
channel-pair min/max over the g tokens, and emits packed uint8 codes plus
the four per-group stat rows. Token axis g is sublane-aligned (g % 8 == 0
for all supported group sizes); channel-pair axis P = d/2 sits in lanes.

Mirrors ``repro.core.quantizers.encode_polar_keys`` bit-for-bit (same
mid-rise grid, same eps guard) — tests assert exact code equality.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array
_EPS = 1e-8


def _encode_kernel(k_ref, codes_ref, rs_ref, rz_ref, ts_ref, tz_ref, *,
                   r_bits: int, t_bits: int):
    k = k_ref[0, 0].astype(jnp.float32)            # (g, d)
    g, d = k.shape
    p = d // 2
    x, y = k[:, :p], k[:, p:]                      # "half" pairing
    rho = jnp.sqrt(x * x + y * y)                  # (g, P)
    theta = jnp.arctan2(y, x) + jnp.pi

    def stats(v, bits):
        mn = jnp.min(v, axis=0, keepdims=True)     # (1, P)
        mx = jnp.max(v, axis=0, keepdims=True)
        s = jnp.maximum((mx - mn) / (1 << bits), _EPS)
        c = jnp.clip(jnp.floor((v - mn) / s), 0, (1 << bits) - 1)
        return c.astype(jnp.uint8), s, mn

    rc, rs, rz = stats(rho, r_bits)
    tc, ts, tz = stats(theta, t_bits)
    codes_ref[0, 0, 0] = (rc << t_bits) | tc
    rs_ref[0, 0, 0] = rs.astype(rs_ref.dtype)
    rz_ref[0, 0, 0] = rz.astype(rz_ref.dtype)
    ts_ref[0, 0, 0] = ts.astype(ts_ref.dtype)
    tz_ref[0, 0, 0] = tz.astype(tz_ref.dtype)


@functools.partial(jax.jit, static_argnames=("r_bits", "t_bits", "group_size",
                                             "scale_dtype", "interpret"))
def polar_encode(k: Array, *, r_bits: int = 4, t_bits: int = 4,
                 group_size: int = 128, scale_dtype: str = "float32",
                 interpret: bool):
    """Quantize keys (B, Hkv, T, d) with T % group_size == 0.

    Returns (codes (B,Hkv,G,g,P) uint8, rho_scale, rho_zero, theta_scale,
    theta_zero — each (B,Hkv,G,1,P))."""
    b, hkv, t, d = k.shape
    g = group_size
    assert t % g == 0, (t, g)
    gcount = t // g
    p = d // 2
    sdt = jnp.dtype(scale_dtype)

    kern = functools.partial(_encode_kernel, r_bits=r_bits, t_bits=t_bits)
    stat = jax.ShapeDtypeStruct((b, hkv, gcount, 1, p), sdt)
    stat_spec = pl.BlockSpec((1, 1, 1, 1, p), lambda i, j, n: (i, j, n, 0, 0))
    return pl.pallas_call(
        kern,
        grid=(b, hkv, gcount),
        in_specs=[pl.BlockSpec((1, 1, g, d), lambda i, j, n: (i, j, n, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, 1, g, p), lambda i, j, n: (i, j, n, 0, 0)),
            stat_spec, stat_spec, stat_spec, stat_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, gcount, g, p), jnp.uint8),
            stat, stat, stat, stat,
        ],
        interpret=interpret,
    )(k)
