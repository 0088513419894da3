"""Explicit collectives: softmax-stats merges + int8 error-feedback.

Two families live here:

* **Online-softmax stats merges** for sharded attention
  (:func:`softmax_stats`, :func:`combine_softmax_stats`,
  :func:`merge_softmax_stats`, :func:`allgather_concat`,
  :func:`finalize_softmax`). A shard that scored only part of a query's
  context holds partial ``(m, l, acc)`` carries (running max, normalizer,
  unnormalized value accumulator); merging rescales by
  ``exp(m_i - max_j m_j)`` and psums. The rescale is guarded against
  degenerate shards — a shard with zero live positions carries
  ``m = -inf`` (or the finite ``NEG_INF`` sentinel), and a naive
  ``exp(m - m_max)`` there is ``exp(-inf - -inf) = NaN``; the guard zeroes
  such contributions instead (the ``0 * NaN`` class of bug, same family
  the single-device gather path masks at page granularity).

* **int8 error-feedback gradient compression** (``ef_allreduce_mean``).
  Cross-boundary (e.g. cross-pod DCN) gradient reduction is the bandwidth
  hot-spot at 1000+-node scale: each participant quantizes (grad +
  carried error) to int8 with a per-participant fp32 scale, the int8
  payload is what crosses the axis, and the quantization error is carried
  into the next step (EF-SGD) so the bias vanishes over time. Grads
  arrive stacked on a leading ``workers`` axis sharded over the mesh axis
  being reduced.

Both families are exercised on a multi-device CPU mesh by
tests/test_collectives.py; the softmax merges additionally back the
context-parallel decode reference in distributed/serving.py.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def _ef_leaf(g: Array, err: Array, axis: str):
    """g, err: this participant's block (1, ...). Returns (mean, new_err)."""
    x = g[0].astype(jnp.float32) + err[0]
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    new_err = x - q.astype(jnp.float32) * scale
    # dequantize per participant, then psum — the wire payload in a real
    # DCN deployment is (q, scale); psum of the dequantized value keeps
    # the math identical while remaining one fused collective here.
    contrib = q.astype(jnp.float32) * scale
    total = jax.lax.psum(contrib, axis)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
    return total / n, new_err[None]


# ---------------------------------------------------------------------------
# Online-softmax stats: per-shard partials + merge collectives
# ---------------------------------------------------------------------------


def softmax_stats(scores: Array, values: Array):
    """Partial online-softmax carries for a block of masked scores.

    scores: (..., T) with masked lanes at ``NEG_INF`` (or ``-inf``);
    values: (..., T, d) token-major value rows (masked lanes zeroed or
    finite — they are weighted by an exactly-underflowed 0). Returns
    ``(m, l, acc)``: running max (...,), normalizer (...,), and
    unnormalized accumulator (..., d). A fully-masked block yields
    ``l == 0`` / ``acc == 0`` (not NaN) so it merges away cleanly.
    """
    m = jnp.max(scores, axis=-1)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(jnp.isfinite(m)[..., None],
                  jnp.exp(scores - safe_m[..., None]), 0.0)
    # finite NEG_INF sentinel: when every lane is NEG_INF, m == NEG_INF and
    # p == 1 everywhere — poison the normalizer too so this block carries
    # zero weight into any merge (matching the -inf branch above)
    dead = m <= -1e29
    p = jnp.where(dead[..., None], 0.0, p)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("...t,...td->...d", p, values)
    return m, l, acc


def combine_softmax_stats(a, b):
    """Merge two partial ``(m, l, acc)`` carries over the same queries —
    the pure pairwise combiner (local, no collective). Degenerate operands
    (``m`` at -inf / NEG_INF, i.e. zero live positions) contribute exactly
    zero rather than NaN."""
    m1, l1, acc1 = a
    m2, l2, acc2 = b
    m = jnp.maximum(m1, m2)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)

    def coeff(mi):
        return jnp.where(jnp.isfinite(mi) & (mi > -1e29),
                         jnp.exp(mi - safe_m), 0.0)

    c1, c2 = coeff(m1), coeff(m2)
    l = l1 * c1 + l2 * c2
    acc = acc1 * c1[..., None] + acc2 * c2[..., None]
    return m, l, acc


def merge_softmax_stats(m: Array, l: Array, acc: Array, axis: str):
    """Collective merge of per-shard ``(m, l, acc)`` partials over mesh
    axis ``axis`` (inside shard_map): ``m`` is pmax'd, ``l``/``acc`` are
    rescaled by ``exp(m - m_max)`` and psum'd. The rescale is guarded so a
    shard with zero live positions (``m`` at -inf / NEG_INF) contributes
    exactly zero — it must not poison the merged softmax."""
    m_max = jax.lax.pmax(m, axis)
    safe_max = jnp.where(jnp.isfinite(m_max), m_max, 0.0)
    c = jnp.where(jnp.isfinite(m) & (m > -1e29),
                  jnp.exp(m - safe_max), 0.0)
    l_tot = jax.lax.psum(l * c, axis)
    acc_tot = jax.lax.psum(acc * c[..., None], axis)
    return m_max, l_tot, acc_tot


def finalize_softmax(l: Array, acc: Array) -> Array:
    """``acc / l`` with the all-masked case (l == 0) mapped to 0, not NaN."""
    return jnp.where(l[..., None] > 0,
                     acc / jnp.maximum(l, 1e-38)[..., None], 0.0)


def allgather_concat(x: Array, axis_name: str, axis: int = -1) -> Array:
    """All-gather shard blocks of ``x`` concatenated along ``axis`` in mesh
    order (``tiled``) — the LUT-score all-gather: each context-parallel
    shard contributes its slice of the score row (or value rows), and every
    shard reconstructs the full row so the subsequent softmax is
    *bit-identical* to the single-device formulation (unlike the psum
    merge, whose reduction order differs in the last ulp)."""
    if axis < 0:
        axis += x.ndim
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def ef_allreduce_mean(grads: Any, errors: Any, mesh: Mesh, axis: str = "dp"):
    """Error-feedback int8 mean-all-reduce over mesh axis ``axis``.

    grads/errors: pytrees whose leaves are stacked (W, ...) with W == the
    size of ``axis`` and that leading dim sharded over ``axis``.
    Returns (mean_grads (...), new_errors (W, ...)).
    """
    flat, treedef = jax.tree_util.tree_flatten(grads)
    flat_err = treedef.flatten_up_to(errors)

    outs, new_errs = [], []
    for g, e in zip(flat, flat_err):
        fn = jax.shard_map(
            functools.partial(_ef_leaf, axis=axis), mesh=mesh,
            in_specs=(P(axis), P(axis)), out_specs=(P(), P(axis)),
            check_vma=False)
        o, ne = fn(g, e)
        outs.append(o)
        new_errs.append(ne)
    return treedef.unflatten(outs), treedef.unflatten(new_errs)
