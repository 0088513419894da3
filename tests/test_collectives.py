"""Distributed collectives: int8 error-feedback gradient compression
(exactness-over-time) and the online-softmax stats-merge family backing
context-parallel decode (DESIGN.md §17).

Mesh-dependent legs run in a subprocess with 4 forced host devices (the
main test process must keep seeing 1 device); the pure pairwise combiner
is unit-tested in-process."""
import os
import subprocess
import sys
import textwrap

import numpy as np

import jax.numpy as jnp

from repro.core.kv_cache import NEG_INF
from repro.distributed.collectives import (
    combine_softmax_stats, finalize_softmax, softmax_stats,
)

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.collectives import ef_allreduce_mean

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("dp",))
    key = jax.random.PRNGKey(0)
    true_acc = np.zeros((64,), np.float32)
    comp_acc = np.zeros((64,), np.float32)
    errors = {"g": jnp.zeros((4, 64), jnp.float32)}
    worst_single = 0.0
    for step in range(30):
        key, k = jax.random.split(key)
        g = jax.random.normal(k, (4, 64)) * (1.0 + step % 3)
        mean, errors = ef_allreduce_mean({"g": g}, errors, mesh, "dp")
        tm = np.asarray(jnp.mean(g, 0))
        cm = np.asarray(mean["g"])
        worst_single = max(worst_single,
                           float(np.linalg.norm(cm - tm) / np.linalg.norm(tm)))
        true_acc += tm
        comp_acc += cm
    # error feedback: the ACCUMULATED compressed mean tracks the true mean
    # far better than any single compressed step (bias is carried forward)
    rel = np.linalg.norm(comp_acc - true_acc) / np.linalg.norm(true_acc)
    print("REL", rel, "WORST", worst_single)
    assert rel < 0.01, rel
    assert rel < worst_single, (rel, worst_single)
    print("OK")
""")


def _run_forced_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=300)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_ef_allreduce_subprocess():
    _run_forced_subprocess(SCRIPT)


# ---------------------------------------------------------------------------
# Online-softmax stats merge (context-parallel decode's collectives)
# ---------------------------------------------------------------------------


def _ref_softmax_out(scores, values):
    """Direct masked softmax: the single-device answer the merged carries
    must reproduce. Fully-masked rows (all lanes at/below NEG_INF) -> 0."""
    live = np.isfinite(scores) & (scores > -1e29)
    s = np.where(live, scores, -np.inf)
    m = np.max(s, axis=-1, keepdims=True)
    p = np.where(live, np.exp(s - np.where(np.isfinite(m), m, 0.0)), 0.0)
    l = p.sum(-1, keepdims=True)
    out = (p[..., None] * values).sum(-2)
    return np.where(l > 0, out / np.maximum(l, 1e-38), 0.0)


def _stats_case(seed=0, q=5, t=32, d=4):
    """Masked score rows covering the degenerate spectrum: a live row,
    a row masked with -inf, a row masked with the finite NEG_INF sentinel,
    a half-masked row, and a single-survivor row."""
    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((q, t)).astype(np.float32)
    values = rng.standard_normal((q, t, d)).astype(np.float32)
    scores[1, :] = -np.inf
    scores[2, :] = NEG_INF
    scores[3, : t // 2] = NEG_INF
    scores[4, 1:] = -np.inf
    return scores, values


def test_combine_softmax_stats_matches_direct_softmax():
    """Pairwise tree-combining per-block carries == the one-shot softmax,
    with -inf and finite-NEG_INF degenerate blocks contributing exactly
    zero (the 0 * NaN class of bug this guards against)."""
    scores, values = _stats_case()
    ref = _ref_softmax_out(scores, values)
    blocks = [(jnp.asarray(scores[:, i:i + 8]),
               jnp.asarray(values[:, i:i + 8])) for i in range(0, 32, 8)]
    carry = softmax_stats(*blocks[0])
    for b in blocks[1:]:
        carry = combine_softmax_stats(carry, softmax_stats(*b))
    out = np.asarray(finalize_softmax(carry[1], carry[2]))
    assert np.all(np.isfinite(out))
    np.testing.assert_allclose(out, ref, atol=1e-6, rtol=1e-6)
    # the fully-masked queries resolve to exactly zero, not NaN
    assert np.array_equal(out[1], np.zeros_like(out[1]))
    assert np.array_equal(out[2], np.zeros_like(out[2]))


def test_softmax_stats_fully_masked_block_is_zero_mass():
    """A block with no live lanes must yield (l == 0, acc == 0) so it
    merges away — for both -inf and the finite NEG_INF masking."""
    for sentinel in (-np.inf, NEG_INF):
        scores = jnp.full((3, 8), sentinel, jnp.float32)
        values = jnp.ones((3, 8, 4), jnp.float32)
        m, l, acc = softmax_stats(scores, values)
        assert np.array_equal(np.asarray(l), np.zeros((3,)))
        assert np.array_equal(np.asarray(acc), np.zeros((3, 4)))
        out = np.asarray(finalize_softmax(l, acc))
        assert np.array_equal(out, np.zeros((3, 4)))


def test_combine_softmax_stats_is_order_insensitive():
    """The combiner is associative-enough: left-fold vs reversed fold
    agree to fp tolerance (the psum merge relies on this)."""
    scores, values = _stats_case(seed=3)
    blocks = [(jnp.asarray(scores[:, i:i + 8]),
               jnp.asarray(values[:, i:i + 8])) for i in range(0, 32, 8)]
    carries = [softmax_stats(s, v) for s, v in blocks]

    def fold(cs):
        acc = cs[0]
        for c in cs[1:]:
            acc = combine_softmax_stats(acc, c)
        return np.asarray(finalize_softmax(acc[1], acc[2]))

    np.testing.assert_allclose(fold(carries), fold(carries[::-1]),
                               atol=1e-6, rtol=1e-6)


MERGE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.kv_cache import NEG_INF
    from repro.distributed.collectives import (
        allgather_concat, finalize_softmax, merge_softmax_stats,
        softmax_stats)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("cp",))
    rng = np.random.default_rng(0)
    q, t, d = 5, 32, 4
    scores = rng.standard_normal((q, t)).astype(np.float32)
    values = rng.standard_normal((q, t, d)).astype(np.float32)
    # shard 1 fully dead at -inf, shard 2 fully dead at the finite
    # NEG_INF sentinel, for query 0; query 3 dead on EVERY shard
    scores[0, 8:16] = -np.inf
    scores[0, 16:24] = NEG_INF
    scores[3, :] = NEG_INF
    live = np.isfinite(scores) & (scores > -1e29)
    s = np.where(live, scores, -np.inf)
    m = np.max(s, -1, keepdims=True)
    p = np.where(live, np.exp(s - np.where(np.isfinite(m), m, 0.0)), 0.0)
    l = p.sum(-1, keepdims=True)
    ref = np.where(l > 0,
                   (p[..., None] * values).sum(-2) / np.maximum(l, 1e-38),
                   0.0)

    def psum_body(sc, va):
        m, l, acc = softmax_stats(sc, va)
        _, l, acc = merge_softmax_stats(m, l, acc, "cp")
        return finalize_softmax(l, acc)

    out = jax.shard_map(
        psum_body, mesh=mesh, in_specs=(P(None, "cp"), P(None, "cp", None)),
        out_specs=P(), check_vma=False)(jnp.asarray(scores),
                                        jnp.asarray(values))
    out = np.asarray(out)
    assert np.all(np.isfinite(out)), out
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    assert np.array_equal(out[3], np.zeros(d, np.float32)), out[3]

    def gather_body(sc, va):
        full_s = allgather_concat(sc, "cp", axis=-1)
        full_v = allgather_concat(va, "cp", axis=-2)
        _, l, acc = softmax_stats(full_s, full_v)
        return finalize_softmax(l, acc), full_s

    out_g, s_g = jax.shard_map(
        gather_body, mesh=mesh,
        in_specs=(P(None, "cp"), P(None, "cp", None)),
        out_specs=(P(), P()), check_vma=False)(jnp.asarray(scores),
                                               jnp.asarray(values))
    # tiled all-gather reconstructs the row in mesh order, bit-exactly
    assert np.array_equal(np.asarray(s_g), scores)
    np.testing.assert_allclose(np.asarray(out_g), ref, atol=1e-5, rtol=1e-5)
    print("OK")
""")


def test_softmax_merge_collectives_subprocess():
    """merge_softmax_stats / allgather_concat under shard_map on 4 forced
    devices: psum merge matches the direct softmax with degenerate shards
    (-inf AND finite-NEG_INF, plus an all-dead query) contributing zero;
    the tiled all-gather reconstructs rows bit-exactly."""
    _run_forced_subprocess(MERGE_SCRIPT)
