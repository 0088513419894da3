"""Page-native fused prefill: parity of paged_prefill_attention with the
gathered jnp reference across every registered codec, GQA + fragmented
non-monotonic page tables, width-sliced rows, fresh-vs-adopted prefix
pages, the rem == 0 misaligned-residual invariant across a chunked
prefill -> decode sequence at exact page multiples, and bit-identical
greedy outputs from the continuous-batching engine under
prefill_backend=paged_fused."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import QuantConfig, codecs
from repro.core import paged_cache as pg
from repro.core.cache_layout import PagedLayout
from repro.models import get_model
from repro.serve import ContinuousBatchingEngine, GenerationConfig, Request

H, d, g = 2, 32, 16
QPK = 2                      # GQA: query heads per kv head
TC = 16                      # chunk bucket (tokens)
LAYOUT = PagedLayout(page_size=g, num_pages=24, slots=3, pages_per_slot=6)
# fragmented, non-monotonic row: pages land wherever the allocator found
# free slots, and the kernel must visit them in *logical* order anyway
ROW = (9, 0, 5, 1, 2, 3)


def _cfg(method: str, value_bits: int = 0) -> QuantConfig:
    return QuantConfig(method=method, group_size=g, key_bits=8,
                       value_bits=value_bits, rho_bits=4, theta_bits=4,
                       residual_dtype="float32")


def _prefix_cache(cfg, start=3 * g, row=ROW, seed=0, slot=0, cache=None):
    """Prefill a ``start``-token prefix (page-aligned) into a fragmented
    row; returns (cache, row, start)."""
    cache = cache if cache is not None else pg.init_paged_cache(
        cfg, LAYOUT, H, d)
    row = jnp.asarray(row, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    k = jax.random.normal(ks[0], (1, H, start, d))
    v = jax.random.normal(ks[1], (1, H, start, d))
    cache = pg.paged_prefill(cache, jnp.asarray(slot), row, k, v,
                             jnp.asarray(start))
    return cache, row, jnp.asarray(start, jnp.int32)


def _chunk(seed=7, tc=TC):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, H * QPK, tc, d))
    k = jax.random.normal(ks[1], (1, H, tc, d))
    v = jax.random.normal(ks[2], (1, H, tc, d))
    return q, k, v


# ---------------------------------------------------------------------------
# Parity: page-native dispatch vs the gathered jnp reference, whole registry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(codecs.registered_codecs()))
def test_paged_fused_prefill_matches_jnp_reference(name):
    """paged_prefill_attention(backend="paged_fused") must agree with the
    gathered jnp reference for every registered codec — page-native walk
    for codecs with the capability, gathered fallback for the rest."""
    cfg = _cfg(name)
    cache, row, start = _prefix_cache(cfg)
    q, kc, vc = _chunk()
    clen = jnp.asarray(13, jnp.int32)    # ragged chunk: tail is padding
    o_ref = pg.paged_prefill_attention(cache, q, kc, vc, row, start, clen,
                                       backend="jnp")
    o_fused = pg.paged_prefill_attention(cache, q, kc, vc, row, start, clen,
                                         backend="paged_fused")
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_fused),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("value_bits", [0, 4])
def test_polar_jnp_oracle_is_bit_identical(value_bits):
    """The page-walking jnp oracle reorders no float ops relative to the
    gathered reference — outputs are bit-identical, which is what lets the
    engine flip prefill_backend without perturbing greedy decoding."""
    cfg = _cfg("polar", value_bits=value_bits)
    cache, row, start = _prefix_cache(cfg)
    q, kc, vc = _chunk()
    clen = jnp.asarray(13, jnp.int32)
    o_jnp = pg.paged_prefill_attention(cache, q, kc, vc, row, start, clen,
                                       backend="jnp")
    o_ref = pg.paged_prefill_attention(cache, q, kc, vc, row, start, clen,
                                       backend="ref")
    np.testing.assert_array_equal(np.asarray(o_jnp), np.asarray(o_ref))


@pytest.mark.parametrize("value_bits", [0, 4])
def test_polar_pallas_kernel_parity_interpret(value_bits):
    """Interpret-mode Pallas (kernel body on CPU CI) vs the gathered
    reference, quantized and fp values."""
    cfg = _cfg("polar", value_bits=value_bits)
    cache, row, start = _prefix_cache(cfg)
    q, kc, vc = _chunk()
    clen = jnp.asarray(13, jnp.int32)
    o_jnp = pg.paged_prefill_attention(cache, q, kc, vc, row, start, clen,
                                       backend="jnp")
    o_k = pg.paged_prefill_attention(cache, q, kc, vc, row, start, clen,
                                     backend="interpret")
    np.testing.assert_allclose(np.asarray(o_jnp), np.asarray(o_k),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("value_bits", [0, 4])
def test_pallas_prefill_row_blocks_match_one_block(value_bits):
    """The kernel tiles the folded query rows over a grid axis. Blocks of
    8 rows (half of one query head's TC rows, so the causal mask must use
    global row indices) reproduce the single-block result."""
    from repro.kernels.paged_prefill import polar_paged_prefill_grouped
    cfg = _cfg("polar", value_bits=value_bits)
    cache, row, start = _prefix_cache(cfg)
    q, kc, vc = _chunk()
    qf = (q * d ** -0.5).reshape(H, QPK * TC, d)
    sc = cache.key_scales
    if value_bits:
        vals = (cache.value_codes, cache.value_scale, cache.value_zero)
    else:
        vals = (cache.value_fp, None, None)
    outs = [polar_paged_prefill_grouped(
        qf, kc[0], vc[0], cache.key_codes, sc["rho_scale"], sc["rho_zero"],
        sc["theta_scale"], sc["theta_zero"], *vals, row, start,
        jnp.asarray(13, jnp.int32), block_rows=br, interpret=True)
        for br in (8, QPK * TC)]
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               atol=1e-6, rtol=1e-6)


def test_width_sliced_row_matches_full_row():
    """The engine buckets the table row to the pages covering the live
    prefix; masked lanes contribute exactly-0.0 probability, so slicing is
    numerically equivalent (to reduction-order rounding — the contraction
    width changes, so exact bit layout may differ by ~1 ulp)."""
    cfg = _cfg("polar", value_bits=4)
    cache, row, start = _prefix_cache(cfg)
    q, kc, vc = _chunk()
    clen = jnp.asarray(TC, jnp.int32)
    full = pg.paged_prefill_attention(cache, q, kc, vc, row, start, clen,
                                      backend="ref")
    sliced = pg.paged_prefill_attention(cache, q, kc, vc, row[:4], start,
                                        clen, backend="ref")
    np.testing.assert_allclose(np.asarray(full), np.asarray(sliced),
                               atol=1e-6, rtol=1e-5)


def test_fresh_vs_adopted_prefix_pages_identical():
    """Shared-prefix adoption: a slot whose row points at pages *another*
    slot's prefill wrote must score the prefix identically to a slot that
    recomputed the same prefix into fresh pages (same bytes -> same
    bits)."""
    cfg = _cfg("polar", value_bits=4)
    cache, row_a, start = _prefix_cache(cfg, seed=0, slot=0)
    # same prefix content, fresh pages, different slot
    row_b = (14, 20, 7, 11, 12, 13)
    cache, row_b, _ = _prefix_cache(cfg, row=row_b, seed=0, slot=1,
                                    cache=cache)
    q, kc, vc = _chunk()
    clen = jnp.asarray(TC, jnp.int32)
    o_fresh = pg.paged_prefill_attention(cache, q, kc, vc, row_b, start,
                                         clen, backend="ref")
    # adoption == pointing the row at the original writer's pages
    o_adopted = pg.paged_prefill_attention(cache, q, kc, vc, row_a, start,
                                           clen, backend="ref")
    np.testing.assert_array_equal(np.asarray(o_fresh), np.asarray(o_adopted))


def test_start_zero_first_chunk():
    """First chunk of a prompt: no prefix pages live, pure fp causal."""
    cfg = _cfg("polar")
    cache = pg.init_paged_cache(cfg, LAYOUT, H, d)
    row = jnp.asarray(ROW, jnp.int32)
    q, kc, vc = _chunk()
    z = jnp.asarray(0, jnp.int32)
    clen = jnp.asarray(TC, jnp.int32)
    o_jnp = pg.paged_prefill_attention(cache, q, kc, vc, row, z, clen,
                                       backend="jnp")
    o_ref = pg.paged_prefill_attention(cache, q, kc, vc, row, z, clen,
                                       backend="ref")
    o_k = pg.paged_prefill_attention(cache, q, kc, vc, row, z, clen,
                                     backend="interpret")
    np.testing.assert_array_equal(np.asarray(o_jnp), np.asarray(o_ref))
    np.testing.assert_allclose(np.asarray(o_jnp), np.asarray(o_k),
                               atol=2e-5, rtol=1e-4)


def test_unknown_backend_rejected():
    cfg = _cfg("polar")
    cache, row, start = _prefix_cache(cfg)
    q, kc, vc = _chunk()
    with pytest.raises(ValueError, match="unknown paged prefill backend"):
        pg.paged_prefill_attention(cache, q, kc, vc, row, start,
                                   jnp.asarray(1, jnp.int32),
                                   backend="cuda")


# ---------------------------------------------------------------------------
# rem == 0: the misaligned-residual invariant at exact page multiples
# ---------------------------------------------------------------------------


def test_rem_zero_residual_garbage_never_visible():
    """When a prefill chunk ends exactly on a page boundary (rem == 0),
    paged_prefill's clamped dynamic_slice writes *misaligned garbage* into
    key_residual (src/repro/core/paged_cache.py, res_lo clamp). The
    invariant: that garbage is dead — every later read is either masked by
    lengths or overwritten before becoming visible. Poisoning the residual
    after each rem == 0 chunk must not change a single output bit across a
    chunked prefill -> decode sequence at exact page multiples."""
    cfg = _cfg("polar", value_bits=4)

    def poison(cache):
        return dataclasses.replace(
            cache, key_residual=jnp.full_like(cache.key_residual, 1e9))

    row = jnp.asarray(ROW, jnp.int32)
    slot = jnp.asarray(0)
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    chunks = [(jax.random.normal(ks[2 * i], (1, H, g, d)),
               jax.random.normal(ks[2 * i + 1], (1, H, g, d)))
              for i in range(2)]
    q, kc, vc = _chunk(seed=11, tc=g)

    outs = []
    for arm in ("clean", "poisoned"):
        cache = pg.init_paged_cache(cfg, LAYOUT, H, d)
        arm_out = []
        for i, (k, v) in enumerate(chunks):       # two chunks of exactly g
            start = jnp.asarray(i * g, jnp.int32)
            arm_out.append(pg.paged_prefill_attention(
                cache, q, k, v, row, start, jnp.asarray(g, jnp.int32),
                backend="ref"))
            cache = pg.paged_prefill(cache, slot, row, k, v,
                                     jnp.asarray(g), start=start)
            if arm == "poisoned":
                cache = poison(cache)             # rem == 0: garbage anyway
        # decode step: append one token, attend over the whole slot
        k1 = jax.random.normal(ks[4], (LAYOUT.slots, H, 1, d))
        v1 = jax.random.normal(ks[5], (LAYOUT.slots, H, 1, d))
        table = jnp.tile(row[None], (LAYOUT.slots, 1))
        active = jnp.asarray([True, False, False])
        cache = pg.paged_append(cache, k1, v1, table, active)
        qd = jax.random.normal(jax.random.PRNGKey(9),
                               (LAYOUT.slots, H * QPK, d))
        for be in ("jnp", "paged_fused"):
            arm_out.append(pg.paged_decode_attention(cache, qd, table,
                                                     backend=be))
        outs.append(arm_out)

    for o_clean, o_poisoned in zip(*outs):
        np.testing.assert_array_equal(np.asarray(o_clean),
                                      np.asarray(o_poisoned))


# ---------------------------------------------------------------------------
# Engine: bit-identical greedy outputs with prefill_backend=paged_fused
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_cfg_params():
    cfg = reduce_for_smoke(get_config("tinyllama-1.1b"))
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def test_cb_engine_paged_fused_prefill_bit_identical(smoke_cfg_params):
    """Shared-prefix chunked-prefill workload under the CB engine: flipping
    prefill_backend jnp -> paged_fused (page-native kernel + width-sliced
    table rows) must leave every greedy output token bit-identical."""
    cfg0, params = smoke_cfg_params
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg0.vocab_size, (96,)).astype(np.int32)
    tails = [rng.integers(0, cfg0.vocab_size,
                          (int(rng.integers(8, 40)),)).astype(np.int32)
             for _ in range(5)]

    def _reqs():  # fresh Requests per arm: the engine mutates them
        return [Request(rid=i, prompt=np.concatenate([shared, tails[i]]),
                        max_new_tokens=6,
                        arrival_time=0.0 if i == 0 else 1.0 + 0.01 * i)
                for i in range(5)]

    results = {}
    for pb in ("jnp", "paged_fused"):
        cfg = dataclasses.replace(cfg0, decode_backend="paged_fused",
                                  prefill_backend=pb)
        eng = ContinuousBatchingEngine(
            get_model(cfg), params, max_slots=3, max_len=192,
            prefill_chunk=32, prefix_cache=True)
        out = eng.run(_reqs(), GenerationConfig(max_new_tokens=6))
        assert out["prefill_backend"] == pb
        results[pb] = {r.rid: list(r.out_tokens) for r in out["requests"]}
    assert results["jnp"] == results["paged_fused"]
