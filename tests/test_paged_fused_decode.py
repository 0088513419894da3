"""Page-native fused decode: parity with the gathered reference across
every registered codec, fragmented/non-monotonic page tables, scratch-page
masking, width-sliced tables, mixed per-layer policies under the
continuous-batching engine, and decode-state donation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantConfig, codecs
from repro.core import paged_cache as pg
from repro.core.cache_layout import PagedLayout, PageAllocator
from repro.utils import tree_bytes

H, d, g = 2, 32, 16
LAYOUT = PagedLayout(page_size=g, num_pages=24, slots=3, pages_per_slot=6)


def _cfg(method: str, value_bits: int = 0) -> QuantConfig:
    return QuantConfig(method=method, group_size=g, key_bits=8,
                       value_bits=value_bits, rho_bits=4, theta_bits=4,
                       residual_dtype="float32")


def _fill_slots(cfg, layout=LAYOUT, lengths=(9, 38, 64), alloc=None):
    """Prefill each slot to its length (heterogeneous, residuals included)."""
    alloc = alloc or PageAllocator(layout)
    cache = pg.init_paged_cache(cfg, layout, H, d)
    for slot, tp in enumerate(lengths):
        assert alloc.alloc(slot, layout.pages_for(max(tp, 1)))
        bucket = -(-tp // g) * g
        ks = jax.random.split(jax.random.PRNGKey(slot), 2)
        k = jax.random.normal(ks[0], (1, H, bucket, d))
        v = jax.random.normal(ks[1], (1, H, bucket, d))
        cache = pg.paged_prefill(cache, jnp.asarray(slot),
                                 alloc.table()[slot], k, v, jnp.asarray(tp))
    return cache, alloc


def _q(seed=7, slots=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (slots, H * 2, d))


# ---------------------------------------------------------------------------
# Parity: page-native dispatch vs the gathered reference, whole registry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(codecs.registered_codecs()))
def test_paged_fused_matches_gathered_reference(name):
    """paged_decode_attention(backend="paged_fused") must agree with the
    gathered jnp reference for every registered codec — page-native kernel
    for codecs with the capability, gathered fallback for the rest."""
    cfg = _cfg(name)
    cache, alloc = _fill_slots(cfg)
    q = _q()
    o_ref = pg.paged_decode_attention(cache, q, alloc.table(), backend="jnp")
    o_fused = pg.paged_decode_attention(cache, q, alloc.table(),
                                        backend="paged_fused")
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_fused),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("value_bits", [0, 4])
@pytest.mark.parametrize("backend", ["paged_fused", "interpret"])
def test_polar_page_native_kernel_parity(backend, value_bits):
    """The page-table-walking kernel (jnp page walk AND interpret-mode
    Pallas, so CPU CI exercises the kernel body) vs the gathered dense
    path, heterogeneous per-slot lengths + quantized values."""
    cfg = _cfg("polar", value_bits=value_bits)
    cache, alloc = _fill_slots(cfg)
    q = _q()
    o_ref = pg.paged_decode_attention(cache, q, alloc.table(), backend="jnp")
    o = pg.paged_decode_attention(cache, q, alloc.table(), backend=backend)
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o),
                               atol=2e-5, rtol=1e-4)


#: (Hkv, query heads per KV head, head_dim) as served
SERVED_WIDTHS = {"yi-9b": (4, 8, 128), "granite-8b": (8, 4, 128),
                 "tinyllama-1.1b": (4, 8, 64)}


@pytest.mark.parametrize("quantized_values", [False, True],
                         ids=["fp_values", "uint8_values"])
@pytest.mark.parametrize("widths", list(SERVED_WIDTHS.values()),
                         ids=list(SERVED_WIDTHS))
def test_page_native_kernel_matches_oracle_at_served_widths(
        widths, quantized_values):
    """The interpret-mode kernel vs ``ref_polar_paged_decode_attention`` at
    page 128 and served head widths, the shapes whose page decode and MXU
    score product the small fixtures above never build. Three slots on a
    shuffled pool hold 4, 1 and 3 live pages of 4: the one-page slot
    clamps every later grid step to its only page."""
    from repro.kernels import ref as R
    from repro.kernels.paged_decode import polar_paged_decode_grouped
    hkv, qh, dh = widths
    gs, n, slots = 128, 4, 3
    pp, p = slots * n + 1, dh // 2            # pool pages + scratch page
    ks = jax.random.split(jax.random.PRNGKey(dh + qh), 9)

    def unif(k, shape, lo, hi):
        return jax.random.uniform(k, shape, minval=lo, maxval=hi)

    q = jax.random.normal(ks[0], (slots, hkv, qh, dh)) * dh ** -0.5
    codes = jax.random.randint(ks[1], (pp, hkv, gs, p), 0, 256,
                               jnp.int32).astype(jnp.uint8)
    stat = (pp, hkv, 1, p)
    rs, rz = unif(ks[2], stat, 0.05, 0.3), unif(ks[3], stat, 0.0, 0.5)
    ts, tz = unif(ks[4], stat, 0.3, 0.5), unif(ks[5], stat, -0.2, 0.2)
    if quantized_values:
        vals = (jax.random.randint(ks[6], (pp, hkv, gs, dh), 0, 256,
                                   jnp.int32).astype(jnp.uint8),
                unif(ks[7], (pp, hkv, gs, 1), 0.005, 0.02),
                unif(ks[8], (pp, hkv, gs, 1), -2.0, 0.0))
    else:
        vals = (jax.random.normal(ks[6], (pp, hkv, gs, dh)), None, None)
    table = jnp.asarray(np.random.default_rng(dh).permutation(slots * n)
                        .reshape(slots, n), jnp.int32)
    flushed = jnp.asarray([4, 1, 3], jnp.int32) * gs
    got = polar_paged_decode_grouped(q, codes, rs, rz, ts, tz, *vals, table,
                                     flushed, interpret=True)
    want = R.ref_polar_paged_decode_attention(
        q, codes, rs, rz, ts, tz, *vals, table, flushed, r_bits=4, t_bits=4)
    for x, y in zip(got, want):                      # out, m, l partials
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   atol=2e-5, rtol=1e-4)


def test_gathered_backend_still_runs_dense_fused_path():
    """backend="gathered" keeps the PR-2 formulation alive for A/B."""
    cfg = _cfg("polar")
    cache, alloc = _fill_slots(cfg)
    q = _q()
    o_ref = pg.paged_decode_attention(cache, q, alloc.table(), backend="jnp")
    o = pg.paged_decode_attention(cache, q, alloc.table(), backend="gathered")
    np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o),
                               atol=2e-5, rtol=1e-4)


def test_unknown_backend_rejected():
    cfg = _cfg("polar")
    cache, alloc = _fill_slots(cfg)
    with pytest.raises(ValueError, match="unknown paged decode backend"):
        pg.paged_decode_attention(cache, _q(), alloc.table(),
                                  backend="warp-drive")


# ---------------------------------------------------------------------------
# Fragmented / non-monotonic page tables + scratch-page masking
# ---------------------------------------------------------------------------


def test_fragmented_non_monotonic_table_parity():
    """Slots admitted onto recycled pages (table rows out of pool order):
    page-native and gathered paths must both match bit-for-bit semantics."""
    lay = PagedLayout(page_size=g, num_pages=10, slots=3, pages_per_slot=6)
    cfg = _cfg("polar", value_bits=4)
    # alloc/free churn: the free list wraps, so new rows interleave fresh
    # and recycled page ids
    alloc = PageAllocator(lay)
    assert alloc.alloc(0, 4)          # pages 0..3
    assert alloc.alloc(1, 3)          # pages 4..6
    assert alloc.alloc(2, 2)          # pages 7..8
    alloc.free_slot(0)                # free list: [9, 0, 1, 2, 3]
    alloc.free_slot(2)                # free list: [9, 0, 1, 2, 3, 7, 8]
    cache = pg.init_paged_cache(cfg, lay, H, d)
    for slot, tp in [(0, 40), (2, 25)]:   # rows [9, 0, 1] and [2, 3]
        assert alloc.alloc(slot, lay.pages_for(tp))
        bucket = -(-tp // g) * g
        ks = jax.random.split(jax.random.PRNGKey(10 + slot), 2)
        k = jax.random.normal(ks[0], (1, H, bucket, d))
        v = jax.random.normal(ks[1], (1, H, bucket, d))
        cache = pg.paged_prefill(cache, jnp.asarray(slot),
                                 alloc.table()[slot], k, v, jnp.asarray(tp))
    rows = alloc.table_np()
    assert (np.diff(rows[0][rows[0] != lay.scratch_page]) < 0).any(), \
        "fixture should produce a non-monotonic row"
    q = _q()
    o_ref = pg.paged_decode_attention(cache, q, alloc.table(), backend="jnp")
    for backend in ("paged_fused", "interpret"):
        o = pg.paged_decode_attention(cache, q, alloc.table(),
                                      backend=backend)
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o),
                                   atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("backend", ["jnp", "paged_fused", "interpret"])
def test_scratch_page_masked_at_page_granularity(backend):
    """Regression (fragmented pool): a poisoned scratch page — NaN stats
    and value rows, the worst stale garbage masked writes could leave —
    must not leak into any slot's output. gather_view now masks unassigned
    entries at *page* granularity before scoring; the page-native kernel
    never dereferences the scratch page at all."""
    cfg = _cfg("polar", value_bits=0)
    cache, alloc = _fill_slots(cfg, lengths=(9, 38, 64))
    clean = pg.paged_decode_attention(cache, _q(), alloc.table(),
                                      backend="jnp")
    sp = LAYOUT.scratch_page
    bad = jnp.nan
    poisoned = dataclasses.replace(
        cache,
        key_scales={k: v.at[sp].set(bad) for k, v in cache.key_scales.items()},
        value_fp=cache.value_fp.at[sp].set(bad))
    out = pg.paged_decode_attention(poisoned, _q(), alloc.table(),
                                    backend=backend)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(clean), np.asarray(out),
                               atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Width-sliced page tables (engine decode buckets)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "paged_fused", "interpret"])
def test_width_sliced_table_matches_full(backend):
    """Slicing the table to the live pages (the engines' pow2 width
    buckets) must not change the result — only the read volume."""
    cfg = _cfg("polar", value_bits=4)
    cache, alloc = _fill_slots(cfg, lengths=(9, 38, 64))
    q = _q()
    full = pg.paged_decode_attention(cache, q, alloc.table(),
                                     backend=backend)
    live = max(LAYOUT.pages_for(t) for t in (9, 38, 64))   # 4 of 6 pages
    sliced = pg.paged_decode_attention(cache, q, alloc.table()[:, :live],
                                       backend=backend)
    np.testing.assert_allclose(np.asarray(full), np.asarray(sliced),
                               atol=2e-5, rtol=1e-4)


def test_append_with_sliced_table():
    """paged_append must address pages through a width-sliced table too
    (clamped group index; inactive slots land on scratch)."""
    cfg = _cfg("polar")
    cache, alloc = _fill_slots(cfg, lengths=(9, 38, 64))
    w = max(LAYOUT.pages_for(t + 1) for t in (9, 38, 64))
    s = LAYOUT.slots
    kn = jax.random.normal(jax.random.PRNGKey(0), (s, H, 1, d))
    active = jnp.ones((s,), bool)
    a_full = pg.paged_append(cache, kn, kn, alloc.table(), active)
    a_sliced = pg.paged_append(cache, kn, kn, alloc.table()[:, :w], active)
    for x, y in zip(jax.tree_util.tree_leaves(a_full),
                    jax.tree_util.tree_leaves(a_sliced)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Model + engine integration (per-segment dispatch, donation)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import get_model
    cfg = reduce_for_smoke(get_config("tinyllama-1.1b"))
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def test_model_decode_paged_backend_parity(smoke_model):
    """decode_paged logits agree across jnp / paged_fused / interpret —
    the cfg-driven dispatch reaches the page-native kernel."""
    from repro.models import get_model
    cfg, m, params = smoke_model
    lay = PagedLayout(page_size=cfg.quant.group_size, num_pages=8, slots=2,
                      pages_per_slot=4)
    logits = {}
    for be in ("jnp", "paged_fused", "interpret"):
        mb = get_model(dataclasses.replace(cfg, decode_backend=be))
        alloc = PageAllocator(lay)
        assert alloc.alloc(0, 2) and alloc.alloc(1, 1)
        state = mb.init_paged_state(lay)
        rng = np.random.default_rng(0)
        for slot, tl in [(0, 40), (1, 17)]:
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                            (1, 64)).astype(np.int32))
            _, state = mb.prefill_paged(params, toks, state,
                                        jnp.asarray(slot, jnp.int32),
                                        alloc.table()[slot],
                                        jnp.asarray(tl, jnp.int32))
        lg, _ = mb.decode_paged(params, state,
                                jnp.asarray([3, 5], jnp.int32),
                                alloc.table(), jnp.ones((2,), bool))
        logits[be] = np.asarray(lg)
    np.testing.assert_allclose(logits["jnp"], logits["paged_fused"],
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(logits["paged_fused"], logits["interpret"],
                               atol=1e-4, rtol=1e-4)


def test_mixed_policy_paged_fused_engine(smoke_model):
    """first_k mixed policy under continuous batching with
    decode_backend="paged_fused": the polar segment runs page-native, the
    int8 segment takes the gathered fallback — requests must complete."""
    from repro.core import CachePolicy
    from repro.models import get_model
    from repro.serve import ContinuousBatchingEngine, GenerationConfig, Request
    cfg, m, params = smoke_model
    policy = CachePolicy.first_k(
        1, dataclasses.replace(cfg.quant, method="int", key_bits=8),
        dataclasses.replace(cfg.quant, method="polar"))
    cfg_m = dataclasses.replace(cfg, cache_policy=policy,
                                decode_backend="paged_fused")
    eng = ContinuousBatchingEngine(get_model(cfg_m), params, max_slots=2,
                                   max_len=128)
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        (int(rng.integers(8, 50)),)
                                        ).astype(np.int32),
                    max_new_tokens=6, arrival_time=i * 0.01)
            for i in range(4)]
    out = eng.run(reqs, GenerationConfig())
    assert len(out["requests"]) == 4
    assert all(r.done_tokens == 6 for r in out["requests"])
    assert out["decode_backend"] == "paged_fused"
    assert out["decode_step_s_mean"] > 0.0


def test_decode_state_donated_no_per_step_copy(smoke_model):
    """Both engines donate the decode state: the compiled step aliases the
    cache buffers in place of copying them, and the only fresh allocation
    per step is logits-sized — asserted via memory_analysis/cost_analysis
    on the exact jitted callables the engines run."""
    from repro.serve import ContinuousBatchingEngine, ServeEngine
    cfg, m, params = smoke_model

    # --- paged engine ---
    eng = ContinuousBatchingEngine(m, params, max_slots=2, max_len=128)
    state = m.init_paged_state(eng.layout)
    s = eng.layout.slots
    args = (params, state, jnp.zeros((s,), jnp.int32),
            jnp.zeros((s, eng.layout.pages_per_slot), jnp.int32),
            jnp.zeros((s,), bool))
    compiled = eng.core._decode.lower(*args).compile()
    ma = compiled.memory_analysis()
    state_bytes = tree_bytes(state)
    assert ma.alias_size_in_bytes >= 0.9 * state_bytes
    fresh_out = ma.output_size_in_bytes - ma.alias_size_in_bytes
    assert fresh_out < max(1 << 20, 0.1 * state_bytes)
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert ca.get("bytes accessed", 0.0) > 0.0   # sanity: analysis populated

    # --- dense engine ---
    dense = ServeEngine(m, params, max_len=128)
    dstate = m.init_decode_state(2, 128)
    compiled = dense._decode.lower(
        params, dstate, jnp.zeros((2,), jnp.int32)).compile()
    ma = compiled.memory_analysis()
    dbytes = tree_bytes(dstate)
    assert ma.alias_size_in_bytes >= 0.9 * dbytes
    assert (ma.output_size_in_bytes - ma.alias_size_in_bytes
            < max(1 << 20, 0.1 * dbytes))
