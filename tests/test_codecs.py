"""KeyCodec registry + CachePolicy: buffer contracts, golden parity with
the pre-registry implementation, dense-vs-paged parity for every registered
codec, and runtime extensibility with a third-party codec."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CachePolicy, QuantConfig, append, decode_attention, init_cache, prefill,
)
from repro.core import codecs
from repro.core import paged_cache as pg
from repro.core.cache_layout import LinearLayout, PagedLayout, PageAllocator


# ---------------------------------------------------------------------------
# A toy third-party codec, registered at runtime: token-wise symmetric
# 8-bit absmax. Exercises the full extension surface (allocation, encode,
# decode, default dequant-matmul score path) with none of the built-in code.
# ---------------------------------------------------------------------------


class ToyAbsmaxCodec(codecs.KeyCodec):
    name = "toy-absmax"

    def bits_per_element(self, cfg, head_dim):
        return 8.0 + 16.0 / head_dim

    def init_buffers(self, cfg, lead, tokens, head_dim, dtype):
        sdt = jnp.dtype(cfg.scale_dtype)
        return (jnp.zeros((*lead, tokens, head_dim), jnp.uint8),
                {"amax": jnp.zeros((*lead, tokens, 1), sdt)})

    def encode(self, cfg, k):
        a = jnp.maximum(jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1,
                                keepdims=True), 1e-8)
        codes = jnp.clip(jnp.round(k / a * 127.0) + 128.0, 0, 255)
        return codes.astype(jnp.uint8), {
            "amax": a.astype(jnp.dtype(cfg.scale_dtype))}

    def decode(self, cfg, codes, scales, dtype=jnp.float32):
        a = scales["amax"].astype(jnp.float32)
        return ((codes.astype(jnp.float32) - 128.0) / 127.0 * a).astype(dtype)

    # container() inherited: the generic codecs.CodecKeys wrapper


if "toy-absmax" not in codecs.registered_codecs():
    codecs.register_codec(ToyAbsmaxCodec())

ALL_CODECS = sorted(codecs.registered_codecs())
QUANTIZING = [n for n in ALL_CODECS if codecs.get_codec(n).quantizes]


def _cfg(method: str) -> QuantConfig:
    return QuantConfig(method=method, group_size=16, key_bits=8,
                       rho_bits=4, theta_bits=4, residual_dtype="float32")


def _kv(seed, b, h, t, d):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k1, (b, h, t, d)),
            jax.random.normal(k2, (b, h, t, d)))


# ---------------------------------------------------------------------------
# Registry + buffer contract
# ---------------------------------------------------------------------------


def test_builtin_codecs_registered():
    assert {"none", "int", "kivi", "zipcache", "polar"} <= set(ALL_CODECS)
    with pytest.raises(KeyError, match="unknown key codec"):
        codecs.get_codec("no-such-codec")
    with pytest.raises(ValueError, match="already registered"):
        codecs.register_codec(ToyAbsmaxCodec())


@pytest.mark.parametrize("name", ALL_CODECS)
def test_encode_matches_init_buffer_shapes(name):
    """codec.encode output must drop into codec.init_buffers storage —
    the contract the caches rely on for any registered codec."""
    cfg = _cfg(name)
    codec = codecs.get_codec(name)
    b, h, t, d = 2, 2, 64, 32
    k, _ = _kv(0, b, h, t, d)
    buf_codes, buf_scales = codec.init_buffers(cfg, (b, h), t, d,
                                               jnp.float32)
    codes, scales = codec.encode(cfg, k)
    assert codes.shape == buf_codes.shape
    assert set(scales) == set(buf_scales)
    for key in scales:
        assert scales[key].shape == buf_scales[key].shape, key


@pytest.mark.parametrize("name", QUANTIZING)
def test_codec_roundtrip(name):
    cfg = _cfg(name)
    codec = codecs.get_codec(name)
    k, _ = _kv(1, 2, 2, 64, 32)
    kt = codec.decode(cfg, *codec.encode(cfg, k))
    assert kt.shape == k.shape
    rel = float(jnp.linalg.norm(k - kt) / jnp.linalg.norm(k))
    tol = 0.35 if name == "polar" else 0.02   # 8-bit baselines vs polar 4+4
    assert rel < tol, (name, rel)


@pytest.mark.parametrize("name", ALL_CODECS)
def test_generic_encode_decode_keys_entry_points(name):
    """quantizers.encode_keys/decode_keys must round-trip every registered
    codec — third-party codecs ride the generic CodecKeys container."""
    from repro.core.quantizers import decode_keys, encode_keys

    cfg = _cfg(name)
    k, _ = _kv(8, 2, 2, 64, 32)
    kt = decode_keys(encode_keys(k, cfg))
    assert kt.shape == k.shape
    np.testing.assert_allclose(
        np.asarray(kt),
        np.asarray(codecs.get_codec(name).decode(cfg, *codecs.get_codec(
            name).encode(cfg, k))) if codecs.get_codec(name).quantizes
        else np.asarray(k, np.float32),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", ALL_CODECS)
def test_codec_scores_match_dequant_matmul(name):
    """The codec score path (LUT for polar) must agree with the oracle
    dequantize-then-matmul on its own decode output."""
    cfg = _cfg(name)
    codec = codecs.get_codec(name)
    k, _ = _kv(2, 1, 2, 32, 16)
    codes, scales = codec.encode(cfg, k)
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 4, 16))
    s = codec.scores(cfg, q, codes, scales)
    oracle = jnp.einsum("bhqd,bhtd->bhqt", q,
                        codec.decode(cfg, codes, scales))
    np.testing.assert_allclose(np.asarray(s), np.asarray(oracle),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Golden parity with the pre-registry implementation
# ---------------------------------------------------------------------------

def _golden_inputs(seed: int, shape) -> jnp.ndarray:
    """Platform-exact pseudo-normal fp32 inputs that depend on no PRNG
    implementation: a 64-bit LCG (Knuth's MMIX constants) drawn in pure
    integer arithmetic, four 16-bit draws summed per value (Irwin-Hall)
    and scaled to about unit variance. Every value is an exact multiple of
    2**-15, so the fp32 inputs are bit-identical everywhere."""
    n = int(np.prod(shape)) * 4
    x = np.empty(n, np.uint64)
    state = np.uint64(seed)
    a, c = np.uint64(6364136223846793005), np.uint64(1442695040888963407)
    with np.errstate(over="ignore"):
        for i in range(n):
            state = state * a + c
            x[i] = state >> np.uint64(48)
    draws = x.reshape(-1, 4).astype(np.int64).sum(axis=1) - 2 * 65535
    return jnp.asarray((draws * 2.0 ** -15).reshape(shape), jnp.float32)


# Pinned from the codec registry at shapes B,H,d,g,T = 1,2,32,16,70:
# (key-code sum, sum(out), sum|out|) for prefill(64) + 6 appends +
# decode_attention, fp32, on the _golden_inputs streams below. The
# registry was itself pinned to the pre-registry string-dispatch
# implementation; these sums guard both against behaviour drift.
_GOLDEN = {
    "polar": (236475, 1.8684691191e+00, 2.7099872589e+01),
    "kivi": (30876, 1.3096932173e+00, 2.7681785583e+01),
    "zipcache": (30756, 1.3180801868e+00, 2.6527612686e+01),
    "int": (33640, 1.2014031410e+00, 2.6737098694e+01),
    "none": (0, 1.3729696274e+00, 2.7152233124e+01),
    "polar+v4": (236475, 1.4071828127e+00, 2.6993419647e+01),
}


@pytest.mark.parametrize("name", sorted(_GOLDEN))
def test_golden_parity_with_pre_registry_implementation(name):
    method, _, v = name.partition("+v")
    value_bits = int(v) if v else 0
    B, H, d, g, T = 1, 2, 32, 16, 70
    k = _golden_inputs(42, (B, H, T, d))
    v_ = _golden_inputs(43, (B, H, T, d))
    cfg = QuantConfig(method=method, group_size=g, key_bits=4,
                      value_bits=value_bits, residual_dtype="float32")
    cache = prefill(init_cache(cfg, B, H, d, 128, dtype=jnp.float32),
                    k[:, :, :64], v_[:, :, :64])
    for i in range(64, T):
        cache = append(cache, k[:, :, i : i + 1], v_[:, :, i : i + 1])
    q = _golden_inputs(7, (B, H * 2, d))
    out = decode_attention(cache, q)
    code_sum, out_sum, out_abs = _GOLDEN[name]
    if cache.key_codes.dtype == jnp.uint8:
        assert int(np.asarray(cache.key_codes, np.int64).sum()) == code_sum
    np.testing.assert_allclose(float(out.sum()), out_sum, rtol=1e-6)
    np.testing.assert_allclose(float(jnp.abs(out).sum()), out_abs, rtol=1e-6)


# ---------------------------------------------------------------------------
# Dense vs paged parity for EVERY registered codec (toy included)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ALL_CODECS)
def test_dense_paged_parity(name):
    cfg = _cfg(name)
    B, H, d, g = 1, 2, 32, 16
    layout = PagedLayout(page_size=g, num_pages=12, slots=2, pages_per_slot=6)
    tp, tdec, slot, bucket = 38, 13, 1, 48
    t = tp + tdec
    k, v = _kv(5, B, H, t, d)
    cap = layout.pages_per_slot * g

    dense = prefill(init_cache(cfg, B, H, d, cap, layout=LinearLayout(cap)),
                    k[:, :, :tp], v[:, :, :tp])
    for i in range(tp, t):
        dense = append(dense, k[:, :, i : i + 1], v[:, :, i : i + 1])

    alloc = PageAllocator(layout)
    assert alloc.alloc(slot, layout.pages_for(tp))
    paged = pg.init_paged_cache(cfg, layout, H, d)
    kp = jnp.pad(k[:, :, :tp], ((0, 0), (0, 0), (0, bucket - tp), (0, 0)))
    vp = jnp.pad(v[:, :, :tp], ((0, 0), (0, 0), (0, bucket - tp), (0, 0)))
    paged = pg.paged_prefill(paged, jnp.asarray(slot), alloc.table()[slot],
                             kp, vp, jnp.asarray(tp))
    ap = jax.jit(pg.paged_append)
    for i in range(tp, t):
        ln = int(paged.lengths[slot])
        if ln % g == 0 and alloc.slot_pages(slot) <= ln // g:
            assert alloc.alloc(slot, 1)
        s = layout.slots
        kn = jnp.zeros((s, H, 1, d)).at[slot].set(k[0, :, i : i + 1])
        vn = jnp.zeros((s, H, 1, d)).at[slot].set(v[0, :, i : i + 1])
        active = jnp.zeros((s,), bool).at[slot].set(True)
        paged = ap(paged, kn, vn, alloc.table(), active)

    view = pg.gather_view(paged, alloc.table())
    if codecs.get_codec(name).grouped:
        nfull = int(dense.length) // g
        np.testing.assert_array_equal(
            np.asarray(dense.key_codes)[0, :, :nfull],
            np.asarray(view.key_codes)[slot, :, :nfull])
    elif codecs.get_codec(name).quantizes:
        tlen = int(dense.length)
        np.testing.assert_array_equal(
            np.asarray(dense.key_codes)[0, :, :tlen],
            np.asarray(view.key_codes)[slot, :, :tlen])

    q = jax.random.normal(jax.random.PRNGKey(9), (B, H * 2, d))
    qs = jnp.zeros((layout.slots, H * 2, d)).at[slot].set(q[0])
    o_dense = decode_attention(dense, q)
    o_paged = pg.paged_decode_attention(paged, qs, alloc.table(),
                                        backend="jnp")
    np.testing.assert_allclose(np.asarray(o_dense[0]),
                               np.asarray(o_paged[slot]),
                               atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Toy codec end-to-end through make_cache -> decode attention
# ---------------------------------------------------------------------------


def test_third_party_codec_through_make_cache():
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import attn_block as AB

    cfg = reduce_for_smoke(get_config("tinyllama-1.1b"))
    cfg = dataclasses.replace(
        cfg, quant=dataclasses.replace(cfg.quant, method="toy-absmax"),
        dtype="float32")
    cache = AB.make_cache(cfg, batch=2, max_len=96)
    cache_fp = AB.make_cache(
        dataclasses.replace(cfg, quant=dataclasses.replace(
            cfg.quant, method="none")), batch=2, max_len=96)
    h, d = cfg.num_kv_heads, cfg.head_dim
    k, v = _kv(11, 2, h, 70, d)
    cache = prefill(cache, k, v)
    cache_fp = prefill(cache_fp, k, v)
    q = jax.random.normal(jax.random.PRNGKey(12), (2, cfg.num_heads, d))
    out = decode_attention(cache, q)
    ref = decode_attention(cache_fp, q)
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert rel < 0.05, rel  # 8-bit absmax keys track the fp cache closely
    assert cfg.quant.key_bits_per_element(d) == 8.0 + 16.0 / d


# ---------------------------------------------------------------------------
# CachePolicy
# ---------------------------------------------------------------------------


def test_cache_policy_segments_and_lookup():
    int8 = QuantConfig(method="int", key_bits=8)
    polar = QuantConfig(method="polar")
    pol = CachePolicy.first_k(2, int8, polar)
    assert pol.layer_config(0) == int8
    assert pol.layer_config(1) == int8
    assert pol.layer_config(5) == polar
    assert pol.segments(6) == ((0, 2, int8), (2, 6, polar))
    assert not pol.is_uniform

    uni = CachePolicy.uniform(polar)
    assert uni.is_uniform
    assert uni.segments(4) == ((0, 4, polar),)

    sparse = CachePolicy.per_layer({1: int8}, polar)
    assert sparse.segments(3) == ((0, 1, polar), (1, 2, int8), (2, 3, polar))


def test_cache_policy_avg_bits_and_group_size():
    int8 = QuantConfig(method="int", key_bits=8, group_size=128)
    polar = QuantConfig(method="polar", rho_bits=4, theta_bits=4,
                        group_size=128)
    pol = CachePolicy.first_k(2, int8, polar)
    avg = pol.avg_key_bits(4, head_dim=128)
    expect = (2 * (8 + 32 / 128) + 2 * 4.25) / 4
    assert abs(avg - expect) < 1e-6
    assert pol.page_group_size() == 128

    bad = CachePolicy.first_k(1, dataclasses.replace(int8, group_size=64),
                              polar)
    with pytest.raises(ValueError, match="one group size"):
        bad.page_group_size()
    assert bad.max_group_size() == 128   # dense buckets use the largest

    small = pol.map(lambda q: dataclasses.replace(q, group_size=32))
    assert small.page_group_size() == 32
    assert small.layer_config(0).method == "int"


def test_mixed_policy_dense_cache_state():
    """Per-layer mixed policy through the dense transformer serving state:
    segment caches carry each layer's own codec buffers."""
    from repro.configs import get_config, reduce_for_smoke
    from repro.models import transformer as TF

    base = reduce_for_smoke(get_config("tinyllama-1.1b"))
    policy = CachePolicy.first_k(
        1, dataclasses.replace(base.quant, method="int", key_bits=8),
        base.quant)
    cfg = dataclasses.replace(base, cache_policy=policy)
    caches = TF.init_decode_caches(cfg, batch=2, max_len=64)
    assert len(caches) == 2                       # int segment + polar segment
    assert caches[0].cfg.method == "int"
    assert caches[1].cfg.method == "polar"
    per_layer = TF.per_layer_cache_bytes(cfg, caches)
    assert len(per_layer) == cfg.num_layers
    assert all(b > 0 for b in per_layer)
