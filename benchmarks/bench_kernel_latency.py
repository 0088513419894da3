"""Table 4 / Figure 3 analog: query-key decode kernel latency.

The paper times Triton kernels on GPU across (batch, context). On this
CPU container we time the *jit-compiled jnp paths* (fp16-style dense QK,
dequant-then-matmul, and the LUT path) as a relative-structure check, and
report the analytic TPU bytes-moved model that the real kernel's roofline
win comes from (memory-bound decode: bytes ~ latency).

Columns: wall-clock us/call (CPU, relative only) + derived per-token HBM
bytes for a v5e (absolute, the quantity that sets TPU decode latency).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.common import emit, rope_structured_keys, time_fn
from repro.core.quantizers import QuantConfig, encode_polar_keys
from repro.core import lut as lut_mod
from repro.launch.chips import chip_peaks
from repro.core.quantizers import decode_polar_keys

# Llama-3.1-8B attention geometry (paper §4.2): 32 q heads, 8 kv heads, d=128
QH, HKV, D = 32, 8, 128


def hbm_bytes_per_layer(t: int, b: int, method: str, g: int = 128) -> int:
    """Bytes read from HBM per decode step for the K-score pass (per layer)."""
    pairs = D // 2
    if method == "fp16":
        per_tok = D * 2
    elif method == "kivi4":
        per_tok = D // 2 + 4 * D * 2 // g      # 4-bit codes + fp16 z/s per group
    elif method == "polar44":
        per_tok = pairs + 4 * pairs * 2 * 2 // g  # packed u8/pair + 4 fp16 stats
    elif method == "polar33":
        per_tok = (pairs * 6 + 7) // 8 + 4 * pairs * 2 * 2 // g
    else:
        raise ValueError(method)
    return b * HKV * t * per_tok


def run_paged() -> None:
    """Decode-step latency split on the paged cache: the gather_view copy
    vs the attention math, gathered vs page-native.

    Live context is held fixed while the pool *capacity* sweeps: the
    gathered path re-materializes every slot's full capacity each step
    (cost grows with the sweep), the page-native path walks only the live
    pages through a width-sliced table (cost flat). This is the structural
    O(capacity) -> O(live tokens) claim, measured.
    """
    import functools as ft

    import numpy as np

    from repro.core import paged_cache as pgc
    from repro.core.cache_layout import PageAllocator, PagedLayout
    from repro.core.quantizers import QuantConfig
    from repro.utils import pow2_bucket

    g = 64
    slots, live = 4, 256
    cfg = QuantConfig(method="polar", group_size=g, value_bits=4)
    for cap_tokens in (1024, 4096, 8192):
        lay = PagedLayout(page_size=g, num_pages=slots * cap_tokens // g,
                          slots=slots, pages_per_slot=cap_tokens // g)
        alloc = PageAllocator(lay)
        cache = pgc.init_paged_cache(cfg, lay, HKV, D)
        for s in range(slots):
            tl = live - 7 * s          # heterogeneous live lengths
            if not alloc.alloc(s, lay.pages_for(tl)):
                raise RuntimeError("page pool sized to fit every slot")
            bucket = -(-tl // g) * g
            k = rope_structured_keys(jax.random.PRNGKey(s), 1, HKV, bucket, D)
            v = jax.random.normal(jax.random.PRNGKey(100 + s),
                                  (1, HKV, bucket, D))
            cache = pgc.paged_prefill(cache, jnp.asarray(s),
                                      alloc.table()[s], k, v,
                                      jnp.asarray(tl))
        q = jax.random.normal(jax.random.PRNGKey(1), (slots, QH, D))
        table = alloc.table()
        wp = min(pow2_bucket(lay.pages_for(live), 1), lay.pages_per_slot)
        sliced = table[:, :wp]

        gather = jax.jit(pgc.gather_view)
        gathered = jax.jit(ft.partial(pgc.paged_decode_attention,
                                      backend="gathered"))
        paged = jax.jit(ft.partial(pgc.paged_decode_attention,
                                   backend="paged_fused"))
        us_gather = time_fn(gather, cache, table, iters=10)
        us_gathered = time_fn(gathered, cache, q, table, iters=10)
        us_paged = time_fn(paged, cache, q, sliced, iters=10)
        tag = f"paged_decode/cap{cap_tokens}_live{live}"
        emit(f"{tag}/gather_view_copy", us_gather,
             f"pool_bytes={sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree_util.tree_leaves(cache))}")
        emit(f"{tag}/gathered_total", us_gathered,
             "gather+dense fused (O(capacity))")
        emit(f"{tag}/paged_fused", us_paged,
             f"page-native, table width {wp} pages (O(live))")
        emit(f"{tag}/speedup_gathered_over_paged", 0.0,
             f"ratio={us_gathered / max(us_paged, 1e-9):.2f}x")


def run_prefill() -> None:
    """Chunked-prefill attention latency on the paged cache: jnp gather
    (O(capacity)) vs page-native fused (O(live prefix)).

    One slot holds a fixed live prefix while the pool capacity sweeps.
    The jnp reference gathers ``pool[page_row]`` over the *full* table row
    each chunk — cost grows with capacity even though the live prefix
    never changes — while the page-native path walks only the live pages
    through a width-sliced row and stays flat.
    """
    import functools as ft

    from repro.core import paged_cache as pgc
    from repro.core.cache_layout import PageAllocator, PagedLayout
    from repro.core.quantizers import QuantConfig
    from repro.utils import pow2_bucket

    g = 64
    live, tc = 512, 128                    # fixed prefix + one chunk
    cfg = QuantConfig(method="polar", group_size=g, value_bits=4)
    for cap_tokens in (1024, 4096, 8192):
        lay = PagedLayout(page_size=g, num_pages=cap_tokens // g + 1,
                          slots=1, pages_per_slot=cap_tokens // g)
        alloc = PageAllocator(lay)
        cache = pgc.init_paged_cache(cfg, lay, HKV, D)
        if not alloc.alloc(0, lay.pages_for(live)):
            raise RuntimeError("page pool sized to fit the prefix")
        k = rope_structured_keys(jax.random.PRNGKey(0), 1, HKV, live, D)
        v = jax.random.normal(jax.random.PRNGKey(100), (1, HKV, live, D))
        cache = pgc.paged_prefill(cache, jnp.asarray(0), alloc.table()[0],
                                  k, v, jnp.asarray(live))
        q = jax.random.normal(jax.random.PRNGKey(1), (1, QH, tc, D))
        kc = rope_structured_keys(jax.random.PRNGKey(2), 1, HKV, tc, D)
        vc = jax.random.normal(jax.random.PRNGKey(3), (1, HKV, tc, D))
        row = alloc.table()[0]
        start = jnp.asarray(live, jnp.int32)
        clen = jnp.asarray(tc, jnp.int32)
        wp = min(pow2_bucket(lay.pages_for(live + tc), 1),
                 lay.pages_per_slot)

        jnp_ref = jax.jit(ft.partial(pgc.paged_prefill_attention,
                                     backend="jnp"))
        fused = jax.jit(ft.partial(pgc.paged_prefill_attention,
                                   backend="paged_fused"))
        # few iters: the jnp arm's dense softmax over the full capacity is
        # seconds per call on CPU at 8k (which is the point being measured)
        us_jnp = time_fn(jnp_ref, cache, q, kc, vc, row, start, clen,
                         iters=3, warmup=1)
        us_fused = time_fn(fused, cache, q, kc, vc, row[:wp], start, clen,
                           iters=3, warmup=1)
        tag = f"paged_prefill/cap{cap_tokens}_live{live}_chunk{tc}"
        emit(f"{tag}/jnp_gather", us_jnp,
             "full-pool gather + dense softmax (O(capacity))")
        emit(f"{tag}/page_native", us_fused,
             f"fused over live pages, table width {wp} (O(live))")
        emit(f"{tag}/speedup_jnp_over_page_native", 0.0,
             f"ratio={us_jnp / max(us_fused, 1e-9):.2f}x")


def run() -> None:
    g = 128
    for b, t in [(1, 4096), (8, 4096), (8, 8192), (1, 32768)]:
        key = jax.random.PRNGKey(0)
        k = rope_structured_keys(key, b, HKV, t, D)
        q = jax.random.normal(jax.random.PRNGKey(1), (b, HKV, QH // HKV, D))
        cfg = QuantConfig(method="polar", group_size=g)
        pk = encode_polar_keys(k, cfg)
        pk_exp = jax.tree_util.tree_map(lambda a: a[:, :, None], pk)

        fp_qk = jax.jit(lambda q, k: jnp.einsum("bhqd,bhtd->bhqt", q, k))
        lut_qk = jax.jit(functools.partial(lut_mod.lut_qk_scores))
        deq_qk = jax.jit(lambda q, pk: jnp.einsum(
            "bhqd,bhtd->bhqt", q, decode_polar_keys(pk)))

        us_fp = time_fn(fp_qk, q, k, iters=10)
        us_lut = time_fn(lut_qk, q, pk_exp, iters=10)
        us_deq = time_fn(deq_qk, q, pk, iters=10)

        for name, us in [("fp16", us_fp), ("polar44_lut", us_lut),
                         ("polar44_dequant", us_deq)]:
            mth = {"fp16": "fp16"}.get(name, "polar44")
            hbm = hbm_bytes_per_layer(t, b, mth, g)
            emit(f"qk_latency/b{b}_t{t}/{name}", us,
                 f"tpu_hbm_bytes={hbm};v5e_mem_us="
                 f"{hbm / chip_peaks('TPU v5 lite').hbm_bw * 1e6:.2f}")
        # paper Table 4 headline: byte ratio fp16 / polar
        ratio = hbm_bytes_per_layer(t, b, "fp16") / hbm_bytes_per_layer(
            t, b, "polar44", g)
        emit(f"qk_latency/b{b}_t{t}/bytes_ratio_fp16_over_polar44", 0.0,
             f"ratio={ratio:.2f}x")
    run_paged()
    run_prefill()


if __name__ == "__main__":
    run()
