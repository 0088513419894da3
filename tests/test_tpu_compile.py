"""Compile the serving kernels for a described TPU v5e at tinyllama-1.1b's
published widths (Hkv 4, 8 query heads per KV head, head_dim 64, page 128,
uint8 codes); the paged decode kernel also at the benchmark cells' widths,
yi-9b's (Hkv 4, 8 query heads each, head_dim 128) and granite-8b's
(Hkv 8, 4 query heads each, head_dim 128).

Interpret mode runs the kernel bodies on the CPU but accepts programs the
TPU compiler refuses: unaligned block shapes, uint8 shifts and casts, more
VMEM than a kernel may use. Each test here hands the real compiler the
kernel at serving shapes, for a chip that is described and not attached,
and checks that the compiled program holds the Mosaic kernel
(``tpu_custom_call``). Nothing runs, so these say nothing about results
or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and a test worker that
imports this file must not take it from another.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_decode import polar_paged_decode_grouped
from repro.kernels.paged_prefill import polar_paged_prefill_grouped

CFG = get_config("tinyllama-1.1b")
HKV, QPK, D = CFG.num_kv_heads, CFG.q_per_kv, CFG.head_dim
G = CFG.quant.group_size           # page size == quantization group
SLOTS, MAX_LEN = 4, 2048
N = MAX_LEN // G                   # pages per slot
PP = SLOTS * N + 1                 # pool pages + the scratch page
CHUNK = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _pools(sds, quantized_values, hkv=HKV, d=D):
    """Shape stand-ins for one layer's page pools: codes, the four polar
    stats, and values (fp rows, or uint8 codes with scale/zero)."""
    stat = sds((PP, hkv, 1, d // 2), jnp.float32)
    pools = [sds((PP, hkv, G, d // 2), jnp.uint8), stat, stat, stat, stat]
    if quantized_values:
        vstat = sds((PP, hkv, G, 1), jnp.float32)
        return pools + [sds((PP, hkv, G, d), jnp.uint8), vstat, vstat]
    return pools + [sds((PP, hkv, G, d), jnp.bfloat16), None, None]


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _decode_cases():
    """(arch, quantized_values) cases; tinyllama's, the file's own widths,
    are named by the value format alone."""
    for arch in ("tinyllama-1.1b", "yi-9b", "granite-8b"):
        for qv in (False, True):
            fmt = "uint8_values" if qv else "fp_values"
            yield pytest.param(arch, qv, id=fmt if arch == CFG.name
                               else f"{arch}-{fmt}")


@pytest.mark.parametrize("arch,quantized_values", list(_decode_cases()))
def test_paged_decode_compiles_for_v5e(one_chip, arch, quantized_values):
    cfg = get_config(arch)
    hkv, qpk, d = cfg.num_kv_heads, cfg.q_per_kv, cfg.head_dim
    assert cfg.quant.group_size == G
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = [sds((SLOTS, hkv, qpk, d), jnp.float32),
            *_pools(sds, quantized_values, hkv, d),
            sds((SLOTS, N), jnp.int32), sds((SLOTS,), jnp.int32)]

    def step(q, c, rs, rz, ts, tz, v, vs, vz, table, flushed):
        return polar_paged_decode_grouped(
            q, c, rs, rz, ts, tz, v, vs, vz, table, flushed,
            r_bits=4, t_bits=4, interpret=False)

    assert "tpu_custom_call" in _compiled_text(step, args)


@pytest.mark.parametrize("quantized_values", [False, True],
                         ids=["fp_values", "uint8_values"])
def test_paged_prefill_chunk_compiles_for_v5e(one_chip, quantized_values):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = [sds((HKV, QPK * CHUNK, D), jnp.float32),
            sds((HKV, CHUNK, D), jnp.bfloat16),
            sds((HKV, CHUNK, D), jnp.bfloat16),
            *_pools(sds, quantized_values),
            sds((N,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32)]

    def chunk(q, kc, vc, c, rs, rz, ts, tz, v, vs, vz, row, start, clen):
        return polar_paged_prefill_grouped(
            q, kc, vc, c, rs, rz, ts, tz, v, vs, vz, row, start, clen,
            r_bits=4, t_bits=4, interpret=False)

    assert "tpu_custom_call" in _compiled_text(chunk, args)
