"""Pallas prefill flash-attention kernel vs the jnp reference (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.attention import reference_attention
from repro.kernels.flash_prefill import flash_prefill


def _qkv(seed, b, h, hkv, tq, tk, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, h, tq, d), dtype),
            jax.random.normal(ks[1], (b, hkv, tk, d), dtype),
            jax.random.normal(ks[2], (b, hkv, tk, d), dtype))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (2, 1)])
def test_matches_reference(causal, h, hkv):
    q, k, v = _qkv(0, 1, h, hkv, 64, 64, 32)
    out = flash_prefill(q, k, v, causal=causal, q_blk=16, k_blk=16,
                        interpret=True)
    ref = reference_attention(q, k, v, mode="causal" if causal else "full")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_unpadded_lengths():
    q, k, v = _qkv(1, 1, 2, 2, 50, 70, 16)
    out = flash_prefill(q, k, v, causal=False, q_blk=16, k_blk=32,
                        interpret=True)
    ref = reference_attention(q, k, v, mode="full")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_bf16():
    q, k, v = _qkv(2, 1, 2, 2, 32, 32, 32, jnp.bfloat16)
    out = flash_prefill(q, k, v, causal=True, q_blk=16, k_blk=16,
                        interpret=True)
    ref = reference_attention(q, k, v, mode="causal")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)
