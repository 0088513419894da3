"""Pallas TPU kernels for PolarQuant hot spots + jnp oracles.

Kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling). Every
wrapper takes ``interpret`` explicitly: the CPU tests run the kernel
bodies with interpret=True against ``ref.py``, and
``tests/test_tpu_compile.py`` compiles them for a described v5e.
"""
from repro.kernels.ops import (  # noqa: F401
    polar_qk_scores, polar_encode, polar_decode_attention_grouped,
    polar_decode_attention_full, merge_softmax_partials,
)
from repro.kernels.flash_prefill import flash_prefill  # noqa: F401
