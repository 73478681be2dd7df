"""Pallas Mosaic-TPU kernels — the TPU-native replacement for the
reference's CUDA ``megatron/fused_kernels`` + FlashAttention-2.

Every kernel has an XLA (plain jnp) fallback used on non-TPU backends and
in interpret-mode tests; dispatch is by ``jax.default_backend()``.
"""

import os

import jax


def pallas_backend_available() -> bool:
    """Shared backend gate for every kernel module's ``_use_pallas``.

    MLT_FORCE_PALLAS: AOT compiles (jax.experimental.topologies) run
    with a CPU default backend while lowering FOR a TPU topology —
    without the override they'd silently compile the XLA fallbacks
    (tools/aot_memcheck.py sets it).
    """
    return (jax.default_backend() == "tpu"
            or os.environ.get("MLT_FORCE_PALLAS") == "1")
