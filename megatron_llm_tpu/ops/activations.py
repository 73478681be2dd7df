"""Activations: GLU family + (bias-)GeLU.

Reference: ``megatron/model/glu_activations.py:8-49`` (liglu/geglu/reglu/
swiglu as chunk-multiply modules) and ``megatron/model/fused_bias_gelu.py``
(a torch.jit fused bias+tanh-gelu with hand-written backward).

On TPU none of these need custom kernels: XLA fuses bias-add + gelu into
the producing matmul's epilogue, and the GLU chunk-multiply is a single
fused elementwise op.  The math (tanh-approximate gelu constants) matches
the reference so losses are comparable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gelu(x: jax.Array) -> jax.Array:
    """Tanh-approximate gelu — same polynomial as the reference's
    fused_bias_gelu.py:15-20."""
    return 0.5 * x * (1.0 + jnp.tanh(0.79788456 * x * (1.0 + 0.044715 * x * x)))


def bias_gelu(bias: jax.Array, x: jax.Array) -> jax.Array:
    # reference: fused_bias_gelu.py:18-20
    return gelu(x + bias)


def squared_relu(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def _split2(x: jax.Array):
    return jnp.split(x, 2, axis=-1)


def _pair2(x: jax.Array):
    """``_split2``'s sibling for a product whose gate / up pair is an axis
    of its own, ``[..., 2, F]`` (``parallel/glu_pairs.py``): the halves by
    index, which a shard of ``F`` takes locally."""
    return x[..., 0, :], x[..., 1, :]


def liglu(x: jax.Array, halves=_split2) -> jax.Array:
    # reference: glu_activations.py (LiGLU: linear gate)
    a, b = halves(x)
    return a * b


def geglu(x: jax.Array, halves=_split2) -> jax.Array:
    a, b = halves(x)
    return gelu(a) * b


def reglu(x: jax.Array, halves=_split2) -> jax.Array:
    a, b = halves(x)
    return jax.nn.relu(a) * b


def swiglu(x: jax.Array, halves=_split2) -> jax.Array:
    # reference: glu_activations.py:38-42 (silu(a) * b)
    a, b = halves(x)
    return jax.nn.silu(a) * b


GLU_ACTIVATIONS = {
    "liglu": liglu,
    "geglu": geglu,
    "reglu": reglu,
    "swiglu": swiglu,
}


def glu_activation(name: str, x: jax.Array) -> jax.Array:
    return GLU_ACTIVATIONS[name](x)


def apply_mlp_activation(h: jax.Array, cfg, paired: bool = False) -> jax.Array:
    """The MLP nonlinearity selected by config — GLU family (halves the
    doubled first projection: its last axis, or with ``paired`` the pair
    axis before it), the ungated ``relu(x)^2``
    (``mlp_activation='relu2'``) or a gelu variant ('exact' = erf gelu
    for Falcon, else the GPT-2/Megatron tanh polynomial).  Shared by the
    dense MLP (models/transformer.py), the MoE experts and their shared
    MLP (models/moe.py)."""
    if cfg.glu_activation:
        return GLU_ACTIVATIONS[cfg.glu_activation](
            h, _pair2 if paired else _split2)
    if cfg.mlp_activation == "relu2":
        return squared_relu(h)
    if cfg.gelu_variant == "exact":
        return jax.nn.gelu(h, approximate=False)
    return gelu(h)
