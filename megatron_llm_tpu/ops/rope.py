"""Rotary positional embeddings with position-interpolation scaling.

Reference: ``megatron/model/positional_embeddings.py:7-51`` —
``precompute_freqs_cis`` builds complex e^{i t theta^-2k/d} with the RoPE
*scaling* divisor ``t /= scaling_factor`` (linear position interpolation
for context extension, flag ``--rope_scaling_factor`` arguments.py:465),
and ``apply_rotary_emb`` rotates (q, k) by complex multiply over
*interleaved* even/odd feature pairs, with optional non-monotonic
``position_ids``.

TPU design: complex dtypes lower poorly on TPU, so the rotation is done as
the equivalent real cos/sin rotation over interleaved pairs — numerically
identical (same pairing as the Meta/Llama layout, which is why the HF
converter's rotary permutation in ``weights_conversion/hf_to_megatron.py:
117-160`` has an exact analogue here).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp


def llama3_scale_freqs(
    freqs: jax.Array,
    factor: float = 8.0,
    low_freq_factor: float = 1.0,
    high_freq_factor: float = 4.0,
    original_max_position: int = 8192,
) -> jax.Array:
    """Llama-3.1 NTK-by-parts frequency remap (the published scheme, as
    in HF ``modeling_rope_utils._compute_llama3_parameters``): leave
    high-frequency components (wavelength shorter than
    original_max/high_freq_factor) untouched, divide low-frequency
    components (wavelength longer than original_max/low_freq_factor) by
    ``factor``, and smoothly interpolate between the two bands."""
    two_pi = 2.0 * jnp.pi
    wavelen = two_pi / freqs
    low_freq_wavelen = original_max_position / low_freq_factor
    high_freq_wavelen = original_max_position / high_freq_factor
    # smooth factor in the interpolation band
    smooth = (original_max_position / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor
    )
    interp = (1.0 - smooth) * (freqs / factor) + smooth * freqs
    out = jnp.where(wavelen > low_freq_wavelen, freqs / factor, freqs)
    in_band = (wavelen <= low_freq_wavelen) & (wavelen >= high_freq_wavelen)
    return jnp.where(in_band, interp, out)


def yarn_scale_freqs(
    freqs: jax.Array,
    theta: float,
    factor: float,
    original_max_position: int,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
) -> jax.Array:
    """YaRN's frequency remap (the published scheme, as in HF
    ``modeling_rope_utils._compute_yarn_parameters``) of the ``dim / 2``
    pair frequencies ``theta^(-2i/dim)``: pair i keeps its frequency up
    to ``low`` (it turns more than ``beta_fast`` times within the
    original context), is divided by ``factor`` from ``high`` on (fewer
    than ``beta_slow`` turns), and goes linearly from the one to the
    other between; ``c(b) = dim ln(original_max / (2 pi b)) / (2 ln
    theta)`` is the pair that turns b times, ``low = floor(c(beta_fast))``
    and ``high = ceil(c(beta_slow))``, both within [0, dim - 1]."""
    dim = 2 * freqs.shape[0]

    def pair_turning(b):
        return (dim * math.log(original_max_position / (b * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    if low == high:
        high += 0.001               # as published: no division by zero
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return (freqs / factor) * ramp + freqs * (1.0 - ramp)


def _yarn(freqs, theta, yarn):
    """``freqs`` remapped by ``yarn`` = (factor, original_max_position,
    beta_fast, beta_slow, attention_factor), and the factor cos and sin
    are multiplied by; (freqs, 1.0) for None."""
    if yarn is None:
        return freqs, 1.0
    factor, orig, fast, slow, attention_factor = yarn
    return (yarn_scale_freqs(freqs, theta, factor, orig, fast, slow),
            attention_factor)


def precompute_freqs_cis(
    dim: int,
    end: int,
    theta: float = 10000.0,
    scaling_factor: float = 1.0,
    llama3_scaling: dict | None = None,
    yarn: Optional[Sequence[float]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (cos, sin), each [end, dim // 2], fp32.

    reference: positional_embeddings.py:7-14 (including ``t /= scaling_factor``).
    ``llama3_scaling``: optional kwargs for :func:`llama3_scale_freqs`
    (Llama-3.1+ checkpoints; mutually exclusive with linear scaling).
    ``yarn``: (factor, original_max_position, beta_fast, beta_slow,
    attention_factor) for :func:`yarn_scale_freqs`; cos and sin come
    multiplied by the last (so q.k carries its square, and the softmax
    scale is left alone).
    """
    freqs = 1.0 / (
        theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)[: dim // 2] / dim)
    )
    if yarn is not None:
        if scaling_factor != 1.0 or llama3_scaling:
            raise ValueError("rope yarn scaling excludes the linear and "
                             "the llama3 scaling")
        freqs, mult = _yarn(freqs, theta, yarn)
        ang = jnp.outer(jnp.arange(end, dtype=jnp.float32), freqs)
        return jnp.cos(ang) * mult, jnp.sin(ang) * mult
    if llama3_scaling:
        if scaling_factor != 1.0:
            raise ValueError(
                "rope llama3 scaling and linear scaling_factor "
                f"({scaling_factor}) are mutually exclusive — no "
                "checkpoint is trained with both")
        freqs = llama3_scale_freqs(freqs, **llama3_scaling)
    t = jnp.arange(end, dtype=jnp.float32) / scaling_factor
    freqs = jnp.outer(t, freqs)  # [end, dim/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rotary_emb(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    position_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Rotate interleaved feature pairs of ``x``.

    x: [..., seq, heads, head_dim] (seq is axis -3)
    cos/sin: [max_pos, head_dim // 2]
    position_ids: optional int array broadcastable to x's batch+seq dims
      (reference supports non-monotonic ids for packed sequences,
      positional_embeddings.py:33-44).
    """
    *lead, s, h, d = x.shape
    rot_d = 2 * cos.shape[-1]
    if rot_d < d:
        # partial rotary (GPT-NeoX/Pythia rotary_pct): rotate the first
        # rot_d dims of each head, pass the rest through unchanged
        out_rot = apply_rotary_emb(x[..., :rot_d], cos, sin, position_ids)
        return jnp.concatenate([out_rot, x[..., rot_d:]], axis=-1)
    if position_ids is None:
        c = cos[:s]  # [s, d/2]
        sn = sin[:s]
        c = c[:, None, :]  # [s, 1, d/2]
        sn = sn[:, None, :]
    else:
        c = cos[position_ids]  # [..., s, d/2]
        sn = sin[position_ids]
        c = c[..., :, None, :]
        sn = sn[..., :, None, :]
    return rotate_pairs(x, c, sn)


def rotate_pairs(x: jax.Array, c: jax.Array, sn: jax.Array) -> jax.Array:
    """x [..., s, heads, d] with its interleaved pairs (2i, 2i+1) turned
    by the angles whose cosines and sines are ``c`` / ``sn``
    [..., s, 1, d/2] (fp32); back in x's dtype."""
    *lead, s, h, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, s, h, d // 2, 2)
    x_even = xf[..., 0]
    x_odd = xf[..., 1]
    # (a + ib) * (cos + i sin) = (a cos - b sin) + i(a sin + b cos)
    out_even = x_even * c - x_odd * sn
    out_odd = x_even * sn + x_odd * c
    out = jnp.stack([out_even, out_odd], axis=-1).reshape(*lead, s, h, d)
    return out.astype(x.dtype)


def section_streams(sections: Sequence[int], pairs: int) -> jax.Array:
    """[pairs] int32: the position stream each frequency pair follows
    when ``sections`` (``mrope_section``: so many pairs to the first
    stream, so many to the second, ...) are dealt over ``pairs`` pairs.
    Sections that sum to another number of pairs (the indexer's head is
    narrower than the attention's) keep their proportions."""
    total = sum(sections)
    bounds, acc = [], 0
    for n in sections:
        acc += n
        bounds.append(round(acc * pairs / total))
    idx = jnp.arange(pairs)
    return sum((idx >= b).astype(jnp.int32) for b in bounds[:-1])


def apply_rotary_at(
    x: jax.Array,
    position_ids: jax.Array,
    theta: float,
    sections: Optional[Sequence[int]] = None,
    yarn: Optional[Sequence[float]] = None,
    rot_d: Optional[int] = None,
) -> jax.Array:
    """Rotate the interleaved pairs of ``x`` [..., s, heads, d] at
    explicit positions, with no table: pair i turns by
    ``position * theta^(-2i/d)``.  ``rot_d`` (even, under ``d``): only a
    head's first ``rot_d`` dimensions rotate, as a head of that width
    would, and the rest pass (a partial rotary, ``rotary_percent``).
    ``position_ids`` [..., s], or ``[streams, ..., s]`` with ``sections``: pair i then follows the
    stream its section names (the sectioned rotary embedding of
    multimodal models).  Streams that coincide, as a text token's do,
    give the plain embedding.  ``yarn``: as :func:`precompute_freqs_cis`
    takes it."""
    d = x.shape[-1]
    if rot_d is not None and rot_d < d:
        turned = apply_rotary_at(x[..., :rot_d], position_ids, theta,
                                 sections, yarn)
        return jnp.concatenate([turned, x[..., rot_d:]], axis=-1)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    inv, mult = _yarn(inv, theta, yarn)
    pos = position_ids.astype(jnp.float32)
    if sections is not None and position_ids.ndim == x.ndim - 1:
        # [streams, ..., s] -> each pair's own stream's position
        stream = section_streams(sections, d // 2)               # [d/2]
        pos = jnp.moveaxis(pos, 0, -1)[..., stream]              # [..., s, d/2]
        ang = pos * inv
    else:
        ang = pos[..., None] * inv                               # [..., s, d/2]
    return rotate_pairs(x, (jnp.cos(ang) * mult)[..., None, :],
                        (jnp.sin(ang) * mult)[..., None, :])
