"""Rotary embedding tests: parity with the reference's complex-multiply
formulation (``megatron/model/positional_embeddings.py:7-51``), RoPE
scaling, position_ids."""

import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.ops.rope import apply_rotary_emb, precompute_freqs_cis


def reference_complex_rope(x, end, theta=10000.0, scaling=1.0, position_ids=None):
    """Numpy re-derivation of the reference math: freqs_cis complex,
    interleaved pairs viewed as complex, elementwise multiply."""
    x = np.asarray(x, np.float32)
    b, s, h, d = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, d, 2)[: d // 2] / d))
    t = np.arange(end) / scaling
    freqs_cis = np.exp(1j * np.outer(t, freqs))  # [end, d/2]
    if position_ids is None:
        fc = freqs_cis[:s][None, :, None, :]
    else:
        fc = freqs_cis[position_ids][:, :, None, :]
    xc = x.reshape(b, s, h, d // 2, 2)
    xc = xc[..., 0] + 1j * xc[..., 1]
    out = xc * fc
    res = np.stack([out.real, out.imag], axis=-1).reshape(b, s, h, d)
    return res.astype(np.float32)


def _x():
    rng = np.random.RandomState(7)
    return rng.randn(2, 16, 4, 8).astype(np.float32)


def test_matches_complex_reference():
    x = _x()
    cos, sin = precompute_freqs_cis(8, 32)
    out = apply_rotary_emb(jnp.asarray(x), cos, sin)
    np.testing.assert_allclose(out, reference_complex_rope(x, 32), atol=1e-5)


def test_rope_scaling():
    x = _x()
    cos, sin = precompute_freqs_cis(8, 32, scaling_factor=4.0)
    out = apply_rotary_emb(jnp.asarray(x), cos, sin)
    np.testing.assert_allclose(
        out, reference_complex_rope(x, 32, scaling=4.0), atol=1e-5
    )


def test_position_ids():
    x = _x()
    rng = np.random.RandomState(3)
    pos = rng.randint(0, 32, size=(2, 16))
    cos, sin = precompute_freqs_cis(8, 32)
    out = apply_rotary_emb(jnp.asarray(x), cos, sin, jnp.asarray(pos))
    np.testing.assert_allclose(
        out, reference_complex_rope(x, 32, position_ids=pos), atol=1e-5
    )


def test_norm_preserved():
    # rotation must preserve pairwise norms
    x = _x()
    cos, sin = precompute_freqs_cis(8, 32)
    out = np.asarray(apply_rotary_emb(jnp.asarray(x), cos, sin))
    n_in = np.linalg.norm(x.reshape(2, 16, 4, 4, 2), axis=-1)
    n_out = np.linalg.norm(out.reshape(2, 16, 4, 4, 2), axis=-1)
    np.testing.assert_allclose(n_in, n_out, atol=1e-4)


def test_llama3_scale_freqs_matches_hf():
    """ops.rope.llama3_scale_freqs reproduces HF's llama3 rope init
    (transformers.modeling_rope_utils._compute_llama3_parameters) over
    all three bands: untouched high-freq, /factor low-freq, and the
    smooth interpolation between."""
    pytest.importorskip("transformers")
    from transformers import LlamaConfig
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    from megatron_llm_tpu.ops.rope import llama3_scale_freqs

    hf_cfg = LlamaConfig(
        rope_theta=500000.0, hidden_size=256, num_attention_heads=2,
        max_position_embeddings=65536,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 8192})
    hf_inv, _ = ROPE_INIT_FUNCTIONS["llama3"](hf_cfg, "cpu")
    base = 1.0 / (500000.0
                  ** (np.arange(0, 128, 2, dtype=np.float32) / 128))
    mine = np.asarray(llama3_scale_freqs(jnp.asarray(base),
                                         8.0, 1.0, 4.0, 8192))
    np.testing.assert_allclose(mine, hf_inv.numpy(), rtol=1e-6)
    # all three bands actually exercised
    ratio = mine / base
    assert (np.isclose(ratio, 1.0)).any(), "no untouched high-freq band"
    assert (np.isclose(ratio, 1 / 8.0)).any(), "no /factor low-freq band"
    assert ((ratio > 1 / 8.0 + 1e-3) & (ratio < 1.0 - 1e-3)).any(), \
        "no interpolation band"


# ---------------------------------------------------------------------------
# the sectioned rotary embedding (mrope_section): frequency pairs dealt to
# several position streams
# ---------------------------------------------------------------------------

def test_rotary_at_positions_is_the_tables_rotation():
    """``apply_rotary_at`` computes its angles from the positions it is
    given; on one stream it is ``apply_rotary_emb`` over the table."""
    from megatron_llm_tpu.ops.rope import apply_rotary_at

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 9, 3, 16)), jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7, 8],
                       [40, 41, 42, 43, 44, 45, 46, 47, 48]])
    cos, sin = precompute_freqs_cis(16, 64, theta=1e4)
    want = apply_rotary_emb(x, cos, sin, pos)
    np.testing.assert_allclose(
        np.asarray(apply_rotary_at(x, pos, 1e4)), np.asarray(want),
        atol=1e-5)


@pytest.mark.parametrize("d,sections", [(16, (2, 3, 3)), (128, (16, 24, 24)),
                                        (64, (16, 24, 24))])
def test_sectioned_rotary_is_the_plain_one_for_text(d, sections):
    """Three position streams that coincide (a text token's) give the
    plain embedding, whatever the sections; a head narrower than the
    sections sum to (the indexer's 64 under 16 + 24 + 24 pairs) deals
    them in proportion."""
    from megatron_llm_tpu.ops.rope import apply_rotary_at, section_streams

    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (1, 7, 2, d)), jnp.float32)
    pos = jnp.arange(7)[None] + 3
    plain = apply_rotary_at(x, pos, 1e7)
    text = apply_rotary_at(x, jnp.stack([pos, pos, pos]), 1e7, sections)
    assert (np.asarray(text) == np.asarray(plain)).all()
    streams = np.asarray(section_streams(sections, d // 2))
    assert streams.tolist() == sorted(streams.tolist())
    assert set(streams.tolist()) == {0, 1, 2}
    want = np.asarray(sections) * (d // 2) // sum(sections)
    assert np.bincount(streams).tolist() == want.tolist()


def test_sectioned_rotary_differs_where_the_streams_differ():
    """Where the streams part (an image patch's height and width), pair i
    follows ITS stream: each section's pairs equal the plain embedding at
    that stream's position, and no other's."""
    from megatron_llm_tpu.ops.rope import apply_rotary_at, section_streams

    sections, d = (2, 3, 3), 16
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 5, 2, d)), jnp.float32)
    t = jnp.arange(5)[None]
    streams3 = jnp.stack([t, t + 10, t + 20])
    got = np.asarray(apply_rotary_at(x, streams3, 1e4, sections))
    of = np.asarray(section_streams(sections, d // 2))
    for stream in range(3):
        plain = np.asarray(apply_rotary_at(x, streams3[stream], 1e4))
        cols = np.flatnonzero(np.repeat(of == stream, 2))
        np.testing.assert_allclose(got[..., cols], plain[..., cols],
                                   atol=1e-6)
        others = np.flatnonzero(np.repeat(of != stream, 2))
        assert np.abs(got[..., others] - plain[..., others]).max() > 1e-2


# ---------------------------------------------------------------------------
# YaRN (rope_type 'yarn'): the frequencies and the factor on cos and sin
# ---------------------------------------------------------------------------

# rope_parameters.full_attention of Mellum2-12B-A2.5B-Instruct, head 128
YARN = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
THETA = 500000.0


def _yarn_by_the_formula(d=128, theta=THETA, yarn=YARN):
    """theta'_i = (theta_i / f)(1 - g_i) + theta_i g_i, g_i = 1 -
    clip((i - low) / (high - low), 0, 1), low = floor(c(beta_fast)), high =
    ceil(c(beta_slow)), c(b) = d ln(orig / (2 pi b)) / (2 ln theta),
    in float64."""
    import math

    f, orig, fast, slow, _ = yarn
    c = lambda b: d * math.log(orig / (2 * math.pi * b)) / (2 * math.log(theta))
    low, high = max(math.floor(c(fast)), 0), min(math.ceil(c(slow)), d - 1)
    i = np.arange(d // 2, dtype=np.float64)
    freq = theta ** (-2.0 * i / d)
    g = 1.0 - np.clip((i - low) / (high - low), 0.0, 1.0)
    return low, high, (freq / f) * (1 - g) + freq * g


def test_yarn_frequencies_at_the_published_numbers():
    from megatron_llm_tpu.ops.rope import yarn_scale_freqs

    low, high, want = _yarn_by_the_formula()
    assert (low, high) == (18, 35)
    plain = 1.0 / (THETA ** (jnp.arange(0, 128, 2, dtype=jnp.float32) / 128))
    got = np.asarray(yarn_scale_freqs(plain, THETA, *YARN[:4]))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    ratio = got / np.asarray(plain)
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-6)       # kept
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-6)    # divided
    assert (np.diff(ratio[18:36]) < 0).all()                     # between
    # the factor is 0.1 ln(16) + 1
    np.testing.assert_allclose(YARN[4], 0.1 * np.log(16.0) + 1, rtol=1e-12)


@pytest.mark.parametrize("positions", [[0, 1, 2, 3], [5, 1023, 8191, 100_000]])
def test_yarn_table_and_rotation_at_positions_agree_with_the_formula(
        positions):
    """The precomputed table and ``apply_rotary_at`` both turn pair i at
    position p by p * theta'_i and multiply cos and sin by the attention
    factor (so a rotated vector is 1.277 times as long)."""
    from megatron_llm_tpu.ops.rope import apply_rotary_at

    _, _, freq = _yarn_by_the_formula()
    pos = np.asarray(positions)
    ang = pos[:, None].astype(np.float64) * freq[None, :]
    cos, sin = precompute_freqs_cis(128, 100_001, theta=THETA, yarn=YARN)
    # fp32 angles at 1e5 radians carry some 1e-2 of error: compare where
    # the table itself is exact enough, and the two paths with each other
    small = ang < 100.0
    np.testing.assert_allclose(np.asarray(cos)[pos][small],
                               (YARN[4] * np.cos(ang))[small], atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin)[pos][small],
                               (YARN[4] * np.sin(ang))[small], atol=2e-5)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, len(pos), 2, 128).astype(np.float32))
    by_table = apply_rotary_emb(x, cos, sin, jnp.asarray(pos)[None])
    at = apply_rotary_at(x, jnp.asarray(pos)[None], THETA, yarn=YARN)
    np.testing.assert_allclose(np.asarray(at), np.asarray(by_table),
                               atol=1e-5)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(at), axis=-1),
        YARN[4] * np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    plain = apply_rotary_at(x, jnp.asarray(pos)[None], THETA)
    if pos.max() > 100:
        assert np.abs(np.asarray(at) / YARN[4] - np.asarray(plain)).max() > 0.1


def test_yarn_excludes_the_other_scalings():
    with pytest.raises(ValueError, match="excludes"):
        precompute_freqs_cis(128, 16, theta=THETA, scaling_factor=2.0,
                             yarn=YARN)


def test_a_one_type_model_with_yarn_builds_its_table():
    """``rope_yarn_scaling`` without ``layer_types``: the stack's one
    table carries it; a patterned model has no one table."""
    from megatron_llm_tpu.models.llama import llama_config
    from megatron_llm_tpu.models.mellum import mellum_config
    from megatron_llm_tpu.models.transformer import rotary_freqs

    cfg = llama_config("tiny", rope_theta=THETA, rope_yarn_scaling=YARN,
                       seq_length=32, max_position_embeddings=32)
    cos, _ = rotary_freqs(cfg)
    want, _ = precompute_freqs_cis(cfg.head_dim, 32, theta=THETA, yarn=YARN)
    np.testing.assert_array_equal(np.asarray(cos), np.asarray(want))
    assert float(cos[0, 0]) == pytest.approx(YARN[4])
    assert rotary_freqs(mellum_config("tiny")) is None
