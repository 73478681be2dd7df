"""The plain reference against the program at the ``tiny`` presets of
models/mistral.py and models/mixtral.py: logits in float32."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH
from harness import shape


def _load(rel):
    path = os.path.join(BENCH, rel)
    s = importlib.util.spec_from_file_location(
        "t_" + os.path.basename(rel)[:-3], path)
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


decoder = _load("reference/decoder.py")
from_program = _load("reference/from_program.py")

# float32 on both sides, different order of operations (fused QKV,
# grouped attention, one-hot dispatch): agreement to rounding.  The
# logits' own spread is ~0.2 at these sizes, so 2e-4 is a thousandth of
# it; a wrong mask, rotation, head grouping or gate shows as >= 1e-2.
TOL = 2e-4


def _program_logits(model, params, tokens):
    from megatron_llm_tpu.models.language_model import language_model_forward
    out = language_model_forward(params, jnp.asarray(tokens)[None], None,
                                 None, model.cfg)
    out = out[0] if isinstance(out, tuple) else out
    return np.asarray(out[0], np.float32)


def _tiny(kind, **over):
    if kind == "mistral":
        from megatron_llm_tpu.models.mistral import MistralModel, mistral_config
        return MistralModel(mistral_config("tiny", use_flash_attn=False, **over))
    from megatron_llm_tpu.models.mixtral import MixtralModel, mixtral_config
    return MixtralModel(mixtral_config("tiny", use_flash_attn=False, **over))


def _reference_logits(model, params, tokens, **cfg_over):
    cfg = dict(shape.model_shape(model.cfg), **cfg_over)
    weights = from_program.ProgramWeights(params, cfg)
    return np.asarray(decoder.forward_logits(weights, cfg, tokens)), cfg


@pytest.mark.parametrize("kind", ["mistral", "mixtral"])
def test_reference_matches_program_logits(kind):
    over = {"moe_capacity_factor": 2.0} if kind == "mixtral" else {}
    model = _tiny(kind, **over)      # capacity = experts / top_k: dropless
    params = model.init(jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(1, 31999, 48)
    want, _ = _reference_logits(model, params, tokens)
    got = _program_logits(model, params, tokens)
    assert np.max(np.abs(got - want)) < TOL


def test_reference_is_sensitive():
    """A wrong rotary base or head grouping must show far above TOL,
    or the tolerance proves nothing."""
    model = _tiny("mistral")
    params = model.init(jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(1, 31999, 48)
    got = _program_logits(model, params, tokens)
    wrong, _ = _reference_logits(model, params, tokens, rope_theta=500.0)
    assert np.max(np.abs(got - wrong)) > 50 * TOL


def test_sliding_window_is_applied():
    """Mistral's window, at a size the test can afford: the model asserts
    4096, so the reference alone is run with a window of 8 and must differ
    from the full-causal result only from position 8 on."""
    model = _tiny("mistral")
    params = model.init(jax.random.PRNGKey(3))
    tokens = np.random.default_rng(1).integers(1, 31999, 24)
    full, _ = _reference_logits(model, params, tokens)
    cut, _ = _reference_logits(model, params, tokens, sliding_window=8)
    assert np.max(np.abs(full[:8] - cut[:8])) < 1e-6
    assert np.max(np.abs(full[8:] - cut[8:])) > 1e-3


def test_mixtral_chunk_the_default_capacity_would_drop():
    """With the program's default capacity factor (1.25) a chunk whose
    routing is uneven drops tokens, and the program then departs from the
    published model; with experts / top_k (what the benchmark's
    configuration sets) it does not.  The reference never drops."""
    from megatron_llm_tpu.models.moe import moe_capacity
    dropless = _tiny("mixtral", moe_capacity_factor=2.0)
    default = _tiny("mixtral")
    params = dropless.init(jax.random.PRNGKey(5))
    # bias the router towards expert 0 so that more than `capacity` of a
    # chunk's choices fall on it
    router = params["transformer"]["layers"]["mlp"]["router"]["kernel"]
    params["transformer"]["layers"]["mlp"]["router"]["kernel"] = \
        router.at[:, :, 0].add(0.5 * jnp.sign(router[:, :, 0]) + 0.5)
    tokens = np.random.default_rng(2).integers(1, 31999, 64)
    assert moe_capacity(default.cfg, 64) < 64      # 1.25 can drop
    assert moe_capacity(dropless.cfg, 64) >= 64    # 2.0 = E/k cannot
    want, _ = _reference_logits(dropless, params, tokens)
    ok = _program_logits(dropless, params, tokens)
    dropped = _program_logits(default, params, tokens)
    assert np.max(np.abs(ok - want)) < TOL
    assert np.max(np.abs(dropped - want)) > 10 * TOL


def test_a_turned_routing_choice_moves_that_position_and_none_before():
    """``turned`` seats the first rejected expert in place of the last
    chosen one at the named positions of the named layer: the logits
    there (and, through attention, after) change, those before do not,
    and every layer reports its margins."""
    model = _tiny("mixtral", moe_capacity_factor=2.0)
    params = model.init(jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(1, 31999, 24)
    cfg = shape.model_shape(model.cfg)
    weights = from_program.ProgramWeights(params, cfg)
    margins = []
    plain = np.asarray(decoder.forward_logits(weights, cfg, tokens,
                                              router_margins=margins))
    assert len(margins) == cfg["num_hidden_layers"]
    assert all(m.shape == (24,) and float(jnp.min(m)) >= 0 for m in margins)
    turned = np.asarray(decoder.forward_logits(weights, cfg, tokens,
                                               turned={0: [10]}))
    assert np.max(np.abs(turned[:10] - plain[:10])) < 1e-6
    assert np.max(np.abs(turned[10] - plain[10])) > 1e-3
    same = np.asarray(decoder.forward_logits(weights, cfg, tokens,
                                             turned={}))
    assert np.array_equal(same, plain)


def test_position_losses_sum_to_the_cross_entropy():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(7, 11)),
                         jnp.float32)
    labels = np.arange(7) % 11
    per = decoder.position_losses(logits, labels)
    assert per.shape == (7,)
    assert float(jnp.sum(per)) == pytest.approx(
        float(decoder.cross_entropy(logits, labels)), rel=1e-6)
