"""What runs with what has ONE home (``config.RUNS_WITH``): every square
of the table, taken from the table itself, is held to the sentence the
one reader (``config.refusal``) gives and to whoever asks it: the
config's own ``__post_init__`` for a model against itself, the serving
engine for the features its ``EngineConfig`` turns on,
``paged_kv.init_pools`` for the int8 pool, ``GPTModel`` for the
parallelism in force, ``transformer_stack`` for training,
``init_kv_caches`` for the legacy rolling cache.
"""

import copy
import functools
import importlib

import jax
import pytest

import _family
from megatron_llm_tpu import config as C
from megatron_llm_tpu.models import MODEL_REGISTRY, gpt
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.serving import EngineConfig, InferenceEngine

SQUARES = [(has, what) for has, whats in C.RUNS_WITH for what in whats]

# a served family that has each mechanism, and how a config (or an
# engine) is given each thing a mechanism does not run with
FAMILY = {C.SPARSE: "keye", C.STATE_SPACE: "granite", C.TYPED: "mellum",
          C.SHORT_CONV: "lfm2", C.RETENTION: "brumby",
          C.GATED_DELTA: "qwen3_next",
          C.ONE_SUBLAYER: "nemotron_h",
          C.GATE: "trinity", C.OUTPUT_NORMS: "trinity",
          C.ROPE_TYPES: "trinity", C.LOOPED: "ouro",
          C.FIRST_DENSE: "kanana", C.LATENT: "kanana",
          C.SPARSE_LATENT: "glm5",
          C.QK_NORM_WHOLE: "olmoe", C.EXPERTS: "olmoe", C.SHARE: "granite"}
GIVEN = {
    C.SLIDING: dict(sliding_window_size=16),
    C.NOT_ROTARY: dict(position_embedding_type="learned_absolute"),
    # the family's own period with its last layer a window layer
    C.OTHER_TYPES: lambda cfg: dict(
        layer_types=cfg.layer_types[:-1] + ("sliding",),
        sliding_window_size=16),
    # the same for a stack of 'conv' and 'attention' layers
    C.CONV_OTHER_TYPES: lambda cfg: dict(
        layer_types=cfg.layer_types[:-1] + ("sliding",),
        sliding_window_size=16),
    # the same for a stack of 'gated_delta' and 'attention' layers
    C.DELTA_OTHER_TYPES: lambda cfg: dict(
        layer_types=cfg.layer_types[:-1] + ("sliding",),
        sliding_window_size=16),
    # a retention stack with its last layer an attention layer; experts
    # beside the state
    C.RETENTION_OTHER_TYPES: dict(layer_types=("retention", "attention")),
    C.EXPERTS: dict(num_experts=4),
    C.GATE: dict(attention_output_gate=True),
    C.OUTPUT_NORMS: dict(sublayer_output_norm=True),
    C.BIASES: dict(add_bias_linear=True),
    C.QKV_BIAS: dict(add_qkv_bias=True),
    C.PARALLEL_ATTN: dict(parallel_attn=True),
    C.POST_LN: dict(use_post_ln=True),
    C.LATENT: dict(kv_lora_rank=32),
    C.SPARSE: dict(dsa_index_heads=4),
    C.SECTIONED: dict(rope_sections=(4, 6, 6)),
    C.TYPED: dict(layer_types=("full",)),
    C.QK_NORM_WHOLE: dict(qk_norm=True),
    C.QK_NORM_PER_HEAD: dict(qk_norm_per_head=True),
    C.ROPE_SCALING: dict(rope_scaling_factor=2.0),
    # a period of the depth's parity whose mixers are of two kinds, or
    # whose layers are one sublayer each; no type is left to rotate
    C.STATE_SPACE: lambda cfg: dict(
        layer_types=("mamba", "attention") + ("mamba",) * (
            cfg.num_layers % 2), rope_layer_types=None),
    C.ONE_SUBLAYER: lambda cfg: dict(
        layer_types=("attention", "moe") + ("attention",) * (
            cfg.num_layers % 2), rope_layer_types=None),
}
TURNS_ON = {
    C.VERIFY_STEP: dict(speculative=True, draft_k=2),
    C.INT8_POOL: dict(int8_kv_cache=True),
    C.HOST_TIER: dict(host_cache_bytes=1 << 20),
    C.PREEMPTION: dict(preemption=True),
    C.PREFIX_CACHE: dict(prefix_cache=True),
}


def _config(family, **overrides):
    module = importlib.import_module("megatron_llm_tpu.models." + family)
    return getattr(module, family + "_config")(
        "tiny", use_flash_attn=False, **overrides)


@functools.lru_cache(maxsize=None)
def _model(family):
    """(model, params): the harness's where the family has a row."""
    if family in _family.FAMILIES:
        return _family.built(family)[:2]
    model = MODEL_REGISTRY[family](_config(family))
    return model, model.init(jax.random.PRNGKey(0))


def _engine(family, **kw):
    kw = {"preemption": False, **kw}
    return InferenceEngine(*_model(family), EngineConfig(
        num_slots=2, block_size=8, max_model_len=32, prefill_chunk=16, **kw))


@pytest.mark.parametrize("has,what", SQUARES,
                         ids=[f"{h.split(' (')[0]} x {w.split(' (')[0]}"
                              for h, w in SQUARES])
def test_a_square_of_the_table_is_told_by_whoever_asks(has, what,
                                                       monkeypatch, capsys):
    family = FAMILY[has]
    cfg = _config(family)
    assert C.HAS[has](cfg)
    # the config with ``what`` on (a feature, or a field set as no
    # constructor would leave it) against this one square
    both, on = copy.copy(cfg), (what,) if what in C.FEATURES else ()
    given = GIVEN.get(what, {})
    given = given(cfg) if callable(given) else given
    for field, value in given.items():
        object.__setattr__(both, field, value)
    monkeypatch.setattr(C, "RUNS_WITH", ((has, (what,)),))
    assert C.refusal(cfg) is None
    tail = C.TAILS.get((has, what), "")
    assert C.refusal(both, on) == (
        f"{has}: {what}{tail}" if what in C.TURNED_OFF
        else f"{has}: not implemented with {what}{tail}")
    monkeypatch.undo()
    # against the whole table: the family is told the first square it
    # falls in with ``what``, and a plain decoder none
    said = C.refusal(cfg, on)
    assert C.refusal(cfg) is None
    assert C.refusal(_config("mistral"), C.FEATURES) is None

    if what not in C.FEATURES:
        # a model against itself: the constructor asks
        with pytest.raises(ValueError, match="not implemented with"):
            _config(family, **given)
    elif what in C.TURNED_OFF:
        # the engine says so and runs without
        eng = _engine(family, **TURNS_ON[what])
        assert said in capsys.readouterr().out
        assert not eng.config.prefix_cache
        assert not eng.blocks.prefix_cache_enabled
    elif what in TURNS_ON:
        with pytest.raises(ValueError) as raised:
            _engine(family, **TURNS_ON[what])
        assert str(raised.value) == said
    elif what == C.ROLLING_CACHE:
        # the legacy decode stack asks when it makes the ring
        from megatron_llm_tpu.text_generation.generation import (
            init_kv_caches)

        with pytest.raises(ValueError) as raised:
            init_kv_caches(cfg, 1, 32, rolling=True)
        assert str(raised.value) == said
    elif what == C.TRAINING:
        # the stack asks when it is run to train
        model, params = _model(family)
        with pytest.raises(NotImplementedError) as raised:
            model(params, jax.numpy.ones((1, 8), "int32"), train=True)
        assert str(raised.value) == said
    else:
        # the mesh's: GPTModel asks with what is in force
        monkeypatch.setattr(gpt, "_vocab_unsharded", lambda: False)
        assert gpt.parallelism_in_force() == (C.TENSOR_PARALLEL,
                                              C.MODEL_PARALLEL)
        with pytest.raises(ValueError) as raised:
            MODEL_REGISTRY[family](cfg)
        assert str(raised.value) == said
    if what in C.FEATURES:
        assert what in said


def test_a_conv_stack_with_mamba_layers_is_refused_by_name():
    """``mamba`` and ``conv`` in one stack: two states of two shapes a
    slot, held to nothing, so the constructor says so."""
    with pytest.raises(ValueError, match="'conv' layer type goes with "
                                         "'attention' layers only"):
        _config("lfm2", layer_types=("conv", "mamba") * 4)


@pytest.mark.parametrize("family,dense", [("granite", 1), ("granite", 3),
                                          ("lfm2", 2)])
def test_leading_dense_layers_run_in_a_stack_whose_mixers_are_by_kind(
        family, dense):
    """``FIRST_DENSE`` beside a state stack is no square of the table any
    more: the config is built, and the cache-less forward (the dense
    layers and the rest of their period unrolled, then whole periods
    scanned) and the engine's own programs (a mixer by
    ``cfg.mixer_index`` over the whole depth) count a layer the same
    way: one prompt's last logits and its greedy continuation agree."""
    import numpy as np

    cfg = _config(family, moe_first_dense_layers=dense)
    assert C.HAS[C.FIRST_DENSE](cfg) and cfg.mixers_by_kind
    assert C.refusal(cfg) is None
    model = MODEL_REGISTRY[family](cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_leaves(params["transformer"]["dense_layers"])[
        0].shape[0] == dense
    kinds = [cfg.mixer_index(i)[0] for i in range(cfg.num_layers)]
    for kind, n in cfg.mixer_counts.items():
        assert kinds.count(kind) == n == jax.tree_util.tree_leaves(
            params["transformer"]["layers"][kind])[0].shape[0]
    eng = _family.engine(model, params, max_model_len=64, prefill_chunk=16,
                         preemption=False)
    inner, got = eng._prefill_step, {}

    def tapped(params, pages, tokens, start, valid, table):
        out = inner(params, pages, tokens, start, valid, table)
        got[int(start) + int(valid) - 1] = np.asarray(out[0])
        return out

    eng._prefill_step = tapped      # the chunks' rows alone: no step's
    prompt = _family.tokens(21, seed=3, vocab=501)
    req = _family.serve(eng, prompt, 4)
    seq = prompt + list(req.out_tokens)[:-1]
    want = np.asarray(jax.jit(lambda p, t: model(p, t, train=False))(
        params, jax.numpy.asarray([seq], "int32"))[0])
    np.testing.assert_allclose(got[20], want[20], atol=2e-4, rtol=0)
    assert list(req.out_tokens) == want[20:].argmax(-1).tolist()


def test_a_retention_stack_with_another_layer_type_is_refused_by_name():
    """Pages, or another kind's state, beside a state of this size: held
    to nothing, so the constructor says so."""
    for other in ("attention", "mamba", "conv", "sliding"):
        with pytest.raises(ValueError, match="every layer of the depth is "
                                             "'retention'"):
            _config("brumby", layer_types=("retention", other),
                    sliding_window_size=16)


@pytest.mark.parametrize("other", ["mamba", "conv", "retention", "sliding",
                                   "full", "moe"])
def test_a_delta_stack_with_another_layer_type_is_refused_by_name(other):
    """A 'gated_delta' layer goes with 'attention' layers only: another
    kind's state beside it (two states of two shapes a slot), a window
    group or an expert layer alone is held to nothing, so the
    constructor says so, whatever row of the table comes first."""
    with pytest.raises(ValueError, match="not implemented with"):
        _config("qwen3_next",
                layer_types=("gated_delta", "gated_delta", other,
                             "attention"), sliding_window_size=16)
    # and said by the delta row's own sentence where no earlier row
    # speaks (an expert layer alone is ONE_SUBLAYER's, which comes first)
    cfg = copy.copy(_config("qwen3_next"))
    object.__setattr__(cfg, "layer_types", ("gated_delta", other))
    said = C.refusal(cfg)
    assert ("'gated_delta' layer type goes with 'attention' layers only"
            in said) == (other not in ("moe", "retention")), said


@pytest.mark.parametrize("what,row", [
    (C.VERIFY_STEP, C.SPARSE), (C.INT8_POOL, C.SPARSE),
    (C.HOST_TIER, C.LATENT), (C.TRAINING, C.SPARSE_LATENT)])
def test_the_selection_over_latents_runs_and_still_refuses_by_name(what,
                                                                   row):
    """``SPARSE`` has left ``LATENT``'s row: a model with both is built
    and told nothing against itself.  What the pair does not run with is
    still said by the row that says it of either alone (the verify step
    and the int8 pool by sparse attention's, the host tier by latent
    attention's), training by the pair's own."""
    cfg = _config("glm5")
    assert C.HAS[C.SPARSE](cfg) and C.HAS[C.LATENT](cfg)
    assert C.refusal(cfg) is None
    assert C.SPARSE not in dict(C.RUNS_WITH)[C.LATENT]
    said = C.refusal(cfg, (what,))
    assert said.startswith(f"{row}: not implemented with {what}"), said
    if what in TURNS_ON:
        with pytest.raises(ValueError) as raised:
            _engine("glm5", **TURNS_ON[what])
        assert str(raised.value) == said


@pytest.mark.parametrize("parallel", [C.TENSOR_PARALLEL, C.MODEL_PARALLEL])
def test_the_selection_over_latents_is_refused_on_a_sharded_mesh(
        parallel, monkeypatch):
    """Tensor parallelism by sparse attention's row, pipeline parallelism
    by latent attention's: ``GPTModel`` asks with what is in force."""
    cfg = _config("glm5")
    assert parallel in dict(C.RUNS_WITH)[
        C.SPARSE if parallel == C.TENSOR_PARALLEL else C.LATENT]
    monkeypatch.setattr(gpt, "_vocab_unsharded", lambda: False)
    with pytest.raises(ValueError) as raised:
        MODEL_REGISTRY["glm5"](cfg)
    assert str(raised.value) == C.refusal(cfg, gpt.parallelism_in_force())
    assert "sparse attention" in str(raised.value)


def test_a_compressed_query_is_built_and_its_indexer_reads_it():
    """``q_lora_rank`` is a field that is built (it was refused by name
    until PR 61): the query's two projections with a norm between, and an
    indexer whose queries read the compressed query has a projection of
    that width; what does not go together is said at construction."""
    cfg = _config("glm5")
    att = _model("glm5")[1]["transformer"]["layers"]["attention"]
    assert att["query_down"]["kernel"].shape[1:] == (cfg.hidden_size,
                                                      cfg.q_lora_rank)
    assert att["query_norm"]["scale"].shape[1:] == (cfg.q_lora_rank,)
    assert att["query"]["kernel"].shape[1] == cfg.q_lora_rank
    assert att["indexer"]["query"]["kernel"].shape[1] == cfg.q_lora_rank
    assert att["indexer"]["key"]["kernel"].shape[1] == cfg.hidden_size
    with pytest.raises(ValueError, match="needs kv_lora_rank"):
        _config("mistral", q_lora_rank=16)
    with pytest.raises(ValueError, match="compressed needs q_lora_rank"):
        _config("keye", dsa_index_query="compressed")
    with pytest.raises(ValueError, match="dsa_index_rope_dim"):
        _config("keye", dsa_index_rope_dim=3)
    # an indexer that reads the layer's input beside a compressed query
    assert _config("glm5", dsa_index_query="input").dsa_index_query == "input"


@pytest.mark.parametrize("family", ["kanana", "keye", "mellum", "granite",
                                    "lfm2", "brumby", "qwen3_next", "glm5"])
def test_the_pool_and_the_engine_refuse_int8_in_one_sentence(family):
    """``init_pools(quantized=True)`` and the engine ask the same table,
    so a latent, an indexed, a grouped model and one with state-space
    layers are each told ONE sentence by both (two wordings before)."""
    model, _ = _model(family)
    with pytest.raises(ValueError) as pool:
        paged_kv.init_pools(model.cfg, 4, 8, quantized=True,
                            window_blocks=4, num_slots=2)
    with pytest.raises(ValueError) as engine:
        _engine(family, int8_kv_cache=True)
    assert str(pool.value) == str(engine.value) == C.refusal(
        model.cfg, (C.INT8_POOL,))
    assert "int8 KV pool" in str(pool.value)


def test_no_other_module_words_a_refusal_of_its_own():
    """The engine, ``init_pools`` and ``GPTModel`` hold no sentence with
    "not implemented" of their own."""
    import inspect

    from megatron_llm_tpu.serving import engine

    for source in (inspect.getsource(engine),
                   inspect.getsource(paged_kv.init_pools),
                   inspect.getsource(gpt)):
        assert "not implemented" not in source
