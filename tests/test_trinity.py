"""Trinity-Mini (``afmoe``: a sigmoid gate on the attention output, four
norms a layer, window layers that rotate beside full layers that carry
no positions, two leading dense layers inside the typed stack, a sigmoid
router with a choice bias and a shared expert, of whose experts a chip
may hold a share), against the benchmark's plain reference.

Seeded random weights, CPU, float32 on both sides, small size: hidden
128, 4 query and 2 KV heads of 32, a window of 16 over contexts of 5 to
150 tokens, 8 experts of 64 at 4 a token, at TWO depths: 8 layers (2
dense + 2 sparse before the first period boundary + one scanned period)
holding experts 0-3 of the router's 8, as the benchmark's cell holds
0-63 of 128, and 12 layers (two scanned periods) holding all 8.  The
reference is the file the benchmark's probe loads
(``benchmarks/reference/trinity.py``), loaded here by path.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import BS, serve, tokens
from megatron_llm_tpu import config as C
from megatron_llm_tpu.models import moe
from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.trinity import TrinityModel, trinity_config
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.serving import SamplingParams

# float32 on both sides, the same mathematics summed in another order.
# Mellum's and Kanana's files hold 2e-4; here every sublayer's output is
# normed to a root mean square of one whatever its size, so a rounding
# in a small output is carried at full weight into the stream (read: up
# to 1.6e-4 at depth 12), and the limit stands at 5e-4.  Every named
# fault moves the logits by whole tenths
ROW = _family.FAMILIES["trinity"]
LOGIT_TOL, CHUNK = ROW.tol, ROW.chunk
WINDOW = 16
BOUND = paged_kv.window_pages_bound(WINDOW, CHUNK, BS)      # 5 pages
FAULTS = ("no_gate", "full_rotates", "no_output_norms", "no_scale",
          "bias_in_gates", "no_multiplier", "dense_layer_sparse",
          "all_full", "no_shared", "no_qk_norm", "float8")
# the row's two sizes: the cell's own shape (depth 8 holding experts 0-3
# of the router's 8, as the benchmark's cell holds 0-63 of 128), which
# every test walks, and a deeper one (two scanned periods, all 8 held)
# for the two comparisons
SIZES = {"family": None, "deep": "depth12_whole"}


def _config(depth, held, **kw):
    share = {} if held == 8 else dict(num_experts=held, moe_router_experts=8)
    return trinity_config("tiny", use_flash_attn=False, num_layers=depth,
                          **share, **kw)


@pytest.fixture(scope="module")
def family():
    return _family.built("trinity")


@pytest.mark.parametrize("either,n", [("family", 5), ("family", 17),
                                      ("family", 70), ("deep", 70)])
def test_full_forward_matches_the_reference(either, n):
    """The program's plain (cache-less) forward: two dense layers of the
    types their indices give them, the sparse layers before the first
    period boundary unrolled, whole periods scanned: logits at every
    position against the reference, at contexts under the window (5),
    one past it (17) and several windows long (70); at depth 12 two
    periods are scanned."""
    _family.full_forward_is_the_references("trinity", n, SIZES[either])


def test_the_scan_starts_at_a_period_boundary_and_holds_one_period():
    """Depths 8, 12 and 32 (the published one) trace the same equations
    outside ONE scan, over 1, 2 and 7 whole periods: two dense and two
    sparse layers run before it."""
    def eqns(layers):
        model = TrinityModel(_config(layers, 8))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        jaxpr = jax.make_jaxpr(
            lambda p, t: model(p, t, train=False))(
                params, jnp.zeros((1, 8), jnp.int32))
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1
        assert scans[0].params["length"] == (layers - 4) // 4
        return len(jaxpr.jaxpr.eqns)

    assert eqns(8) == eqns(12) == eqns(32)


@pytest.mark.parametrize("either,prompt,new", [
    ("family", 5, 14), ("family", 64, 10), ("family", 150, 6),
    ("deep", 150, 6)])
def test_the_engine_over_two_groups_matches_one_full_forward(
        engines, either, prompt, new):
    """Chunked prefill then decode through the engine's own programs
    over the two-group pool against the reference's ONE full forward: a
    prompt under the window whose decode steps cross it, one that ends on
    a page's and the window's edge, one of nine windows.  Window pages
    have gone back before most compared positions, and the full layers,
    which carry no positions, see keys far behind any window."""
    held = []
    eng, since, _ = _family.chunked_prefill_then_decode_is_one_forward(
        engines, "trinity", prompt, new, size=SIZES[either],
        each_step=lambda eng, req: held.append(
            eng.blocks.stats()["window_blocks_in_use"]))
    assert max(held) <= BOUND
    stats, _ = since()
    if prompt > 2 * WINDOW:
        assert stats["kv_window_pages_returned"] > 0
    # the router's histogram is over all 8, the held count over the share
    cfg = eng.model.cfg
    assert stats["moe_assignments"] == (
        prompt + new - 1) * 4 * cfg.num_sparse_layers
    if cfg.holds_a_share:
        assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]
    else:
        assert stats["moe_assignments_held"] == stats["moe_assignments"]


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(fault):
    """The same comparison against each FAULTY reference, at a context of
    nine windows: every one is far beyond a hundred tolerances."""
    apart = _family.a_named_fault_is_told("trinity", fault, n=150,
                                          beyond=2 * WINDOW)
    if fault == "all_full":
        # nothing lies behind a window yet
        assert apart[:WINDOW].max() < LOGIT_TOL


def test_a_uniform_scale_of_a_sublayers_output_is_hidden_by_its_norm(family):
    """Why no fault of that form is named: the output norm divides it
    out.  The attention's output projection doubled moves the logits by
    what the norm's epsilon (1e-5 beside a mean square of some
    hundredths) lets through, a hundredth of what any named fault
    does."""
    model, params = family[:2]
    toks = jnp.asarray([tokens(40)], jnp.int32)
    doubled = jax.tree_util.tree_map(lambda a: a, params)
    for stack in ("layers", "dense_layers"):
        dense = doubled["transformer"][stack]["attention"]["dense"]
        dense["kernel"] = dense["kernel"] * 2.0
    forward = jax.jit(lambda p: model(p, toks, train=False))
    np.testing.assert_allclose(np.asarray(forward(doubled)),
                               np.asarray(forward(params)), atol=5e-3,
                               rtol=0)


def _sparse_layer(params, j):
    return jax.tree_util.tree_map(lambda a: a[j],
                                  params["transformer"]["layers"]["mlp"])


def test_the_two_shares_sum_to_the_uncut_layer_before_its_output_norm():
    """Experts 0-3 and 4-7 of 8, the shared expert counted once, are the
    uncut reference's layer under sigmoid routing with the bias and the
    scale: what each chip of the deployment computes before the
    exchange, summed.  BEFORE the output norm: a norm is not additive,
    and the shares normed apart do not sum to the normed layer, so a
    deployment exchanges the experts' outputs, not the layer's."""
    whole_cfg = _config(8, 8)
    whole = _family.shaken("trinity", TrinityModel(whole_cfg))
    ref = _family.load("trinity")
    rcfg = ROW.ref_cfg(whole_cfg, CHUNK)
    weights = _family.load("trinity_from_program").ProgramWeights(whole, rcfg)
    layer = _sparse_layer(whole, 0)
    m = jax.random.normal(jax.random.PRNGKey(8), (1, 60, 128), jnp.float32)
    want = np.asarray(ref.moe_out(m[0], weights.layer(2), weights, rcfg, 2,
                                  {}, frozenset())[0])
    shared = moe._shared_mlp(m, layer, whole_cfg).astype(jnp.float32)
    outs = []
    for first in (0, 4):
        half_cfg = whole_cfg.replace(num_experts=4, moe_router_experts=8,
                                     moe_experts_first=first)
        half = {**layer, "experts": jax.tree_util.tree_map(
            lambda a: a[first:first + 4], layer["experts"])}
        out, _, counts = moe.moe_mlp_dropless(m, half, half_cfg)
        assert int(counts.sum()) == 60 * 4
        outs.append(out)
        # and the reference's own share is the program's
        np.testing.assert_allclose(
            np.asarray(out[0]),
            np.asarray(ref.moe_out(m[0], weights.layer(2), weights,
                                   {**rcfg, "num_experts": 4,
                                    "experts_first": first}, 2, {},
                                   frozenset(), held=range(first, first + 4)
                                   )[0]), atol=3e-5, rtol=0)
    total = outs[0] + outs[1] - shared        # the shared expert once
    np.testing.assert_allclose(np.asarray(total[0]), want, atol=3e-5, rtol=0)
    assert np.abs(np.asarray(outs[0][0]) - want).max() > 1e-2

    def normed(y):
        return np.asarray(ref.rms_norm(y[0], jnp.ones((128,)), 1e-5))

    apart = np.abs(normed(outs[0]) + normed(outs[1]) - normed(shared)
                   - normed(total)).max()
    assert apart > 0.1


def test_a_dense_layers_pages_are_of_its_types_group(family, engines):
    """A layer's group comes from its index in the WHOLE stack, the dense
    layers included: they attend, so they hold pages; layers 0 and 1 are
    window layers, layer 3 (sparse) the first full one."""
    model, params = family[:2]
    cfg = model.cfg
    L = cfg.num_layers
    groups = paged_kv.layer_groups(cfg)
    assert groups == ("window", "window", "window", "full") * (L // 4)
    assert cfg.moe_first_dense_layers == 2 and groups[:2] == ("window",) * 2
    pools = paged_kv.init_pools(cfg, 41, BS, window_blocks=11)
    assert [p["k_pages"].shape[0] for p in pools] == [11, 11, 11, 41] * (
        L // 4)
    eng = engines("trinity")
    assert eng._layer_groups == groups
    assert eng.stats()["window_blocks_total"] == 2 * BOUND
    serve(eng, tokens(100, seed=7), 4)
    last = eng.loop_profiler.records()[-1]
    # 13 full pages on a quarter of the layers, <= 5 window pages on the
    # others, the dense layers' among them
    page = BS * 2 * cfg.num_query_groups * cfg.head_dim * 4
    assert last.kv_full_pages_held == 13
    assert 13 * (L // 4) * page < last.kv_held_bytes <= (
        13 * (L // 4) + BOUND * (3 * L // 4)) * page


def test_a_slot_is_reused_and_two_requests_run_side_by_side(engines):
    """Two long requests decode side by side, each within its bound of
    window pages, the invariants held after every step; then each alone
    in a reused slot answers as it did beside the other."""
    eng = engines("trinity")

    def side_by_side(*sized):
        reqs = [eng.submit(tokens(n, seed=s),
                           SamplingParams(max_new_tokens=6, temperature=0.0))
                for n, s in sized]
        while any(r.finish_reason is None for r in reqs):
            assert eng.step()
            eng.blocks.check_invariants()
            assert eng.blocks.stats()["window_blocks_in_use"] <= (
                len(sized) * BOUND)
        return [list(r.out_tokens) for r in reqs]

    both = side_by_side((90, 11), (70, 12))
    assert eng.stats()["window_blocks_in_use"] == 0
    assert side_by_side((90, 11)) + side_by_side((70, 12)) == both


def test_the_legacy_contiguous_cache_reaches_the_gate_and_the_norms(family):
    """The gate and the output norms lie where EVERY path ends: the
    legacy decode stack's contiguous cache gives the plain forward's
    logits, a chunk and then a token at a time."""
    from megatron_llm_tpu.text_generation.generation import (
        _forward_with_cache, init_kv_caches)

    model, params = family[:2]
    toks = jnp.asarray([tokens(23, seed=9)], jnp.int32)
    want = np.asarray(model(params, toks, train=False))
    caches = init_kv_caches(model.cfg, 1, 32)
    part, caches = _forward_with_cache(model, params, toks[:, :20], caches, 0)
    parts = [part]
    for t in range(20, 23):
        part, caches = _forward_with_cache(model, params, toks[:, t:t + 1],
                                           caches, t)
        parts.append(part)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, axis=1)),
                               want, atol=LOGIT_TOL, rtol=0)
    with pytest.raises(ValueError, match="rolling decode cache"):
        init_kv_caches(model.cfg, 1, 32, rolling=True)


def test_the_scopes_reach_the_programs_tables(family):
    """``attn_gate``, ``post_attn_norm`` and ``post_mlp_norm`` are scopes
    the program tables know, and the lowered decode step carries each on
    every layer."""
    from megatron_llm_tpu import hlo_collectives

    from _hlo_text import lowered_text

    model, params = family[:2]
    assert {"attn_gate", "post_attn_norm", "post_mlp_norm"} <= set(
        hlo_collectives.SCOPES)
    assert hlo_collectives.scope_of(
        "jit(step)/transformer_layer/attention/attn_gate/mul") == "attn_gate"
    text = lowered_text(jax.jit(
        lambda p, t: model(p, t, train=False)).lower(
            params, jnp.zeros((1, 8), jnp.int32)))
    for scope in ("attn_gate", "post_attn_norm", "post_mlp_norm"):
        assert f"/{scope}/" in text, scope


GATED = dict(C.RUNS_WITH)[C.GATE]
NORMED = dict(C.RUNS_WITH)[C.OUTPUT_NORMS]
FEATURES_ON = {C.VERIFY_STEP: dict(speculative=True, draft_k=2),
               C.INT8_POOL: dict(int8_kv_cache=True),
               C.HOST_TIER: dict(host_cache_bytes=1 << 20)}


def test_an_untyped_gated_model_is_refused_by_its_own_rows():
    """The gate and the output norms are fields any model can set: a
    llama-style trunk with both (no layer types, so the row of TYPED says
    nothing) is told by THEIR rows what the engine does not run them
    with, and is served without those features."""
    from megatron_llm_tpu.models.gpt import GPTModel
    from megatron_llm_tpu.models.mistral import mistral_config

    cfg = mistral_config("tiny", use_flash_attn=False,
                         attention_output_gate=True,
                         sublayer_output_norm=True)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    for what, on in FEATURES_ON.items():
        assert what in GATED and what in NORMED
        said = C.refusal(cfg, (what,))
        assert said.startswith(C.GATE) and what in said
        with pytest.raises(ValueError) as raised:
            _family.engine(model, params, prefill_chunk=CHUNK, **on)
        assert str(raised.value) == said
        assert C.refusal(cfg.replace(attention_output_gate=False),
                         (what,)).startswith(C.OUTPUT_NORMS)
    eng = _family.engine(model, params, prefill_chunk=CHUNK)
    assert _family.is_greedy(model, params, tokens(20),
                             serve(eng, tokens(20), 3).out_tokens)


def test_what_the_config_does_not_take_is_said_by_name():
    with pytest.raises(ValueError, match="rope_layer_types names types"):
        trinity_config("tiny", rope_layer_types=("attention",))
    with pytest.raises(ValueError, match="rope_layer_types names types"):
        trinity_config("tiny", layer_types=None, sliding_window_size=None)
    with pytest.raises(ValueError, match="whole periods"):
        trinity_config("tiny", num_layers=6)
    said = C.refusal(trinity_config("tiny"), (C.TRAINING,))
    assert said.startswith(C.GATE) and "training" in said
    from megatron_llm_tpu.models.kanana import kanana_config

    with pytest.raises(ValueError, match="no fourth projection"):
        kanana_config("tiny", attention_output_gate=True)
    model = TrinityModel(trinity_config("tiny", use_flash_attn=False))
    with pytest.raises(NotImplementedError, match="not implemented with "
                                                  "training"):
        model(model.init(jax.random.PRNGKey(0)),
              jnp.ones((1, 8), jnp.int32), train=True)


def test_parallelism_is_refused_at_construction(monkeypatch):
    from megatron_llm_tpu.models import gpt

    monkeypatch.setattr(gpt, "_vocab_unsharded", lambda: False)
    with pytest.raises(ValueError, match="tensor or pipeline"):
        TrinityModel(trinity_config("tiny"))


def test_the_family_wrapper_asserts_its_flags_and_its_published_sizes():
    cfg = trinity_config("tiny")
    for bad in (dict(norm_topk_prob=False), dict(qk_norm_per_head=False),
                dict(attention_output_gate=False),
                dict(sublayer_output_norm=False),
                dict(rope_layer_types=None),
                dict(embedding_multiplier=None),
                dict(moe_shared_experts=0), dict(moe_choice_bias=False)):
        with pytest.raises(AssertionError):
            TrinityModel(cfg.replace(**bad))
    full = trinity_config("mini")
    assert (full.num_layers, full.hidden_size, full.num_attention_heads,
            full.num_attention_heads_kv, full.head_dim) == (32, 2048, 32, 4,
                                                            128)
    assert (full.num_experts, full.moe_top_k, full.expert_hidden_size,
            full.ffn_hidden_size, full.moe_first_dense_layers,
            full.moe_shared_experts) == (128, 8, 1024, 6144, 2, 1)
    assert full.padded_vocab_size == 200192 and full.rope_theta == 10000.0
    assert full.embedding_multiplier == math.sqrt(2048)
    assert full.moe_routed_scale == 2.826 and full.layernorm_epsilon == 1e-5
    assert full.layer_types == ("sliding", "sliding", "sliding", "full")
    assert full.attention_of("sliding") == (2048, None)
    assert full.attention_of("full") == (None, None)
    assert full.rotates("sliding") and not full.rotates("full")
    assert full.num_sparse_layers == 30
    # a model of one type rotates on every layer, as ever
    from megatron_llm_tpu.models.mistral import mistral_config

    assert mistral_config("tiny").rotates(None)
    # ISSUE 47's arithmetic: attention 8.39 M (q) + 1.05 + 1.05 (k, v) +
    # 8.39 (gate) in ONE fused kernel, 8.39 (o): 27.3 M; a dense layer
    # 65.0 M; an expert 6.29 M; a sparse layer's router 0.26 M
    dense, sparse = (jax.eval_shape(
        lambda k: tfm.init_layer_params(k, full, jnp.bfloat16, sparse=s),
        jax.random.PRNGKey(0)) for s in (False, True))
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    attn = dense["attention"]
    assert attn["query_key_value"]["kernel"].shape == (2048, 9216)
    assert count(attn["query_key_value"]) + count(attn["dense"]) == 27_262_976
    assert count(dense["mlp"]) == 3 * 2048 * 6144
    assert {k for k in dense if k.endswith("norm")} == {
        "input_norm", "attention_output_norm", "post_attention_norm",
        "mlp_output_norm"}
    mlp = sparse["mlp"]
    assert count(mlp["experts"]) == 128 * 3 * 2048 * 1024
    assert count(mlp["shared"]) == 3 * 2048 * 1024
    assert mlp["router"]["kernel"].shape == (2048, 128)


def test_the_flags_carry_the_three_fields_and_the_server_builds_it():
    from megatron_llm_tpu.arguments import (parse_args,
                                            transformer_config_from_args,
                                            validate_args)

    args = validate_args(parse_args(args_list=[
        "--num_layers=8", "--hidden_size=128", "--num_attention_heads=4",
        "--num_attention_heads_kv=2", "--kv_channels=32",
        "--ffn_hidden_size=256", "--moe_ffn_hidden_size=64",
        "--num_experts=4", "--moe_router_experts=8", "--moe_top_k=4",
        "--moe_first_dense_layers=2", "--moe_shared_experts=1",
        "--moe_score_function=sigmoid", "--moe_choice_bias=1",
        "--moe_routed_scale=2.826", "--qk_norm_per_head",
        "--attention_output_gate", "--sublayer_output_norm",
        "--embedding_multiplier=11.313708498984761",
        "--sliding_window_size=16", "--layer_types", "sliding", "sliding",
        "sliding", "full", "--rope_layer_types", "sliding",
        "--position_embedding_type=rotary", "--glu_activation=swiglu",
        "--no_bias", "--use_rms_norm", "--no_tie_embed_logits",
        "--seq_length=64", "--max_position_embeddings=64",
        "--padded_vocab_size=512", "--micro_batch_size=1",
        "--global_batch_size=1"]), world_size=1)
    cfg = transformer_config_from_args(args)
    assert cfg.attention_output_gate and cfg.sublayer_output_norm
    assert cfg.rope_layer_types == ("sliding",)
    want = _config(8, 4, seq_length=64, max_position_embeddings=64)
    for field in ("layer_types", "rope_layer_types", "moe_first_dense_layers",
                  "num_experts", "moe_router_experts", "embedding_multiplier",
                  "attention_output_gate", "sublayer_output_norm",
                  "qk_norm_per_head", "moe_routed_scale"):
        assert getattr(cfg, field) == getattr(want, field), field
    TrinityModel(cfg)
    # the presets of --model_name=trinity are the wrapper's
    import finetune

    preset = finetune.MODEL_DEFAULTS["trinity"]
    assert preset["attention_output_gate"] and preset["sublayer_output_norm"]
    assert preset["rope_layer_types"] == ["sliding"]
    assert preset["moe_first_dense_layers"] == 2
