"""Mellum 2 (a layer type per layer: three sliding-window layers to each
full layer, YaRN on the full layers only; 8 of 64 small experts with
gates renormalised), against the benchmark's plain reference.

Seeded random weights, CPU, float32 on both sides, small size: 8 layers
(two periods), hidden 128, 4 query and 2 KV heads of 32, a window of 16
over contexts of 5 to 150 tokens (several windows), 8 experts of 64 at 4
a token.  The reference is the file the benchmark's probe loads
(``benchmarks/reference/mellum.py``), loaded here by path.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.language_model import language_model_forward
from megatron_llm_tpu.models.mellum import MellumModel, mellum_config
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                      SamplingParams)

REFERENCE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "reference")

# float32 on both sides, the same mathematics summed in another order;
# every named fault moves the logits by whole tenths
LOGIT_TOL = 2e-4
WINDOW = 16
FAULTS = ("all_full", "all_window", "plain_rope", "no_attention_factor",
          "gates_as_they_are", "float8")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "ref_" + name, os.path.join(REFERENCE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ref_cfg(cfg):
    f, orig, fast, slow, att = cfg.rope_yarn_scaling
    names = {"sliding": "sliding_attention", "full": "full_attention"}
    period = [names[t] for t in cfg.layer_types]
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.norm_topk_prob,
            "vocab_size": cfg.padded_vocab_size,
            "sliding_window": cfg.sliding_window_size,
            "layer_types": period * (cfg.num_layers // len(period)),
            "mlp_layer_types": ["sparse"] * cfg.num_layers,
            "rope_parameters": {
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": cfg.rope_theta,
                    "factor": f, "original_max_position_embeddings": orig,
                    "beta_fast": fast, "beta_slow": slow,
                    "attention_factor": att},
                "sliding_attention": {"rope_type": "default",
                                      "rope_theta": cfg.rope_theta}}}


def _shake(params, key):
    """Seeded N(0, 0.02) weights make attention nearly uniform and every
    norm's scale is 1 at init: a test that must tell a window from the
    whole context, or one rotary variant from another, needs larger
    projections and scales that differ."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        names = [getattr(p, "key", None) for p in path]
        if "scale" in names:
            leaf = leaf + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, leaf.dtype)
        elif {"kernel", "w_in", "w_out"} & set(names):
            # the experts too, or the MLP adds next to nothing; the
            # router less: gates that are nearly one-hot would hide
            # whether the chosen ones are renormalised
            leaf = leaf * (2.0 if "router" in names else 6.0)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def family():
    model = MellumModel(mellum_config("tiny", use_flash_attn=False))
    params = _shake(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
    cfg = _ref_cfg(model.cfg)
    weights = _load("mellum_from_program").ProgramWeights(params, cfg)
    return model, params, _load("mellum"), weights, cfg


def _tokens(n, seed=3, vocab=512):
    return np.random.default_rng(seed).integers(1, vocab - 1, n).tolist()


@pytest.mark.parametrize("n", [5, 16, 17, 70])
def test_full_forward_matches_the_reference(family, n):
    """The program's plain (cache-less) forward, a scan over periods of
    four layers: logits at every position against the reference, at
    contexts under the window (5), at it (16), one past it (17) and
    several windows long (70)."""
    model, params, ref, weights, cfg = family
    toks = _tokens(n)
    got = np.asarray(model(params, jnp.asarray([toks], jnp.int32),
                           train=False)[0])
    want = np.asarray(ref.forward_logits(weights, cfg, toks))
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_the_trace_holds_one_period_whatever_the_depth():
    """The cache-less forward of 8 layers and of 4 trace the same number
    of equations outside the scan: the stack scans periods."""
    def eqns(layers):
        model = MellumModel(mellum_config("tiny", num_layers=layers,
                                          use_flash_attn=False))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        jaxpr = jax.make_jaxpr(
            lambda p, t: model(p, t, train=False))(
                params, jnp.zeros((1, 8), jnp.int32))
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1 and scans[0].params["length"] == layers // 4
        return len(jaxpr.jaxpr.eqns)

    assert eqns(8) == eqns(4)


BS, CHUNK = 8, 16
BOUND = paged_kv.window_pages_bound(WINDOW, CHUNK, BS)      # 5 pages


def _serve(model, params, prompt, new, **kw):
    """One request through the engine, stepped by hand, the block
    manager's invariants checked after every step.  Returns the engine
    and the request."""
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=2, block_size=BS, max_model_len=192, prefill_chunk=CHUNK,
        **kw))
    req = eng.submit(prompt, SamplingParams(max_new_tokens=new,
                                            temperature=0.0))
    held = []
    while req.finish_reason is None:
        assert eng.step()
        eng.blocks.check_invariants()
        held.append(eng.blocks.stats()["window_blocks_in_use"])
    assert max(held) <= BOUND
    return eng, req


def _tapped(eng):
    """The engine's programs with their logits kept: the prefill step
    returns its chunk's last live row; the decode step is run without its
    sampler on the step's own arguments, as the benchmark's probe does."""
    got = {}
    prefill, decode = eng._prefill_step, eng._decode_step

    def tapped_prefill(params, pages, tokens, start, valid, table):
        out = prefill(params, pages, tokens, start, valid, table)
        got[int(start) + int(valid) - 1] = np.asarray(out[0])
        return out

    def tapped_decode(params, pages, last, ctx, tables, active, *rest):
        caches = paged_kv.step_caches(pages, tables, ctx, active,
                                      eng.paged_kernel, eng._layer_groups)
        logits, _ = language_model_forward(
            params, last[:, None], ctx[:, None], None, eng.model.cfg,
            rng_key=None, train=False, kv_caches=caches)
        for s in np.flatnonzero(np.asarray(active) > 0):
            got[int(np.asarray(ctx)[s])] = np.asarray(logits[s, 0])
        return decode(params, pages, last, ctx, tables, active, *rest)

    eng._prefill_step, eng._decode_step = tapped_prefill, tapped_decode
    return got


@pytest.mark.parametrize("prompt,new", [(5, 14), (64, 10), (150, 6)])
def test_the_engine_over_two_groups_matches_one_full_forward(
        family, prompt, new):
    """Chunked prefill then decode through the engine's own programs
    over the two-group cache against the reference's ONE full forward:
    a prompt under the window whose decode steps cross it (5 -> 19), a
    prompt that ends exactly on a page's and the window's edge (64 = 4
    windows = 8 pages), one of nine windows (150).  Window pages have
    gone back to the allocator before most compared positions."""
    model, params, ref, weights, cfg = family
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=2, block_size=BS, max_model_len=192, prefill_chunk=CHUNK))
    got = _tapped(eng)
    toks = _tokens(prompt, seed=5)
    req = eng.submit(toks, SamplingParams(max_new_tokens=new,
                                          temperature=0.0))
    while req.finish_reason is None:
        assert eng.step()
        eng.blocks.check_invariants()
    seq = toks + list(req.out_tokens)
    want = np.asarray(ref.forward_logits(weights, cfg, seq))
    rows = sorted(got)
    assert rows[-1] == prompt + new - 2 and prompt - 1 in rows
    assert len(rows) == -(-prompt // CHUNK) + new - 1
    np.testing.assert_allclose(np.stack([got[t] for t in rows]), want[rows],
                               atol=LOGIT_TOL, rtol=0)
    # greedy: the engine's tokens are the reference's choices
    assert list(req.out_tokens) == [int(t) for t in
                                    want[prompt - 1:-1].argmax(-1)]
    if prompt > 2 * WINDOW:
        assert eng.stats()["kv_window_pages_returned"] > 0


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(family, fault):
    """The same comparison against each FAULTY reference, at a context of
    nine windows: every one is whole tenths of a logit away."""
    model, params, ref, weights, cfg = family
    toks = _tokens(150, seed=5)
    got = np.asarray(model(params, jnp.asarray([toks], jnp.int32),
                           train=False)[0])
    faulty = np.asarray(ref.forward_logits(weights, cfg, toks,
                                           faults={fault}))
    apart = np.abs(got - faulty).max(axis=-1)
    assert apart[2 * WINDOW:].max() > 100 * LOGIT_TOL, apart.max()
    if fault in ("all_full",):
        # nothing lies behind a window yet
        assert apart[:WINDOW].max() < LOGIT_TOL


def test_window_pages_go_back_and_a_slot_is_reused(family):
    """A request of 150 + 6 tokens holds at most the bound of window
    pages at any step (5 of the 20 its context spans) while the full
    group keeps all 20; its pages go back, and a second request in the
    reused slot answers as the plain forward does."""
    model, params = family[:2]
    eng, req = _serve(model, params, _tokens(150, seed=7), 6)
    stats = eng.stats()
    assert stats["window_blocks_in_use"] == 0 == stats["blocks_in_use"]
    assert stats["window_blocks_total"] == 2 * BOUND
    spanned, returned = (stats["kv_window_pages_spanned"],
                         stats["kv_window_pages_returned"])
    assert spanned == -(-(150 + 5) // BS) == 20
    assert spanned - BOUND <= returned < spanned
    records = eng.loop_profiler.records()
    assert sum(r.kv_window_pages_returned for r in records) == returned
    assert all(r.kv_held_bytes > 0 for r in records)
    # at the last launch: 20 full pages on 2 layers, <= 5 window pages on 6
    per_layer_page = BS * 2 * model.cfg.num_query_groups * model.cfg.head_dim * 4
    last = records[-1]
    assert last.kv_full_pages_held == 20
    assert last.kv_held_bytes <= (20 * 2 + BOUND * 6) * per_layer_page
    assert last.kv_live_tokens == 150 + 4    # as the launch begins
    # one table a slot would hold 8 layers of every page
    assert last.kv_held_bytes < 20 * 8 * per_layer_page / 2
    second = eng.submit(_tokens(40, seed=8),
                        SamplingParams(max_new_tokens=5, temperature=0.0))
    while second.finish_reason is None:
        assert eng.step()
        eng.blocks.check_invariants()
    toks = _tokens(40, seed=8)
    for _ in range(5):
        logits = model(params, jnp.asarray([toks], jnp.int32), train=False)
        toks.append(int(jnp.argmax(logits[0, -1])))
    assert toks[40:] == list(second.out_tokens)


def test_two_requests_share_the_window_group(family):
    """Two long requests decode side by side, each within its bound, the
    invariants held after every step; both answer as when alone."""
    model, params = family[:2]
    alone = [list(_serve(model, params, _tokens(n, seed=s), 8)[1].out_tokens)
             for n, s in ((90, 11), (70, 12))]
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=2, block_size=BS, max_model_len=192, prefill_chunk=CHUNK))
    reqs = [eng.submit(_tokens(n, seed=s),
                       SamplingParams(max_new_tokens=8, temperature=0.0))
            for n, s in ((90, 11), (70, 12))]
    while any(r.finish_reason is None for r in reqs):
        assert eng.step()
        eng.blocks.check_invariants()
        assert eng.blocks.stats()["window_blocks_in_use"] <= 2 * BOUND
    assert [list(r.out_tokens) for r in reqs] == alone


def test_the_pools_are_sized_by_group_and_named_in_the_kernels(family):
    model = family[0]
    cfg = model.cfg
    groups = paged_kv.layer_groups(cfg)
    assert groups == ("window", "window", "window", "full") * 2
    pools = paged_kv.init_pools(cfg, 41, BS, window_blocks=11)
    assert [p["k_pages"].shape[0] for p in pools] == [11, 11, 11, 41] * 2
    tables = {"full": jnp.zeros((1, 4), jnp.int32),
              "window": jnp.ones((1, 4), jnp.int32)}
    caches = paged_kv.step_caches(pools, tables, jnp.zeros(1, jnp.int32),
                                  jnp.ones(1, jnp.int32), "xla", groups)
    assert [c.group for c in caches] == list(groups)
    assert int(caches[0].block_tables[0, 0]) == 1
    assert int(caches[3].block_tables[0, 0]) == 0
    with pytest.raises(ValueError, match="window_blocks"):
        paged_kv.init_pools(cfg, 41, BS)
    # a model of one type has one group, whatever its window
    from megatron_llm_tpu.models.mistral import mistral_config

    assert paged_kv.layer_groups(mistral_config("tiny")) is None


def test_what_the_pattern_does_not_support_is_refused_by_name(family,
                                                              capsys):
    model, params = family[:2]
    small = dict(num_slots=2, block_size=8, max_model_len=32,
                 prefill_chunk=16)
    with pytest.raises(ValueError, match="int8 KV pool"):
        paged_kv.init_pools(model.cfg, 4, BS, quantized=True,
                            window_blocks=4)
    with pytest.raises(ValueError, match="int8 KV pool"):
        InferenceEngine(model, params, EngineConfig(int8_kv_cache=True,
                                                    **small))
    with pytest.raises(ValueError, match="speculative"):
        InferenceEngine(model, params, EngineConfig(speculative=True,
                                                    draft_k=2, **small))
    with pytest.raises(ValueError, match="host KV tier"):
        InferenceEngine(model, params, EngineConfig(host_cache_bytes=1 << 20,
                                                    **small))
    eng = InferenceEngine(model, params, EngineConfig(**small))
    assert "the prefix cache adopts nothing" in capsys.readouterr().out
    assert not eng.blocks.prefix_cache_enabled
    with pytest.raises(ValueError, match="whole periods"):
        mellum_config("tiny", num_layers=6)
    with pytest.raises(ValueError, match="sliding_window_size"):
        mellum_config("tiny", sliding_window_size=None)
    with pytest.raises(ValueError, match="gives its layers no type"):
        tfm.attention(jnp.zeros((1, 2, 128)), {}, model.cfg, freqs=None,
                      attention_mask=None, position_ids=None,
                      dropout_key=None, train=False)


def test_parallelism_is_refused_at_construction(monkeypatch):
    from megatron_llm_tpu.models import mellum

    monkeypatch.setattr(mellum, "_vocab_unsharded", lambda: False)
    with pytest.raises(ValueError, match="tensor or pipeline"):
        MellumModel(mellum_config("tiny"))


def test_the_family_wrapper_asserts_its_flags():
    cfg = mellum_config("tiny")
    for bad in (dict(norm_topk_prob=False), dict(qk_norm_per_head=True),
                dict(layer_types=None, rope_yarn_layer_types=None),
                dict(num_experts=0)):
        with pytest.raises(AssertionError):
            MellumModel(cfg.replace(**bad))
    full = mellum_config("12B-A2.5B")
    assert (full.num_layers, full.hidden_size, full.num_attention_heads,
            full.num_attention_heads_kv, full.head_dim) == (28, 2304, 32, 4,
                                                            128)
    assert (full.num_experts, full.moe_top_k, full.expert_hidden_size,
            full.ffn_hidden_size) == (64, 8, 896, 7168)
    assert full.padded_vocab_size == 98304 and full.rope_theta == 500000.0
    assert full.layer_types == ("sliding", "sliding", "sliding", "full")
    assert full.sliding_window_size == 1024
    assert full.attention_of("sliding") == (1024, None)
    assert full.attention_of("full") == (
        None, (16.0, 8192, 32.0, 1.0, 1.2772588722239782))
    # a layer's parameters: attention 21.23 M, router 0.15 M, experts
    # 396.4 M (417.8 M with them)
    layer = jax.eval_shape(
        lambda k: tfm.init_layer_params(k, full, jnp.bfloat16),
        jax.random.PRNGKey(0))
    sizes = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
             for k, v in layer["attention"].items()}
    assert sizes["query_key_value"] + sizes["dense"] == 21_233_664
    mlp = sum(x.size for x in jax.tree_util.tree_leaves(layer["mlp"]))
    assert mlp == 64 * 3 * 2304 * 896 + 2304 * 64
