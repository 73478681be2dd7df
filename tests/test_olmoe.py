"""OLMoE (64 experts, 8 a token, QK-norm, gates as the router gives them)
and the dropless expert layer, against the benchmark's plain references.

Seeded random weights, CPU, float32 on both sides, small size: hidden 128,
4 heads of 32, 8 experts of 64, 4 a token, 2 layers.  The references are
the files the benchmark's probe loads (``benchmarks/reference/``), loaded
here by path: there is one reference.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.models import moe
from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.language_model import language_model_forward
from megatron_llm_tpu.models.mixtral import MixtralModel, mixtral_config
from megatron_llm_tpu.models.olmoe import OlmoeModel, olmoe_config
from megatron_llm_tpu.ops import paged_kv

REFERENCE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "reference")

# float32 on both sides, the same mathematics summed in another order:
# the logits (standard deviation 0.23 at these sizes) read 5e-7 apart at
# the worst position.  2e-4 leaves room for another backend's order of
# summation and is 38 times under what bf16 compute moves them (0.0077),
# 75 under the capacity einsum's dropped assignments (0.015), 170 under a
# renormalised gate (0.034) and 700 under a per-head QK-norm (0.146).
LOGIT_TOL = 2e-4


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "ref_" + name, os.path.join(REFERENCE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ref_cfg(cfg):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_attention_heads,
            "num_key_value_heads": cfg.num_attention_heads_kv,
            "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.layernorm_epsilon,
            "sliding_window": None,
            "num_local_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.moe_top_k}


def _randomise_scales(params, key):
    """Norm scales are 1 at init; a test that must tell a norm over the
    whole projection from one a head, or a relabelled scale from one left
    in place, needs them to differ."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        if any(getattr(p, "key", None) == "scale" for p in path):
            leaf = leaf * (1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, leaf.dtype))
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


FAMILIES = {
    "olmoe": (OlmoeModel, lambda: olmoe_config("tiny", use_flash_attn=False),
              "olmoe", "olmoe_from_program"),
    "mixtral": (MixtralModel,
                lambda: mixtral_config("tiny", use_flash_attn=False,
                                       padded_vocab_size=512, seq_length=256,
                                       max_position_embeddings=512),
                "decoder", "from_program"),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def family(request):
    """(model, params, reference module, its weights, its cfg dict)."""
    model_cls, make_cfg, ref_name, adapter = FAMILIES[request.param]
    model = model_cls(make_cfg())
    params = _randomise_scales(model.init(jax.random.PRNGKey(0)),
                               jax.random.PRNGKey(1))
    cfg = _ref_cfg(model.cfg)
    weights = _load(adapter).ProgramWeights(params, cfg)
    return model, params, _load(ref_name), weights, cfg


def _tokens(n, seed=3, vocab=512):
    return np.random.default_rng(seed).integers(1, vocab - 1, n).tolist()


def test_full_forward_matches_the_reference(family):
    """(a), (g): the program's plain forward (the dropless path: nothing
    trains here) against the reference, logits at every position."""
    model, params, ref, weights, cfg = family
    toks = _tokens(48)
    got = np.asarray(model(params, jnp.asarray([toks], jnp.int32),
                           train=False)[0])
    want = np.asarray(ref.forward_logits(weights, cfg, toks))
    assert want.std() > 0.1
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


BS, M, CHUNK = 8, 8, 16


def _paged_step(model, params, pages, toks, start, valid, bt):
    """One engine-shaped call through the paged cache: rows of ``toks``
    [S, n], each with ``valid[s]`` real tokens appended at ``start[s]``.
    Returns (logits, the pages, each layer's histogram of assignments)."""
    S, n = toks.shape
    caches = paged_kv.step_caches(
        pages, bt, jnp.asarray(start, jnp.int32),
        jnp.asarray(valid, jnp.int32), "xla")
    positions = jnp.asarray(start, jnp.int32)[:, None] + jnp.arange(n)[None]
    logits, caches = language_model_forward(
        params, jnp.asarray(toks, jnp.int32), positions, None, model.cfg,
        rng_key=None, train=False, kv_caches=caches)
    return (np.asarray(logits), paged_kv.pools_of(caches),
            np.asarray(paged_kv.routing_of(caches)))


def test_chunked_prefill_then_decode_matches_one_full_forward(family):
    """(b), (e): a prompt of 37 tokens prefilled in chunks of 16 (the last
    one 5 tokens and 11 of padding), then 3 tokens decoded in a batch of
    two slots of which one is idle, through the paged cache: the logits
    at every real position against the reference's one full forward, and
    the routing histograms count the live tokens only."""
    model, params, ref, weights, cfg = family
    k, E, L = model.cfg.moe_top_k, model.cfg.num_experts, model.cfg.num_layers
    toks = _tokens(40, seed=5)
    want = np.asarray(ref.forward_logits(weights, cfg, toks))
    pages = paged_kv.init_pools(model.cfg, 1 + 2 * M, BS)
    bt = jnp.asarray(np.arange(1, 1 + 2 * M).reshape(2, M), jnp.int32)
    got = []
    for start in range(0, 37, CHUNK):
        valid = min(CHUNK, 37 - start)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :valid] = toks[start:start + valid]
        logits, pages, counts = _paged_step(
            model, params, pages, chunk, [start], [valid], bt[:1])
        got.append(logits[0, :valid])
        # padding is routed nowhere
        assert counts.shape == (L, E)
        assert (counts.sum(axis=1) == valid * k).all()
    for pos in range(37, 40):
        step = np.asarray([[toks[pos]], [7]], np.int32)
        logits, pages, counts = _paged_step(
            model, params, pages, step, [pos, 0], [1, 0], bt)
        got.append(logits[0])
        # the idle slot touches no expert: one token's k choices a layer
        assert (counts.sum(axis=1) == k).all()
        assert ((counts > 0).sum(axis=1) == k).all()
    np.testing.assert_allclose(np.concatenate(got), want, atol=LOGIT_TOL,
                               rtol=0)


# ---------------------------------------------------------------------------
# the expert layer alone
# ---------------------------------------------------------------------------

def _layer(norm_topk_prob, **kw):
    cfg = olmoe_config("tiny", **kw).replace(norm_topk_prob=norm_topk_prob)
    p = moe.init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    return cfg, p


def _reference_layer(ref, x, p, cfg):
    """The reference's own pieces on an input that is already normed (a
    unit scale and eps 0 leave rms_norm to divide by the input's RMS, so
    the program is fed what comes out of it)."""
    ones = jnp.ones((x.shape[-1],), jnp.float32)
    hn, dense, _ = ref.moe_gates(
        x, ones, p["router"]["kernel"], jnp.zeros(x.shape[0], bool),
        eps=0.0, top_k=cfg.moe_top_k)
    F = cfg.ffn_hidden_size
    y = jnp.zeros_like(hn)
    for e in range(cfg.num_experts):
        w_in, w_out = p["experts"]["w_in"][e], p["experts"]["w_out"][e]
        y = y + ref.expert_out(hn, dense[:, e], w_in[:, :F], w_out,
                               w_in[:, F:])
    return hn, dense, y


def test_every_token_on_the_same_experts_drops_nothing():
    """(c): a router biased so that all 64 tokens choose the same 4 of 8
    experts.  The dropless path equals the reference; the capacity einsum
    at the program's default factor (room for 40 assignments an expert
    where 64 arrive) drops tokens and does not."""
    ref = _load("olmoe")
    cfg, p = _layer(False)
    wr = np.asarray(p["router"]["kernel"]).copy()
    wr[:, [1, 3, 4, 6]] += 0.5      # inputs are positive: +0.5 * sum(x)
    p["router"]["kernel"] = jnp.asarray(wr)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (64, 128))) + 0.1
    hn, dense, want = _reference_layer(ref, x, p, cfg)
    assert (np.asarray((dense > 0).sum(axis=0)) ==
            [0, 64, 0, 64, 64, 0, 64, 0]).all()
    got, _, counts = moe.moe_mlp_dropless(hn[None], p, cfg)
    assert np.asarray(counts).tolist() == [0, 64, 0, 64, 64, 0, 64, 0]
    # float32, one expert's products summed in another order: 1e-6 of
    # outputs near 0.01
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-6, rtol=1e-5)
    dropped, _ = moe.moe_mlp(hn[None], p, cfg)
    assert np.abs(np.asarray(dropped[0]) - np.asarray(want)).max() > 1e-4


def test_gates_are_the_softmax_as_it_is():
    """(d): with ``norm_topk_prob`` off a token's gates are the softmax's
    own values over all experts and sum to less than 1; with it on
    (Mixtral) they sum to 1."""
    cfg, p = _layer(False)
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 128))
    logits, probs, gates, idx = moe._route(x, p, cfg)
    np.testing.assert_allclose(
        np.asarray(gates),
        np.asarray(jnp.take_along_axis(jax.nn.softmax(logits, -1), idx, -1)),
        rtol=1e-6)
    sums = np.asarray(gates.sum(-1))
    assert (sums < 0.9).all() and (sums > 0.4).all(), sums
    _, _, renormed, idx2 = moe._route(x, p, cfg.replace(norm_topk_prob=True))
    assert (np.asarray(idx2) == np.asarray(idx)).all()
    np.testing.assert_allclose(np.asarray(renormed.sum(-1)), 1.0, rtol=1e-6)


def test_tokens_that_are_not_live_are_routed_nowhere():
    """(e), at the layer: rows of no live token and a row's padding add
    nothing to the histogram, get a zero output, and leave the live
    tokens' outputs as they are."""
    cfg, p = _layer(False)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 16, 128))
    live = jnp.arange(16)[None, :] < jnp.asarray([5, 0, 16])[:, None]
    full, _, all_counts = moe.moe_mlp_dropless(x, p, cfg)
    got, _, counts = moe.moe_mlp_dropless(x, p, cfg, live)
    assert int(all_counts.sum()) == 3 * 16 * cfg.moe_top_k
    assert int(counts.sum()) == 21 * cfg.moe_top_k
    keep = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(full)[keep],
                               atol=1e-7)
    assert np.abs(np.asarray(got)[~keep]).max() == 0.0
    # two live tokens cannot touch more than 2 k experts
    two = jnp.zeros((3, 16), bool).at[0, :2].set(True)
    _, _, c2 = moe.moe_mlp_dropless(x, p, cfg, two)
    assert int((c2 > 0).sum()) <= 2 * cfg.moe_top_k


@pytest.mark.parametrize("layer", [0, 2])
def test_the_layers_experts_out_of_the_stacked_weights(layer):
    """What the engine's programs do: the experts of every layer go in
    stacked and the layer's index picks its groups; the same numbers as
    handing in that layer's slice."""
    cfg, _ = _layer(False)
    ps = [moe.init_moe_mlp_params(jax.random.PRNGKey(i), cfg, jnp.float32)
          for i in range(3)]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ps)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 128))
    want, _, wc = moe.moe_mlp_dropless(x, ps[layer], cfg)
    got, _, gc = moe.moe_mlp_dropless(
        x, {"router": ps[layer]["router"], "experts": stacked["experts"]},
        cfg, layer=layer)
    assert np.asarray(gc).tolist() == np.asarray(wc).tolist()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)


def test_dropless_equals_the_capacity_einsum_where_it_drops_nothing():
    """(g): Mixtral's layer (renormalised gates) on the dropless path
    against the capacity einsum at a capacity that drops nothing."""
    cfg = mixtral_config("tiny", moe_capacity_factor=2.0)   # = E / k
    p = moe.init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 128))
    want, aux_want = moe.moe_mlp(x, p, cfg)
    got, aux_got, _ = moe.moe_mlp_dropless(x, p, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(np.asarray(aux_got), np.asarray(aux_want),
                               rtol=1e-5)


def test_qk_norm_over_the_whole_projection_is_not_a_norm_a_head(monkeypatch):
    """(f): on the same weights a per-head QK-norm moves the logits by
    hundreds of times the tolerance the whole-projection one is held to,
    so test (a) can tell them apart."""
    model_cls, make_cfg, ref_name, adapter = FAMILIES["olmoe"]
    model = model_cls(make_cfg())
    params = _randomise_scales(model.init(jax.random.PRNGKey(0)),
                               jax.random.PRNGKey(1))
    toks = jnp.asarray([_tokens(48)], jnp.int32)
    whole = np.asarray(model(params, toks, train=False))

    def per_head(x, scale, eps):
        b, s, n, d = x.shape
        y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        return y * scale.reshape(n, d)

    monkeypatch.setattr(tfm, "_projection_rms_norm", per_head)
    heads = np.asarray(model(params, toks, train=False))
    assert np.abs(heads - whole).max() > 100 * LOGIT_TOL


def test_the_family_wrapper_asserts_its_flags():
    cfg = olmoe_config("tiny")
    assert (cfg.num_experts, cfg.moe_top_k, cfg.qk_norm,
            cfg.norm_topk_prob) == (8, 4, True, False)
    full = olmoe_config("1B-7B")
    assert (full.hidden_size, full.num_attention_heads,
            full.num_attention_heads_kv, full.head_dim, full.num_experts,
            full.moe_top_k, full.ffn_hidden_size, full.padded_vocab_size,
            full.max_position_embeddings, full.num_layers) == (
        2048, 16, 16, 128, 64, 8, 1024, 50304, 4096, 16)
    with pytest.raises(AssertionError):
        OlmoeModel(cfg.replace(norm_topk_prob=True))
    with pytest.raises(AssertionError):
        OlmoeModel(cfg.replace(qk_norm=False))
    params = OlmoeModel(cfg).init(jax.random.PRNGKey(0))
    att = params["transformer"]["layers"]["attention"]
    assert att["q_norm"]["scale"].shape == (2, 128)
    assert att["k_norm"]["scale"].shape == (2, 128)


# ---------------------------------------------------------------------------
# the engine: the routing counters ride the launch record
# ---------------------------------------------------------------------------

def test_engine_counts_live_assignments_only():
    """(e), end to end: ``--model_name=olmoe``'s model through the serving
    engine.  A prompt of 21 tokens in chunks of 16 (the second one 5
    tokens and 11 of padding) and 4 tokens decoded in a batch of 4 slots
    of which one is live: every launch's record counts its live tokens
    x k x layers, touches no more experts than that, and the engine's
    running totals are the records' sums."""
    from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                          SamplingParams)
    from megatron_llm_tpu.serving.loop_profiler import MOE_FIELDS

    model = OlmoeModel(olmoe_config("tiny", use_flash_attn=False))
    params = model.init(jax.random.PRNGKey(0))
    k, E, L = model.cfg.moe_top_k, model.cfg.num_experts, model.cfg.num_layers
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=4, block_size=16, max_model_len=64, prefill_chunk=16))
    req = eng.submit(_tokens(21, seed=9),
                     SamplingParams(max_new_tokens=4, temperature=0.0))
    while req.finish_reason is None:
        assert eng.step()
    records = eng.loop_profiler.records()
    live = [r.valid if r.kind == "prefill" else r.rows for r in records]
    assert live == [16, 5, 1, 1, 1]
    for r, n in zip(records, live):
        assert r.moe_assignments == n * k * L
        assert r.moe_expert_slots == L * E
        assert L * min(k, n * k) <= r.moe_experts_touched <= min(
            n * k, E) * L
        assert L * -(-n * k // E) <= r.moe_busiest_expert_assignments \
            <= n * L
    stats = eng.stats()
    for f in MOE_FIELDS:
        assert stats[f] == sum(getattr(r, f) for r in records) > 0
    # beside them the static choice: the two expert matrices' blocks
    H, F = model.cfg.hidden_size, model.cfg.expert_hidden_size
    blocks = stats["moe_expert_tiles"]
    assert {m: (b["k"], b["n"]) for m, b in blocks.items()} == {
        "w_in": (H, 2 * F), "w_out": (F, H)}
    assert all(b["steps_per_visit"] == (b["k"] // b["tk"])
               * (b["n"] // b["tn"]) for b in blocks.values())
    # a dense model routes nothing
    assert all(getattr(type(records[0]), f) == 0 for f in MOE_FIELDS)


def _reference_routing(ref, weights, cfg, tokens):
    """[positions, layers, E] bool: the experts the REFERENCE's router
    chooses at every position of ``tokens``, layer by layer, from its own
    gate logits (``forward_logits``'s loop, kept to the routing)."""
    x = weights.embedding()[jnp.asarray(tokens, jnp.int32)].astype(
        jnp.float32)
    eps, chosen = float(cfg["rms_norm_eps"]), []
    for i in range(int(cfg["num_hidden_layers"])):
        w = weights.layer(i)
        x = ref.attention_block(
            x, w, n_heads=int(cfg["num_attention_heads"]),
            n_kv=int(cfg["num_key_value_heads"]),
            theta=float(cfg["rope_theta"]), eps=eps)
        hn, dense, _ = ref.moe_gates(
            x, w["ffn_norm"], w["gate"], jnp.zeros(x.shape[0], bool),
            eps=eps, top_k=int(cfg["num_experts_per_tok"]))
        chosen.append(np.asarray(dense) > 0)
        for e in range(int(cfg["num_local_experts"])):
            ew = weights.expert(i, e)
            x = x + ref.expert_out(hn, dense[:, e], ew["w1"], ew["w2"],
                                   ew["w3"])
    return np.stack(chosen, axis=1)


@pytest.mark.parametrize("speculative", [False, True])
def test_engine_histogram_is_the_routers_own(speculative):
    """The ``[layers, E]`` histogram every engine program returns (through
    the cache's declared field) against the reference's router on the same
    step's tokens: prefill chunks, decode steps and, with speculation on,
    verify steps whose rejected drafts are routed and counted too."""
    from megatron_llm_tpu.serving import (EngineConfig, InferenceEngine,
                                          SamplingParams)

    model = OlmoeModel(olmoe_config("tiny", use_flash_attn=False))
    params = model.init(jax.random.PRNGKey(0))
    cfg = _ref_cfg(model.cfg)
    ref = _load("olmoe")
    weights = _load("olmoe_from_program").ProgramWeights(params, cfg)
    eng = InferenceEngine(model, params, EngineConfig(
        num_slots=2, block_size=16, max_model_len=64, prefill_chunk=16,
        prefix_cache=False, speculative=speculative, draft_k=2))
    launches = []

    def recording(name, toks_of):
        program = getattr(eng, name)

        def run(*args):
            out = program(*args)
            launches.append((name, toks_of(*args), np.asarray(out[-1])))
            return out
        setattr(eng, name, run)

    # (row 0's context length, its real tokens of this launch)
    recording("_prefill_step", lambda p, pg, toks, start, valid, bt:
              (int(start), toks[0, :int(valid)].tolist()))
    recording("_decode_step", lambda p, pg, last, ctx, bt, active, *r:
              (int(ctx[0]), [int(last[0])]))
    recording("_verify_step", lambda p, pg, toks, ctx, bt, vlens, *r:
              (int(ctx[0]), toks[0, :int(vlens[0])].tolist()))
    prompt = _tokens(21, seed=9)
    if speculative:
        # a prompt whose decode steps draft: the model's own greedy
        # continuation as far as the first token that closes a bigram the
        # history has seen (greedy decoding repeats itself)
        from megatron_llm_tpu.serving.drafter import lookup_draft
        first = eng.submit(prompt, SamplingParams(max_new_tokens=32,
                                                  temperature=0.0))
        while first.finish_reason is None:
            assert eng.step()
        out = first.out_tokens
        n = next(n for n in range(1, len(out) + 1)
                 if lookup_draft(prompt + out[:n], 1))
        prompt = prompt + out[:n - 1]
        launches.clear()
    req = eng.submit(prompt, SamplingParams(max_new_tokens=6,
                                            temperature=0.0))
    while req.finish_reason is None:
        assert eng.step()
    committed = req.context_tokens()
    kinds = [name for name, _, _ in launches]
    chunks = -(-len(prompt) // 16)
    assert kinds[:chunks] == ["_prefill_step"] * chunks
    assert set(kinds[chunks:]) == {"_verify_step" if speculative
                                   else "_decode_step"}
    for name, (ctx, toks), got in launches:
        chosen = _reference_routing(ref, weights, cfg,
                                    committed[:ctx] + toks)
        want = chosen[ctx:].sum(axis=0)                    # [layers, E]
        assert got.tolist() == want.tolist(), (name, ctx, toks)
    if speculative:
        assert max(len(t) for n, (_, t), _ in launches
                   if n == "_verify_step") > 1
