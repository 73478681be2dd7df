"""Keye (the language model of Keye-VL-2.0-30B-A3B): per-head QK-norm,
the sectioned rotary embedding, 8 of 128 small experts with gates
renormalised, and learned sparse attention (an indexer chooses each
query's keys), against the benchmark's plain reference.

Seeded random weights, CPU, float32 on both sides, small size: 2 layers,
hidden 128, 4 query and 2 KV heads of 32, 4 indexer heads of 16, top-k 8
over contexts of 5 to 70 tokens, 8 experts of 64 at 4 a token.  The
reference is the file the benchmark's probe loads
(``benchmarks/reference/keye.py``), loaded here by path.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import BS, is_greedy, serve, tokens
from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.keye import KeyeModel, keye_config
from megatron_llm_tpu.models.language_model import language_model_forward
from megatron_llm_tpu.ops import dsa, paged_kv

# float32 on both sides, the same mathematics summed in another order:
# the logits (standard deviation 1.4 with the weights scaled as the row
# says) read 5e-6 apart at the worst position.  2e-4 leaves room for
# another backend's order of summation; dense attention in the
# selection's place moves them by whole units.
ROW = _family.FAMILIES["keye"]
LOGIT_TOL, CHUNK = ROW.tol, ROW.chunk
TOPK = 8
M = 12


@pytest.fixture(scope="module")
def family():
    return _family.built("keye")


@pytest.mark.parametrize("n", [5, 8, 9, 70])
def test_full_forward_matches_the_reference(n):
    """The program's plain (cache-less) forward selects too: logits at
    every position against the reference, at contexts under the top-k
    (5), at it (8), one past it (9) and far past it (70)."""
    _family.full_forward_is_the_references("keye", n)


def test_the_reference_reports_its_experts_and_takes_given_ones(family):
    """``routing`` leaves each layer's chosen experts; the same experts
    given back (``forced``) change nothing; one expert exchanged for the
    first rejected one at one position is the ``turned`` choice there, and
    moves that position's logits and no earlier one's."""
    _, _, ref, weights, cfg = family
    toks = tokens(20)
    routed, margins = [], []
    want = np.asarray(ref.forward_logits(weights, cfg, toks, routing=routed,
                                         router_margins=margins))
    top_k = cfg["num_experts_per_tok"]
    assert len(routed) == cfg["num_hidden_layers"]
    for chose, below in routed:
        assert chose.shape == (20, top_k) and not below.any()
    t = 11
    own = {layer: {t: routed[layer][0][t]} for layer in range(len(routed))}
    same = np.asarray(ref.forward_logits(weights, cfg, toks, forced=own))
    np.testing.assert_allclose(same, want, atol=1e-6, rtol=0)
    turned = np.asarray(ref.forward_logits(weights, cfg, toks,
                                           turned={0: [t]}))
    again = []
    seen = np.asarray(ref.forward_logits(weights, cfg, toks, routing=again,
                                         turned={0: [t]}))
    given = np.asarray(ref.forward_logits(
        weights, cfg, toks, forced={0: {t: again[0][0][t]}}, routing=again))
    np.testing.assert_allclose(given, turned, atol=1e-6, rtol=0)
    np.testing.assert_allclose(seen, turned, atol=1e-6, rtol=0)
    # the given experts' lowest lies the router's margin below its own
    # last choice
    np.testing.assert_allclose(again[-2][1][t], np.asarray(margins[0])[t],
                               rtol=1e-5)
    assert np.abs(turned[t] - want[t]).max() > 1e-4
    np.testing.assert_allclose(turned[:t], want[:t], atol=1e-6, rtol=0)


@functools.lru_cache(maxsize=None)
def _paged_forward(model, kernel):
    """A launch through the paged pool as one program a shape (run
    eagerly it is compiled an operation at a time)."""
    def forward(params, pages, toks, start, valid, bt):
        caches = paged_kv.step_caches(pages, bt, start, valid, kernel)
        positions = start[:, None] + jnp.arange(toks.shape[1])[None]
        logits, caches = language_model_forward(
            params, toks, positions, None, model.cfg, rng_key=None,
            train=False, kv_caches=caches)
        return logits, paged_kv.pools_of(caches)
    return jax.jit(forward)


def _paged_step(model, params, pages, toks, start, valid, bt, kernel="xla"):
    logits, pages = _paged_forward(model, kernel)(
        params, pages, jnp.asarray(toks, jnp.int32),
        jnp.asarray(start, jnp.int32), jnp.asarray(valid, jnp.int32), bt)
    return np.asarray(logits), pages


def _prefill_then_decode(model, params, toks, prompt, kernel="xla"):
    """``toks[:prompt]`` prefilled in chunks of 16 (the first chunk
    straddles the top-k of 8, the last is short and padded), the rest
    decoded in a batch of two slots of which one is idle, through the
    paged pool: the logits at every position."""
    pages = paged_kv.init_pools(model.cfg, 1 + 2 * M, BS)
    bt = jnp.asarray(np.arange(1, 1 + 2 * M).reshape(2, M), jnp.int32)
    got = []
    for start in range(0, prompt, CHUNK):
        valid = min(CHUNK, prompt - start)
        chunk = np.zeros((1, CHUNK), np.int32)
        chunk[0, :valid] = toks[start:start + valid]
        logits, pages = _paged_step(model, params, pages, chunk, [start],
                                    [valid], bt[:1], kernel)
        got.append(logits[0, :valid])
    for pos in range(prompt, len(toks)):
        step = np.asarray([[toks[pos]], [7]], np.int32)
        logits, pages = _paged_step(model, params, pages, step, [pos, 0],
                                    [1, 0], bt, kernel)
        got.append(logits[0])
    return np.concatenate(got)


@pytest.mark.parametrize("prompt,total", [(5, 12), (37, 40), (67, 70)])
def test_chunked_prefill_then_decode_matches_one_full_forward(
        family, prompt, total):
    """Prefill in chunks then decode through the three-array pool against
    the reference's ONE full forward, logits at every position: a prompt
    under the top-k whose decode steps cross it (5 -> 12), a prompt whose
    first chunk straddles it (37), a long one (67)."""
    model, params, ref, weights, cfg = family
    toks = tokens(total, seed=5)
    want = np.asarray(ref.forward_logits(weights, cfg, toks))
    got = _prefill_then_decode(model, params, toks, prompt)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_dense_attention_in_the_selections_place_fails(family):
    """The same comparison with every earlier key attended (the
    reference's ``dense`` fault) is hundreds of tolerances apart once the
    context passes the top-k, and equal before it."""
    model, params, ref, weights, cfg = family
    toks = tokens(40, seed=5)
    got = _prefill_then_decode(model, params, toks, 37)
    dense = np.asarray(ref.forward_logits(weights, cfg, toks,
                                          faults={"dense"}))
    apart = np.abs(got - dense).max(axis=-1)
    assert apart[:TOPK].max() < LOGIT_TOL        # nothing to leave out yet
    assert apart[TOPK + 4:].min() > 100 * LOGIT_TOL
    for fault in ("topk_half", "unweighted", "whole_qk_norm"):
        faulty = np.asarray(ref.forward_logits(weights, cfg, toks,
                                               faults={fault}))
        assert np.abs(got - faulty).max() > 100 * LOGIT_TOL, fault


def test_the_plain_forward_selects_in_blocks_of_queries():
    """``causal_selected_attention`` over 70 queries in blocks of 16 (the
    last block padded) is the same as in one block."""
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    n, T = 70, 70
    q = jax.random.normal(ks[0], (2, n, 4, 32))
    k = jax.random.normal(ks[1], (2, T, 2, 32))
    v = jax.random.normal(ks[2], (2, T, 2, 32))
    iq = jax.random.normal(ks[3], (2, n, 4, 16))
    ik = jax.random.normal(ks[4], (2, T, 16))
    iw = jax.random.normal(ks[5], (2, n, 4))
    whole = dsa.causal_selected_attention(q, k, v, iq, ik, iw, TOPK,
                                          block_q=128)
    blocks = dsa.causal_selected_attention(q, k, v, iq, ik, iw, TOPK,
                                           block_q=16)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               atol=1e-6)


def test_select_mask_is_top_k_with_the_earlier_position_first():
    """The sort-free choice against ``lax.top_k`` (the reference's, whose
    tie rule is the lower index first) on rows full of ties, rows with
    fewer valid entries than k, negative, zero and infinite scores."""
    rng = np.random.default_rng(0)
    T, k = 70, 8
    rows = [rng.integers(-2, 3, T).astype(np.float32),      # ties
            np.zeros(T, np.float32),                        # all equal
            rng.normal(size=T).astype(np.float32),
            np.where(rng.random(T) < 0.5, 0.0,
                     rng.normal(size=T)).astype(np.float32),
            -np.abs(rng.normal(size=T)).astype(np.float32) * 1e30,
            rng.integers(0, 2, T).astype(np.float32) * 3e38]
    scores = jnp.asarray(np.stack(rows * 3))
    n_valid = np.repeat([T, 11, 5], len(rows))
    valid = jnp.arange(T)[None, :] < jnp.asarray(n_valid)[:, None]
    got = np.asarray(dsa.select_mask(scores, valid, k))
    masked = jnp.where(valid, scores, -jnp.inf)
    _, idx = jax.lax.top_k(masked, k)
    want = np.zeros(got.shape, bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    want &= np.asarray(valid)
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(n_valid, k)).all()


def test_page_programs_carry_the_indexers_keys(family):
    """A page of the pool is a page of all three arrays: copy-on-write,
    the host tier's fetch and load move the indexer's keys with K and V,
    and ``block_bytes`` counts them."""
    model = family[0]
    cfg = model.cfg
    pools = paged_kv.init_pools(cfg, 6, BS)
    assert set(pools[0]) == {"k_pages", "v_pages", "index_pages"}
    # the indexer's key of 16 is held 128 wide, the TPU's lanes
    assert pools[0]["index_pages"].shape == (6, BS, 128)
    per_token = (2 * cfg.num_query_groups * cfg.head_dim + 128) * 4
    assert paged_kv.block_bytes(pools) == cfg.num_layers * BS * per_token
    key = jax.random.PRNGKey(0)
    pools = jax.tree_util.tree_map(
        lambda a: jax.random.normal(key, a.shape, a.dtype), pools)
    copied = paged_kv.copy_page(pools, 2, 4)
    page = paged_kv.fetch_page(pools, 2)
    loaded = paged_kv.load_page(pools, page, 5)
    for layer in range(cfg.num_layers):
        for name in ("k_pages", "v_pages", "index_pages"):
            src = np.asarray(pools[layer][name][2])
            assert np.abs(src).max() > 0
            assert (np.asarray(copied[layer][name][4]) == src).all()
            assert (np.asarray(loaded[layer][name][5]) == src).all()
            assert (np.asarray(page[layer][name]) == src).all()


def test_a_prefix_cache_adoption_carries_the_indexers_keys(family, engines):
    """Through the engine: a second request with the first one's prompt
    adopts its pages (the prefix cache), indexer keys and all, and
    answers with the same tokens as the first, which prefilled them."""
    eng = engines.fresh("keye")
    prompt = tokens(41, seed=11)
    answers = [list(serve(eng, prompt, 6).out_tokens) for _ in range(2)]
    assert eng.stats()["prefill_tokens_cached"] >= 32
    assert answers[0] == answers[1]
    # and the engine's answer is the plain forward's greedy continuation
    assert is_greedy(*family[:2], prompt, answers[0])


COUNTS = dict(num_slots=4, max_model_len=64, prefix_cache=False)


def test_engine_counts_the_keys_each_query_sees_and_selects(family, engines):
    """``dsa_keys_live`` / ``dsa_keys_selected`` on every launch record:
    the host's count, from the arrays it hands the program, of the
    context each live query sees, summed over rows and layers, and the
    same with each term cut at the top-k; ``stats()`` keeps the totals."""
    L = family.model.cfg.num_layers
    eng = engines("keye", **COUNTS)
    since = _family.counted(eng)
    serve(eng, tokens(21, seed=9), 3)
    stats, records = since()
    assert [r.kind for r in records] == ["prefill"] * 2 + ["decode"] * 2
    sees = [range(1, 17), range(17, 22), [22], [23]]
    for r, seen in zip(records, sees):
        assert r.dsa_keys_live == L * sum(seen)
        assert r.dsa_keys_selected == L * sum(min(t, TOPK) for t in seen)
    assert stats["dsa_keys_live"] == sum(r.dsa_keys_live for r in records)
    assert stats["dsa_keys_selected"] == sum(r.dsa_keys_selected
                                             for r in records)


def test_engine_counts_the_blocks_the_choice_counts_over(family, engines,
                                                        monkeypatch):
    """``dsa_select_blocks_counted`` / ``dsa_select_blocks_table`` on every
    launch record: for each select step of the launch the blocks of keys
    it is given (``ops/pallas/dsa_attention.py::select_blocks``: a chunk's
    steps count through the chunk's last live block, the decode step's
    through its longest row), and the same with every step at the table's
    blocks; summed over steps and layers, and over launches in
    ``stats()``.  Blocks of 16 keys here, so a table of 64 tokens is 4."""
    from megatron_llm_tpu.ops.pallas import dsa_attention
    from megatron_llm_tpu.ops.pallas import paged_attention as pa

    # the plan takes its blocks of keys at construction: a new engine
    monkeypatch.setattr(pa, "_BLOCK_TOKENS", 16)
    L = family.model.cfg.num_layers
    eng = engines.fresh("keye", **COUNTS)
    assert eng._cache.dsa_block_keys == 16
    serve(eng, tokens(40, seed=9), 3)
    records = eng.loop_profiler.records()
    assert [r.kind for r in records] == ["prefill"] * 3 + ["decode"] * 2
    # chunks (0, 16), (16, 16), (32, 8): one select step each (16 queries
    # pad to one step of 32) through keys 16, 32, 40; then the decode
    # step's one step of 8 rows, its one live row at 41 and 42 keys
    for r, newest in zip(records, [16, 32, 40, 41, 42]):
        assert r.dsa_select_blocks_counted == L * -(-newest // 16)
        assert r.dsa_select_blocks_table == L * 4
        assert r.as_dict()["dsa_select_blocks_counted"] == \
            r.dsa_select_blocks_counted
    stats = eng.stats()
    assert stats["dsa_select_blocks_counted"] == L * (1 + 2 + 3 + 3 + 3)
    assert stats["dsa_select_blocks_table"] == L * 4 * 5
    # the host's count is the kernel's own prefetched scalar
    np.testing.assert_array_equal(
        dsa_attention.select_blocks(np.asarray([40, 0, 7, 0]),
                                    np.asarray([1, 0, 1, 0]), 1, 16, xp=np),
        np.asarray(dsa_attention.select_blocks(
            jnp.asarray([40, 0, 7, 0]), jnp.asarray([1, 0, 1, 0]), 1, 16)))
    assert dsa_attention.select_blocks(
        np.asarray([32]), np.asarray([8]), 70, 16, xp=np).tolist() == [[3] * 3]


def test_a_model_that_selects_nothing_counts_no_blocks():
    from megatron_llm_tpu.models.llama import LlamaModel, llama_config

    model = LlamaModel(llama_config("tiny", use_flash_attn=False))
    eng = _family.engine(model, model.init(jax.random.PRNGKey(0)),
                         max_model_len=32, prefill_chunk=CHUNK)
    serve(eng, tokens(9, seed=2, vocab=model.cfg.padded_vocab_size), 2)
    for r in eng.loop_profiler.records():
        assert (r.dsa_select_blocks_counted, r.dsa_select_blocks_table,
                r.dsa_keys_live) == (0, 0, 0)
    stats = eng.stats()
    assert stats["dsa_select_blocks_counted"] == 0
    assert stats["dsa_select_blocks_table"] == 0


def test_the_benchmarks_counted_share_reads_the_records_two_fields(
        monkeypatch):
    """``benchmarks/layer_metrics/dsa_select_counted_pct.json`` (data: the
    benchmark's own ``loop_record_ratio`` reads it) names two fields that
    ``DispatchRecord`` has and ``as_dict()`` gives; a record without them
    (the parent's) reads as nothing, so the parent's line lacks the
    metric and the change's has it."""
    import importlib
    import json
    import types

    from megatron_llm_tpu.serving.loop_profiler import DispatchRecord

    bench = os.path.dirname(_family.REFERENCE)
    with open(os.path.join(bench, "layer_metrics",
                           "dsa_select_counted_pct.json")) as f:
        metric = json.load(f)
    assert metric["source"] == "loop_record_ratio"
    params = metric["params"]
    assert (params["numerator"], params["denominator"]) == (
        "dsa_select_blocks_counted", "dsa_select_blocks_table")
    assert params["kinds"] == ["prefill"] and params["scale"] == 100.0
    root = json.load(open(os.path.join(os.path.dirname(bench),
                                       "BENCHMARK.json")))
    declared, = (m for m in root["per_layer"]
                 if m["name"] == "dsa_select_counted_pct")
    # the cells the metric's file names, and any a later configuration
    # with an indexer appended (PR 61's reads the same two fields)
    workloads = declared.pop("workloads")
    assert workloads[:len(metric["cells"])] == metric["cells"]
    assert declared == {
        "name": "dsa_select_counted_pct", "unit": metric["unit"],
        "better": "lower", "source": "program_counter",
        "layer": metric["layer"], "moves": metric["moves"]}

    # the cache's plan fills a record from what the launch is handed: a
    # chunk of 16 at context 16 is one select step through the blocks
    # that hold 32 keys, of a table of 64 tokens
    cfg = keye_config("tiny")
    plan = paged_kv.plan(cfg, 8, 4, 8, 16, "xla", "xla")
    counted = cfg.num_layers * -(-32 // plan.dsa_block_keys)
    table = cfg.num_layers * -(-64 // plan.dsa_block_keys)
    rec = DispatchRecord(lambda: 0.0, 0, 0.0, 0.0)
    rec.kind = "prefill"
    plan.account(rec, np.asarray([16]), np.asarray([16]), 16, 1)
    for field in (params["numerator"], params["denominator"]):
        assert field in rec.as_dict()
    assert (rec.dsa_select_blocks_counted, rec.dsa_select_blocks_table) == (
        counted, table)

    monkeypatch.syspath_prepend(bench)
    ratio = importlib.import_module("harness.spec").load_module(
        "sources", "loop_record_ratio")
    fields = (params["numerator"], params["denominator"])
    assert ratio.sums([rec, rec], *fields) == (2 * counted, 2 * table)
    parents = types.SimpleNamespace(dsa_keys_live=7, dsa_keys_selected=5)
    assert ratio.sums([parents], *fields) is None
    assert ratio.sums([rec, parents], *fields) is None


def test_what_the_selection_does_not_support_is_refused_by_name(family,
                                                                engines):
    model = family.model
    with pytest.raises(ValueError, match="int8 KV pool"):
        paged_kv.init_pools(model.cfg, 4, BS, quantized=True)
    with pytest.raises(ValueError, match="int8 KV pool"):
        engines.fresh("keye", max_model_len=32, int8_kv_cache=True)
    with pytest.raises(ValueError, match="speculative"):
        engines.fresh("keye", max_model_len=32, speculative=True, draft_k=2)
    with pytest.raises(ValueError, match="sliding window"):
        keye_config("tiny", sliding_window_size=16)
    with pytest.raises(ValueError, match="choose one"):
        keye_config("tiny", qk_norm=True)
    with pytest.raises(ValueError, match="only such a pool"):
        pools = paged_kv.init_pools(model.cfg, 4, BS)
        cache = paged_kv.step_caches(pools, jnp.zeros((1, 2), jnp.int32),
                                     jnp.zeros(1, jnp.int32),
                                     jnp.ones(1, jnp.int32), "xla")[0]
        x = jnp.zeros((1, 1, 2, 32))
        cache.attend(jnp.zeros((1, 1, 4, 32)), x, x, None)


def test_tensor_parallelism_is_refused_at_construction(monkeypatch):
    from megatron_llm_tpu.models import keye

    monkeypatch.setattr(keye, "_vocab_unsharded", lambda: False)
    with pytest.raises(ValueError, match="tensor parallelism"):
        KeyeModel(keye_config("tiny"))


def test_the_family_wrapper_asserts_its_flags():
    cfg = keye_config("tiny")
    for bad in (dict(norm_topk_prob=False), dict(qk_norm_per_head=False),
                dict(dsa_index_heads=0), dict(num_experts=0)):
        with pytest.raises(AssertionError):
            KeyeModel(cfg.replace(**bad))
    full = keye_config("30B-A3B")
    assert (full.num_layers, full.hidden_size, full.num_attention_heads,
            full.num_attention_heads_kv, full.head_dim) == (48, 2048, 32, 4,
                                                            128)
    assert (full.num_experts, full.moe_top_k, full.expert_hidden_size,
            full.ffn_hidden_size) == (128, 8, 768, 6144)
    assert (full.dsa_index_heads, full.dsa_index_head_dim,
            full.dsa_topk) == (16, 64, 2048)
    assert full.padded_vocab_size == 151936
    assert full.rope_sections == (16, 24, 24) and full.rope_theta == 1e7
    # a layer's parameters: attention 18.87 M, indexer 2.26 M, router
    # 0.26 M, experts 604.0 M
    layer = jax.eval_shape(
        lambda k: tfm.init_layer_params(k, full, jnp.bfloat16),
        jax.random.PRNGKey(0))
    sizes = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
             for k, v in layer["attention"].items()}
    assert sizes["indexer"] == 2048 * (1024 + 64 + 16) + 128
    assert sizes["query_key_value"] + sizes["dense"] == 18_874_368
    assert sizes["q_norm"] == sizes["k_norm"] == 128
    mlp = sum(x.size for x in jax.tree_util.tree_leaves(layer["mlp"]))
    assert mlp == 128 * 3 * 2048 * 768 + 2048 * 128


def test_per_head_qk_norm_is_not_the_whole_projection_norm(family):
    """On the same weights the whole-projection form (OLMoE's) moves the
    logits by hundreds of tolerances: the comparison tells them apart."""
    model, params, ref, weights, cfg = family
    toks = tokens(24)
    want = np.asarray(ref.forward_logits(weights, cfg, toks))
    whole = np.asarray(ref.forward_logits(weights, cfg, toks,
                                          faults={"whole_qk_norm"}))
    assert np.abs(whole - want).max() > 100 * LOGIT_TOL
