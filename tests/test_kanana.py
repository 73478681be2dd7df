"""kanana-2 (``model_type`` ``deepseek_v3``: latent attention over a pool
of latents, a sigmoid router with a choice bias and a scale, a shared
MLP, a leading dense layer), against the benchmark's plain reference.

Seeded random weights, CPU, float32 on both sides, small size: 3 layers
(one dense, two sparse), hidden 128, 4 heads of 16 + 8 (values of 16)
over a latent of 32 (narrower than the heads' 4 x 32 of keys and
values), 8 experts of 64 at 3 a token and one shared expert, contexts
of 5 to 156 tokens over pages of 8 and chunks of 16.  The reference is
the file the benchmark's probe loads (``benchmarks/reference/kanana.py``:
the EXPANDED form only), loaded here by path; the engine's decode step
and its dense fallback attend in the absorbed form, its chunk on the
kernel path in the expanded one, inside the kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import BS, is_greedy, kernels, serve, tokens
from megatron_llm_tpu.models import moe
from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.kanana import KananaModel, kanana_config
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.pallas import paged_attention as pa

# float32 on both sides, the same mathematics summed in another order
# (and in another FORM: absorbed against expanded); every named fault
# moves the logits by whole tenths
LOGIT_TOL = _family.FAMILIES["kanana"].tol
FAULTS = ("softmax_router", "bias_left_out", "bias_in_gates", "no_scale",
          "no_shared", "dense_layer_sparse", "no_latent_norm",
          "scale_sqrt_nope", "rope_key_per_head", "rope_whole_head",
          "float8")


@pytest.fixture(scope="module")
def family():
    return _family.built("kanana")


@pytest.mark.parametrize("n", [5, 16, 17, 70])
def test_full_forward_matches_the_reference(n):
    """The program's plain (cache-less) forward, the EXPANDED form, the
    dense layer before a scan over the sparse ones: logits at every
    position against the reference."""
    _family.full_forward_is_the_references("kanana", n)


@pytest.mark.parametrize("prompt,new,kernel", [
    (5, 14, "off"), (64, 10, "off"), (150, 6, "off"), (45, 5, "on")])
def test_the_engine_over_the_latent_pool_matches_one_full_forward(
        engines, prompt, new, kernel):
    """Chunked prefill then decode through the engine's own programs over
    the latent pool, the ABSORBED form, against the reference's ONE full
    forward in the expanded form: contexts of a page to twenty pages and
    one to ten chunks, through the dense gather and (``on``) through the
    walk's kernels in interpret mode.  The engine is the module's and its
    prefix cache is on: a seed a prompt, or a later case would adopt an
    earlier one's pages and compute fewer chunks than it counts."""
    _family.chunked_prefill_then_decode_is_one_forward(
        engines, "kanana", prompt, new, kernel, seed=prompt)


def test_absorbed_and_expanded_agree_on_one_layer(family):
    """ONE function in two forms: a layer's attention over a chunk
    through the latent pool (the up-projection folded into the queries
    and applied to the output) and with no cache (every latent
    expanded), to 1e-5 in float32."""
    model, params = family[:2]
    cfg = model.cfg
    p = jax.tree_util.tree_map(lambda a: a[1],
                               params["transformer"]["layers"]["attention"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, cfg.hidden_size))
    kw = dict(freqs=None, attention_mask=None, position_ids=None,
              dropout_key=None, train=False)
    expanded = tfm.attention(x, p, cfg, **kw)
    pools = paged_kv.init_pools(cfg, 8, BS)
    cache = paged_kv.step_caches(
        pools[:1], jnp.arange(1, 7, dtype=jnp.int32)[None],
        jnp.zeros(1, jnp.int32), jnp.full(1, 40, jnp.int32), "xla")[0]
    absorbed, after = tfm.attention(x, p, cfg, kv_cache=cache, **kw)
    assert np.abs(np.asarray(expanded)).max() > 0.1
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5, rtol=0)
    # what a token left in the pool: its normed latent, its rotary key,
    # zeros up to the lanes
    row = np.asarray(after.pool["latent_pages"][1, 0])
    assert row.shape == (128,) and np.abs(row[:40]).min() > 0
    assert (row[40:] == 0).all()


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(fault):
    _family.a_named_fault_is_told("kanana", fault, beyond=8)


def test_the_drawn_bias_turns_more_than_one_choice_in_ten(family):
    """A fresh model's choice bias is drawn wide enough that leaving it
    out turns a choice for more than one token in ten, a layer."""
    model, params, ref, weights, cfg = family
    toks = tokens(150, seed=9)
    with_bias, without = [], []
    ref.forward_logits(weights, cfg, toks, routing=with_bias)
    ref.forward_logits(weights, cfg, toks, routing=without,
                       faults={"bias_left_out"})
    assert len(with_bias) == model.cfg.num_sparse_layers == 2
    # at the first sparse layer the two runs route the same inputs
    turned = (np.sort(with_bias[0][0], axis=1)
              != np.sort(without[0][0], axis=1)).any(axis=1)
    assert turned.mean() > 0.1, turned.mean()


def test_a_slot_is_reused_after_a_long_request(engines):
    """A request of 150 + 6 tokens, then a short one in the same slot:
    the second answers as the plain forward does, over pages the first
    filled with other latents."""
    _family.a_slot_is_reused(engines, "kanana", prefix_cache=False,
                             **kernels("off"))


def test_page_programs_carry_a_latent_page(family):
    """A page of the pool is a page of its one array: copy-on-write and
    the fetch / load pair move a token's latent and rotary key."""
    cfg = family[0].cfg
    pools = paged_kv.init_pools(cfg, 6, BS)
    key = jax.random.PRNGKey(0)
    pools = jax.tree_util.tree_map(
        lambda a: jax.random.normal(key, a.shape, a.dtype), pools)
    copied = paged_kv.copy_page(pools, 2, 4)
    loaded = paged_kv.load_page(pools, paged_kv.fetch_page(pools, 2), 5)
    for layer in range(cfg.num_layers):
        src = np.asarray(pools[layer]["latent_pages"][2])
        assert np.abs(src).max() > 0
        assert (np.asarray(copied[layer]["latent_pages"][4]) == src).all()
        assert (np.asarray(loaded[layer]["latent_pages"][5]) == src).all()


def test_a_prefix_is_adopted_and_a_shared_page_copied_on_write(family,
                                                               engines):
    """The prefix cache carries over unchanged: a second request with the
    first one's prompt adopts its latent pages and answers alike; a
    third that shares all but its last token writes into a shared page's
    copy (copy-on-write) and answers as the plain forward does."""
    eng = engines.fresh("kanana", max_model_len=96)
    prompt = tokens(41, seed=11)
    first = list(serve(eng, prompt, 6).out_tokens)
    assert list(serve(eng, prompt, 6).out_tokens) == first
    stats = eng.stats()
    assert stats["prefill_tokens_cached"] >= 32
    other = prompt[:40] + [(prompt[40] + 1) % 500 + 1]
    assert is_greedy(*family[:2], other, serve(eng, other, 4).out_tokens)
    assert eng.stats()["prefill_tokens_cached"] > stats[
        "prefill_tokens_cached"]


def test_a_leading_dense_layer_scans_serves_and_counts_sparse_layers_only(
        family, engines):
    """The stack keeps the dense layer's parameters apart; the plain
    forward scans the SPARSE layers (one scan of L - 1 steps); the
    engine's routing record and counters have a row a sparse layer."""
    model, params = family[:2]
    cfg = model.cfg
    stack = params["transformer"]
    assert set(stack) == {"dense_layers", "layers", "final_norm"}
    assert "experts" not in stack["dense_layers"]["mlp"]
    assert stack["dense_layers"]["mlp"]["dense_h_to_4h"]["kernel"].shape == (
        1, cfg.hidden_size, 2 * cfg.ffn_hidden_size)
    assert stack["layers"]["mlp"]["experts"]["w_in"].shape[:2] == (2, 8)
    assert stack["layers"]["mlp"]["shared"]["dense_h_to_4h"][
        "kernel"].shape == (2, cfg.hidden_size, 2 * cfg.expert_hidden_size)
    jaxpr = jax.make_jaxpr(lambda p, t: model(p, t, train=False))(
        params, jnp.zeros((1, 8), jnp.int32))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 2
    specs = model.param_specs(params)
    assert (jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params))
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(s, tuple))))
    eng = engines("kanana", prefix_cache=False, **kernels("off"))
    since = _family.counted(eng)
    serve(eng, tokens(20, seed=4), 3)
    stats, records = since()
    for r in records:
        assert r.moe_expert_slots == 2 * cfg.num_experts
    # 20 prompt tokens and 2 decode steps, 3 experts a token, 2 layers
    assert stats["moe_assignments"] == (20 + 2) * 3 * 2


@pytest.mark.parametrize("kernel", ["off", "on"])
def test_the_engine_counts_latent_attentions_keys_and_pairs(family, engines,
                                                            kernel):
    """``mla_keys_live`` on a decode launch's record (each live row's
    context and itself), ``mla_pairs`` on a chunk's (for each live query
    the keys it sees), ``mla_latents_expanded`` on a chunk's that the
    expanded kernel reads (its context, history and chunk; 0 through the
    dense fallback, which is absorbed, and on every decode launch), all
    summed over the layers, from the arrays the host hands the program."""
    model, params = family[:2]
    L = model.cfg.num_layers
    eng = engines("kanana", prefix_cache=False, **kernels(kernel))
    assert pa.kernel_available() == (kernel == "on")
    since = _family.counted(eng)
    serve(eng, tokens(20, seed=4), 4)
    stats, records = since()
    chunks = [r for r in records if r.kind == "prefill"]
    steps = [r for r in records if r.kind == "decode"]
    assert [r.mla_pairs for r in chunks] == [
        L * sum(range(1, 17)), L * sum(range(17, 21))]
    assert [r.mla_keys_live for r in steps] == [L * 21, L * 22, L * 23]
    assert all(r.mla_keys_live == 0 for r in chunks)
    assert all(r.mla_pairs == 0 for r in steps)
    expanded = [L * 16, L * 20] if kernel == "on" else [0, 0]
    assert [r.mla_latents_expanded for r in chunks] == expanded
    assert all(r.mla_latents_expanded == 0 for r in steps)
    assert stats["mla_pairs"] == L * sum(range(1, 21))
    assert stats["mla_keys_live"] == L * 66
    assert stats["mla_latents_expanded"] == sum(expanded)
    assert chunks[1].as_dict()["mla_latents_expanded"] == expanded[1]
    assert "mla_pairs" in chunks[0].as_dict()


def test_a_model_without_a_latent_pool_expands_nothing(monkeypatch):
    """The kernel path of a model with per-head keys and values: the
    three latent counters stay 0 on every record and in ``stats()``."""
    from megatron_llm_tpu.models.llama import LlamaModel, llama_config

    monkeypatch.setattr(pa, "_INTERPRET", True)
    model = LlamaModel(llama_config(
        "tiny", num_layers=2, hidden_size=64, num_attention_heads=4,
        seq_length=64, max_position_embeddings=64, padded_vocab_size=512,
        use_flash_attn=False))
    eng = _family.engine(model, model.init(jax.random.PRNGKey(0)),
                         prefill_chunk=16, max_model_len=64)
    assert eng.prefill_kernel == "pallas"
    serve(eng, tokens(20, seed=4), 3)
    assert {f: eng.stats()[f] for f in
            ("mla_keys_live", "mla_pairs", "mla_latents_expanded")} == {
        "mla_keys_live": 0, "mla_pairs": 0, "mla_latents_expanded": 0}
    assert all(r.mla_latents_expanded == 0
               for r in eng.loop_profiler.records())


def test_a_chunk_of_several_rows_over_adopted_pages_is_the_plain_forward(
        family, engines):
    """The expanded kernel in the engine's own chunk program, over a
    context it did not write in this request: a second request adopts
    the first one's latent pages (32 tokens of a shared prefix) and its
    chunks, of 16 and of 7 live rows, start on top of them; the logits
    of each chunk's last row are the cache-less forward's."""
    model, params, ref, weights, cfg = family
    eng = engines.fresh("kanana", max_model_len=96, **kernels("on"))
    shared = tokens(36, seed=13)
    serve(eng, shared + tokens(5, seed=14), 2)
    got = engines.tapped(eng)
    toks = shared + tokens(19, seed=15)
    before = eng.stats()["mla_latents_expanded"]
    req = serve(eng, toks, 3)
    assert req.cached_prompt_tokens == 32
    chunks = [r for r in eng.loop_profiler.records()
              if r.kind == "prefill" and r.requests == (req.id,)]
    assert [(r.start, r.valid) for r in chunks] == [(32, 16), (48, 7)]
    L = model.cfg.num_layers
    assert eng.stats()["mla_latents_expanded"] - before == L * (48 + 55)
    seq = toks + list(req.out_tokens)
    want = np.asarray(ref.forward_logits(weights, cfg, seq))
    rows = sorted(got)
    assert rows[:2] == [47, 54]
    np.testing.assert_allclose(np.stack([got[t] for t in rows]), want[rows],
                               atol=LOGIT_TOL, rtol=0)


def test_a_token_holds_at_most_1280_bytes_a_layer_at_the_published_widths():
    full = kanana_config("30B-A3B", num_layers=8)
    pools = jax.eval_shape(lambda: paged_kv.init_pools(
        full, 12289, 16, dtype=jnp.bfloat16))
    assert [tuple(p) for p in pools] == [("latent_pages",)] * 8
    assert pools[0]["latent_pages"].shape == (12289, 16, 640)
    assert paged_kv.block_bytes(pools) == 8 * 16 * 1280
    # 32 heads of keys of 192 and values of 128 would hold 16 times that
    assert 32 * (192 + 128) * 2 == 16 * 1280


def test_what_a_latent_pool_does_not_support_is_refused_by_name(
        family, engines, monkeypatch):
    model, params = family[:2]
    with pytest.raises(ValueError, match="int8 KV pool"):
        paged_kv.init_pools(model.cfg, 4, BS, quantized=True)
    for kw, what in ((dict(int8_kv_cache=True), "int8 KV pool"),
                     (dict(speculative=True, draft_k=2), "speculative"),
                     (dict(host_cache_bytes=1 << 20), "host KV tier")):
        with pytest.raises(ValueError, match=what):
            engines.fresh("kanana", max_model_len=32, **kw)
    # a compressed query is built since PR 61 (models/glm5.py); what is
    # refused is one with no latent behind it
    assert kanana_config("tiny", q_lora_rank=64).q_lora_rank == 64
    with pytest.raises(ValueError, match="q_lora_rank"):
        kanana_config("tiny", kv_lora_rank=None, q_lora_rank=64)
    with pytest.raises(ValueError, match="group-limited"):
        kanana_config("tiny", moe_n_group=2)
    with pytest.raises(ValueError, match="sliding window"):
        kanana_config("tiny", sliding_window_size=16)
    with pytest.raises(ValueError, match="softmax|sigmoid"):
        kanana_config("tiny", moe_score_function="tanh")
    p = jax.tree_util.tree_map(lambda a: a[0],
                               params["transformer"]["layers"]["attention"])
    with pytest.raises(NotImplementedError, match="legacy decode caches"):
        tfm.attention(jnp.zeros((1, 1, 128)), p, model.cfg, freqs=None,
                      attention_mask=None, position_ids=None,
                      dropout_key=None, train=False,
                      kv_cache={"k": None, "v": None, "index": 0})
    from megatron_llm_tpu.models import gpt

    monkeypatch.setattr(gpt, "_vocab_unsharded", lambda: False)
    with pytest.raises(ValueError, match="tensor or pipeline"):
        KananaModel(kanana_config("tiny"))


def test_the_family_wrapper_asserts_its_flags():
    cfg = kanana_config("tiny")
    for bad in (dict(norm_topk_prob=False), dict(kv_lora_rank=None),
                dict(moe_score_function="softmax"),
                dict(moe_choice_bias=False), dict(moe_shared_experts=0)):
        with pytest.raises(AssertionError):
            KananaModel(cfg.replace(**bad))
    full = kanana_config("30B-A3B")
    assert (full.num_layers, full.hidden_size, full.num_attention_heads,
            full.num_attention_heads_kv) == (48, 2048, 32, 32)
    assert (full.kv_lora_rank, full.qk_nope_head_dim, full.qk_rope_head_dim,
            full.qk_head_dim, full.v_head_dim) == (512, 128, 64, 192, 128)
    assert (full.num_experts, full.moe_top_k, full.expert_hidden_size,
            full.ffn_hidden_size, full.moe_shared_experts,
            full.moe_first_dense_layers) == (128, 6, 768, 6144, 2, 1)
    assert (full.moe_routed_scale, full.moe_score_function,
            full.rope_theta) == (2.448, "sigmoid", 1e6)
    assert full.padded_vocab_size == 128256
    # a layer's parameters: attention 26.35 M; the dense MLP 37.75 M; a
    # sparse MLP's router 0.26 M (and 128 of bias), shared 9.44 M,
    # experts 604.0 M
    dense, sparse = (jax.eval_shape(
        lambda k, s=s: tfm.init_layer_params(k, full, jnp.bfloat16,
                                             sparse=s),
        jax.random.PRNGKey(0)) for s in (False, True))

    def size(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    assert size(dense["attention"]) == size(sparse["attention"]) == (
        2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048)
    assert size(dense["mlp"]) == 3 * 2048 * 6144
    assert size(sparse["mlp"]["router"]) == 2048 * 128 + 128
    assert size(sparse["mlp"]["shared"]) == 3 * 2048 * 1536
    assert size(sparse["mlp"]["experts"]) == 128 * 3 * 2048 * 768
    assert moe._CHOICE_BIAS_STD > 0
