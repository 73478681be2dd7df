"""GLM-5 (``model_type`` ``glm_moe_dsa``: latent attention behind a
compressed query, an indexer that reads the compressed query and chooses
each query's LATENT rows, a pool of two arrays a layer, Kanana's router
form over a share of the experts), against the benchmark's plain
reference.

Seeded random weights, CPU, float32 on both sides, small size: 3 layers
(one dense, two sparse), hidden 128, a compressed query of 48, 4 heads of
16 + 8 (values of 24) over a latent of 32, an indexer of 4 heads of 16 of
which the first 8 dimensions rotate and the other 8 pass, top-k 8 (under
every test's context), 4 of the router's 8 experts held (experts 2-5) at
3 a token and one shared expert, contexts of 5 to 156 tokens over pages
of 8 and chunks of 16.  The reference is the file the benchmark's probe
loads (``benchmarks/reference/glm5.py``: the EXPANDED form and
``jax.lax.top_k``), loaded here by path; the engine's decode step and its
dense fallback attend in the absorbed form, its chunk on the kernel path
in the expanded one, inside the kernel, all under ``ops/dsa.py``'s exact
choice.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import BS, is_greedy, kernels, serve, tokens
from megatron_llm_tpu.models import moe
from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.glm5 import Glm5Model, glm5_config
from megatron_llm_tpu.ops import dsa, paged_kv
from megatron_llm_tpu.ops.pallas import dsa_attention
from megatron_llm_tpu.ops.pallas import paged_attention as pa

LOGIT_TOL = _family.FAMILIES["glm5"].tol
# glm5.py's faults of the mathematics (the two of the precision are the
# chip's: against float32 any rounding fails)
FAULTS = ("dense", "topk_half", "unweighted", "index_query_from_input",
          "no_query_norm", "index_no_rope", "index_rope_whole",
          "no_latent_norm", "bias_in_gates", "bias_left_out", "no_scale",
          "no_shared", "float8")


@pytest.fixture(scope="module")
def family():
    return _family.built("glm5")


@pytest.mark.parametrize("n", [5, 16, 17, 70])
def test_full_forward_matches_the_reference(n):
    """The program's plain (cache-less) forward, the EXPANDED form under
    ``ops/dsa.py``'s mask, the dense layer before a scan over the sparse
    ones: logits at every position against the reference."""
    _family.full_forward_is_the_references("glm5", n)


@pytest.mark.parametrize("prompt,new,kernel", [
    (5, 14, "off"), (64, 10, "off"), (150, 6, "off"), (45, 5, "on")])
def test_the_engine_over_the_two_array_pool_matches_one_full_forward(
        engines, prompt, new, kernel):
    """Chunked prefill then decode through the engine's own programs over
    the pool of latent rows and indexer keys against the reference's ONE
    full forward: contexts of a page to twenty pages and one to ten
    chunks, 8 of up to 155 rows chosen at every compared position,
    through the dense gather (absorbed) and (``on``) through the scores,
    the choice and both latent walks under the mask, in interpret mode.
    The engine is the module's and its prefix cache is on: a seed a
    prompt."""
    eng, since, _ = _family.chunked_prefill_then_decode_is_one_forward(
        engines, "glm5", prompt, new, kernel, seed=prompt)
    stats, records = since()
    L, topk = eng.model.cfg.num_layers, eng.model.cfg.dsa_topk
    # the launch records count a latent model's selection as Keye's: for
    # each live query the keys it sees, and the same cut at the top-k
    sees = list(range(1, prompt + new))
    assert stats["dsa_keys_live"] == L * sum(sees)
    assert stats["dsa_keys_selected"] == L * sum(min(s, topk) for s in sees)
    assert stats["mla_pairs"] == L * sum(sees[:prompt])
    assert stats["mla_keys_live"] == L * sum(sees[prompt:])
    expanded = [r.mla_latents_expanded for r in records
                if r.kind == "prefill"]
    assert all(expanded) == (kernel == "on")


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(fault):
    _family.a_named_fault_is_told("glm5", fault, beyond=8)


def test_a_slot_is_reused_after_a_long_request(engines):
    """A request of 150 + 6 tokens, then a short one in the same slot:
    the second answers as the plain forward does, over pages the first
    filled with other latents and other indexer keys."""
    _family.a_slot_is_reused(engines, "glm5", prefix_cache=False,
                             **kernels("off"))


def test_absorbed_under_the_choice_and_expanded_agree_on_one_layer(family):
    """ONE function in two forms, both under the choice: a layer's
    attention over a chunk through the pool (the dense path: absorbed,
    every row gathered and masked) and with no cache (every latent
    expanded, ``causal_selected_attention``), to 1e-5 in float32; and
    what a token leaves in the pool's two arrays."""
    model, params = family[:2]
    cfg = model.cfg
    p = jax.tree_util.tree_map(lambda a: a[1],
                               params["transformer"]["layers"]["attention"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 40, cfg.hidden_size))
    kw = dict(freqs=None, attention_mask=None, position_ids=None,
              dropout_key=None, train=False)
    expanded = tfm.attention(x, p, cfg, **kw)
    pools = paged_kv.init_pools(cfg, 8, BS)
    assert sorted(pools[0]) == ["index_pages", "latent_pages"]
    cache = paged_kv.step_caches(
        pools[:1], jnp.arange(1, 7, dtype=jnp.int32)[None],
        jnp.zeros(1, jnp.int32), jnp.full(1, 40, jnp.int32), "xla")[0]
    absorbed, after = tfm.attention(x, p, cfg, kv_cache=cache, **kw)
    assert np.abs(np.asarray(expanded)).max() > 0.1
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5, rtol=0)
    row = np.asarray(after.pool["latent_pages"][1, 0])
    assert row.shape == (128,) and np.abs(row[:40]).min() > 0
    assert (row[40:] == 0).all()
    key = np.asarray(after.pool["index_pages"][1, 0])
    assert key.shape == (128,) and np.abs(key[:16]).min() > 0
    assert (key[16:] == 0).all()
    # and it is not dense attention: 8 of up to 40 rows are attended
    dense = tfm.attention(x, {k: v for k, v in p.items() if k != "indexer"},
                          cfg.replace(dsa_index_heads=0), **kw)
    assert np.abs(np.asarray(dense) - np.asarray(expanded))[0, 20:].max() > 0.05


def test_the_indexer_reads_the_compressed_query_and_half_its_head_passes(
        family):
    """The indexer's queries are a projection of what they are GIVEN (the
    compressed query: another input gives other queries, the layer's
    input alone decides the key and the weights), and of a head's 16
    dimensions the first 8 rotate and the other 8 are the projection's
    own, whatever the position; Keye's whole head rotates through the
    same code."""
    model, params = family[:2]
    cfg = model.cfg
    ix = jax.tree_util.tree_map(
        lambda a: a[0], params["transformer"]["layers"]["attention"]["indexer"])
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (1, 12, cfg.hidden_size))
    c_q = jax.random.normal(jax.random.fold_in(key, 1),
                            (1, 12, cfg.q_lora_rank))
    pos = jnp.arange(5, 17)[None]
    iq, ik, iw, topk = tfm.indexer_projections(x, ix, cfg, pos,
                                               query_input=c_q)
    assert topk == cfg.dsa_topk and iq.shape == (1, 12, 4, 16)
    plain = (c_q @ ix["query"]["kernel"]).reshape(1, 12, 4, 16)
    np.testing.assert_allclose(iq[..., 8:], plain[..., 8:], atol=1e-6)
    assert np.abs(np.asarray(iq[..., :8] - plain[..., :8])).max() > 0.05
    # a rotation: the rotated half keeps its length
    np.testing.assert_allclose(np.linalg.norm(iq[..., :8], axis=-1),
                               np.linalg.norm(plain[..., :8], axis=-1),
                               rtol=1e-5)
    other = tfm.indexer_projections(x, ix, cfg, pos, query_input=2 * c_q)
    np.testing.assert_allclose(other[0], 2 * iq, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(other[1], ik)
    np.testing.assert_array_equal(other[2], iw)
    # the key's second half is its LayerNorm's own; with no
    # ``dsa_index_rope_dim`` (Keye's) the whole head turns
    normed = tfm.layer_norm(x @ ix["key"]["kernel"], ix["key_norm"]["scale"],
                            ix["key_norm"]["bias"],
                            eps=cfg.layernorm_epsilon)
    np.testing.assert_allclose(ik[..., 8:], normed[..., 8:], atol=1e-6)
    whole = tfm.indexer_projections(
        x, ix, cfg.replace(dsa_index_rope_dim=None), pos, query_input=c_q)
    assert np.abs(np.asarray(whole[1][..., 8:] - ik[..., 8:])).max() > 0.05


def test_the_four_shares_add_up_to_the_uncut_layer(family):
    """8 experts in 4 shares of 2: the four shares' routed parts plus the
    shared expert ONCE equal the uncut reference's layer, and every
    share's histogram is the ROUTER's, over all 8."""
    model, params, ref, weights, cfg = family
    mcfg = model.cfg.replace(num_experts=2, moe_router_experts=8)
    mlp = jax.tree_util.tree_map(lambda a: a[0],
                                 params["transformer"]["layers"]["mlp"])
    key = jax.random.PRNGKey(11)
    # all 8 experts of the uncut layer (the tree holds 4 of them)
    w_in = 0.3 * jax.random.normal(
        key, (8,) + mlp["experts"]["w_in"].shape[1:])
    w_out = 0.3 * jax.random.normal(
        jax.random.fold_in(key, 1), (8,) + mlp["experts"]["w_out"].shape[1:])
    x = jax.random.normal(jax.random.fold_in(key, 2), (1, 24, 128))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))

    def share(first):
        p = {"router": mlp["router"],
             "experts": {"w_in": w_in[first:first + 2],
                         "w_out": w_out[first:first + 2]}}
        if first == 0:
            p["shared"] = mlp["shared"]
        out, _, counts = moe.moe_mlp_dropless(x, p, mcfg.replace(
            moe_experts_first=first, moe_shared_experts=int(first == 0)))
        return out[0], counts

    parts = [share(first) for first in (0, 2, 4, 6)]
    for _, counts in parts[1:]:
        np.testing.assert_array_equal(counts, parts[0][1])
    assert parts[0][1].shape == (8,)
    assert int(parts[0][1].sum()) == 24 * mcfg.moe_top_k

    class Whole:
        def expert(self, i, e):
            f = w_in.shape[2] // 2
            return {"w1": w_in[e][:, :f], "w3": w_in[e][:, f:],
                    "w2": w_out[e]}

    # the reference's own norm over rows of unit mean square, scale one
    w = {**weights.layer(1), "ffn_norm": jnp.ones((128,))}
    want = ref.moe_out(x[0], w, Whole(), {**cfg, "rms_norm_eps": 0.0}, 1, {},
                       frozenset(), held=range(8))[0]
    assert float(jnp.std(want)) > 0.05
    # values of tens: float32's own sums in another order
    np.testing.assert_allclose(sum(out for out, _ in parts), want,
                               atol=2e-5, rtol=1e-5)
    # and one share alone is not the layer
    assert np.abs(np.asarray(parts[0][0] - want)).max() > 0.05


def test_page_programs_carry_a_latent_row_and_its_indexer_key(family):
    """A page of the pool is a page of BOTH arrays: copy-on-write and the
    fetch / load pair move a token's latent row and its indexer key
    together."""
    cfg = family[0].cfg
    pools = paged_kv.init_pools(cfg, 6, BS)
    key = jax.random.PRNGKey(0)
    pools = jax.tree_util.tree_map(
        lambda a: jax.random.normal(key, a.shape, a.dtype), pools)
    copied = paged_kv.copy_page(pools, 2, 4)
    loaded = paged_kv.load_page(pools, paged_kv.fetch_page(pools, 2), 5)
    for layer in range(cfg.num_layers):
        for name in ("latent_pages", "index_pages"):
            src = np.asarray(pools[layer][name][2])
            assert np.abs(src).max() > 0
            assert (np.asarray(copied[layer][name][4]) == src).all()
            assert (np.asarray(loaded[layer][name][5]) == src).all()
    assert paged_kv.block_bytes(pools) == cfg.num_layers * BS * (128 + 128) * 4


def test_a_prefix_is_adopted_and_a_shared_page_copied_on_write(family,
                                                               engines):
    """The prefix cache carries both arrays: a second request with the
    first one's prompt adopts its pages (latent rows AND indexer keys: a
    query over adopted pages scores keys it never wrote) and answers
    alike; a third that shares all but its last token writes into a
    shared page's copy (copy-on-write) and answers as the plain forward
    does, which it could not over a page whose indexer keys stayed
    behind."""
    eng = engines.fresh("glm5", max_model_len=96)
    prompt = tokens(41, seed=11)
    first = list(serve(eng, prompt, 6).out_tokens)
    assert list(serve(eng, prompt, 6).out_tokens) == first
    stats = eng.stats()
    assert stats["prefill_tokens_cached"] >= 32
    other = prompt[:40] + [(prompt[40] + 1) % 500 + 1]
    assert is_greedy(*family[:2], other, serve(eng, other, 4).out_tokens)
    assert eng.stats()["prefill_tokens_cached"] > stats[
        "prefill_tokens_cached"]
    assert first == list(np.asarray(family[0](
        family[1], jnp.asarray([prompt + first[:-1]], jnp.int32),
        train=False)[0][40:].argmax(-1)))


def test_a_chunk_of_several_rows_over_adopted_pages_is_the_reference(
        family, engines):
    """The masked expanded kernel in the engine's own chunk program, over
    a context it did not write in this request: a second request adopts
    the first one's pages (32 tokens of a shared prefix) and its chunks,
    of 16 and of 7 live rows, score, choose and attend on top of them."""
    model, params, ref, weights, cfg = family
    eng = engines.fresh("glm5", max_model_len=96, **kernels("on"))
    shared = tokens(36, seed=13)
    serve(eng, shared + tokens(5, seed=14), 2)
    got = engines.tapped(eng)
    toks = shared + tokens(19, seed=15)
    req = serve(eng, toks, 3)
    assert req.cached_prompt_tokens == 32
    chunks = [r for r in eng.loop_profiler.records()
              if r.kind == "prefill" and r.requests == (req.id,)]
    assert [(r.start, r.valid) for r in chunks] == [(32, 16), (48, 7)]
    seq = toks + list(req.out_tokens)
    want = np.asarray(ref.forward_logits(weights, cfg, seq))
    rows = sorted(got)
    assert rows[:2] == [47, 54]
    np.testing.assert_allclose(np.stack([got[t] for t in rows]), want[rows],
                               atol=LOGIT_TOL, rtol=0)


# ---------------------------------------------------------------------------
# the kernels alone (interpret mode) against ops/dsa.py over the gathered
# rows and against dense_latent_attention
# ---------------------------------------------------------------------------

def _latent_case(rng, S, n, M, ctx, valid, nh=4, r=32, dr=8, dn=16, dv=24,
                 hi=4, ties=False):
    """A pool of latent rows (128 wide: a latent of ``r``, a rotary key of
    ``dr``, zeros) and indexer keys with every row's context scattered
    through ragged tables, and this call's queries in both forms."""
    P, W = 1 + S * M, 128
    pages = np.zeros((P, BS, W), np.float32)
    pages[..., :r + dr] = rng.standard_normal((P, BS, r + dr))
    draw = ((lambda *sh: rng.integers(-1, 2, sh).astype(np.float32))
            if ties else
            (lambda *sh: rng.standard_normal(sh).astype(np.float32)))
    ip = np.zeros((P, BS, 128), np.float32)
    ip[..., :16] = draw(P, BS, 16)
    iq = np.zeros((S, n, hi, 128), np.float32)
    iq[..., :16] = draw(S, n, hi, 16)
    iw = draw(S, n, hi)
    bt = np.zeros((S, M), np.int32)
    order = rng.permutation(np.arange(1, P))
    for s in range(S):
        live = -(-(ctx[s] + valid[s]) // BS)
        bt[s, :live] = order[s * M:s * M + live]
    q_nope = rng.standard_normal((S, n, nh, dn)).astype(np.float32)
    q_rope = rng.standard_normal((S, n, nh, dr)).astype(np.float32)
    kv_up = (0.3 * rng.standard_normal((r, nh, dn + dv))).astype(np.float32)
    out = [jnp.asarray(a) for a in (q_nope, q_rope, kv_up, iq, iw, pages, ip,
                                    bt)]
    return out + [jnp.asarray(ctx, jnp.int32), jnp.asarray(valid, jnp.int32)]


def _absorbed(q_nope, q_rope, kv_up, dn):
    """The absorbed queries at the pool's row width."""
    q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope, kv_up[..., :dn])
    return paged_kv._to_width(jnp.concatenate([q_lat, q_rope], axis=-1), 128)


def _both(case, topk, n, dn=16, r=32):
    """(the kernels, the dense path) per head [S, n, nh, dv]: the decode
    step absorbed, a chunk expanded in its kernel; the dense path
    absorbed, ``ops/dsa.py`` over every gathered row."""
    q_nope, q_rope, kv_up, iq, iw, pages, ip, bt, ctx, valid = case
    scale = 1.0 / math.sqrt(dn + q_rope.shape[-1])
    pool = {"latent_pages": pages, "index_pages": ip}
    index = (iq, None, iw, topk)
    dense = paged_kv.PagedKVCache(pool, bt, ctx, valid, kernel="xla")
    q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope, kv_up[..., :dn])
    want = dense._attend_latent_selected(q_lat, q_rope, pool, index, scale,
                                         r, None)
    want = jnp.einsum("bsnr,rnd->bsnd", want, kv_up[..., dn:])
    if n == 1:
        got = dsa_attention.paged_selected_latent_attention(
            _absorbed(q_nope, q_rope, kv_up, dn), None, None, iq, iw, pages,
            ip, bt, ctx, valid, topk=topk, softmax_scale=scale,
            value_width=r)
        got = jnp.einsum("bsnr,rnd->bsnd", got, kv_up[..., dn:])
    else:
        got = dsa_attention.paged_selected_latent_attention(
            q_nope, q_rope, kv_up, iq, iw, pages, ip, bt, ctx, valid,
            topk=topk, softmax_scale=scale, value_width=r)
    return np.asarray(got), np.asarray(want)


@pytest.fixture
def small_blocks(monkeypatch):
    """Interpret mode, and compute blocks of two 8-token pages, so that a
    few dozen rows cross block boundaries in both walks."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(pa, "_BLOCK_TOKENS", 2 * BS)
    assert dsa_attention.latent_block_keys(BS, 12) == 2 * BS


@pytest.mark.parametrize("topk", [8, 200])
@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("batch", ["ragged", "idle_between"])
def test_the_selected_latent_decode_step_matches_the_dense_path(
        topk, ties, batch, small_blocks):
    """``dsa_index_scores_decode``, ``dsa_select_decode`` and the shared
    walk over a latent pool under the mask (``mla_attention_sparse_
    decode``): rows of one to five blocks, an idle row between live ones,
    a top-k under and over every context, equal scores everywhere."""
    ctx, valid = {"ragged": ([0, 15, 16, 47, 70], [1, 1, 1, 1, 1]),
                  "idle_between": ([33, 9, 50, 0], [1, 0, 1, 0])}[batch]
    case = _latent_case(np.random.default_rng(7), len(ctx), 1, 12, ctx,
                        valid, ties=ties)
    got, want = _both(case, topk, 1)
    live = np.asarray(valid) > 0
    assert np.abs(want[live]).max() > 0.1
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=0)


@pytest.mark.parametrize("ctx,valid", [(0, 24), (0, 13), (16, 24), (37, 24),
                                       (40, 7), (56, 24)])
@pytest.mark.parametrize("ties", [False, True])
def test_the_selected_latent_chunk_matches_the_dense_path(ctx, valid, ties,
                                                          small_blocks):
    """``dsa_index_scores_prefill``, ``dsa_select_prefill`` and the
    latent chunk's own walk under the mask (``mla_attention_prefill_
    masked``: a block's slice of the mask rides with its pages): a chunk
    of 24 rows from an empty context and on top of one to four blocks,
    short last chunks, a first block in which a row's key 0 is not
    chosen."""
    case = _latent_case(np.random.default_rng(ctx + valid), 1, 24, 12, [ctx],
                        [valid], ties=ties)
    got, want = _both(case, 8, 24)
    assert np.abs(want[:, :valid]).max() > 0.1
    np.testing.assert_allclose(got[:, :valid], want[:, :valid], atol=2e-5,
                               rtol=0)


def test_selected_latent_chunks_of_several_slots(small_blocks):
    """Three slots a call, one of them idle: each slot's chunk reads its
    own table and its own mask, and the idle one walks nothing."""
    ctx, valid = [20, 0, 41], [24, 0, 10]
    case = _latent_case(np.random.default_rng(3), 3, 24, 12, ctx, valid)
    got, want = _both(case, 8, 24)
    for s, v in enumerate(valid):
        np.testing.assert_allclose(got[s, :v], want[s, :v], atol=2e-5,
                                   rtol=0)
    assert (got[1] == 0).all()


def test_selected_latent_attention_is_not_dense_latent_attention(
        small_blocks):
    """Under a top-k of 8 the step over 150 rows is NOT
    ``dense_latent_attention`` (every row attended), and over 5 rows, no
    more than the top-k, it is."""
    case = _latent_case(np.random.default_rng(5), 2, 1, 24, [5, 150], [1, 1])
    q_nope, q_rope, kv_up, iq, iw, pages, ip, bt, ctx, valid = case
    got, _ = _both(case, 8, 1)
    scale = 1.0 / math.sqrt(24)
    every = pa.dense_latent_attention(
        _absorbed(q_nope, q_rope, kv_up, 16), pages, bt, ctx, valid, scale,
        32)
    every = np.asarray(jnp.einsum("bsnr,rnd->bsnd", every, kv_up[..., 16:]))
    np.testing.assert_allclose(got[0], every[0], atol=2e-5, rtol=0)
    assert np.abs(got[1] - every[1]).max() > 0.05


def test_the_choice_is_ops_dsa_choose_over_the_latent_blocks(small_blocks):
    """The mask the latent walks take is ``ops/dsa.py::select_mask`` of
    ``index_scores`` over the gathered indexer keys, block by block."""
    ctx, valid = [37], [24]
    case = _latent_case(np.random.default_rng(9), 1, 24, 12, ctx, valid)
    _, _, _, iq, iw, _, ip, bt, ctx_, valid_ = case
    mask = dsa_attention._choice(iq, iw, ip, (bt, ctx_, valid_), 24, 2, 8)
    blocks = -(-(ctx[0] + valid[0]) // (2 * BS))
    got = np.moveaxis(np.asarray(mask)[0, :blocks], 0, 1).reshape(24, -1) == 0
    keys = ip[bt[0, :2 * blocks]].reshape(1, -1, 128)
    pos = ctx[0] + np.arange(24)
    seen = np.arange(keys.shape[1])[None, :] <= pos[:, None]
    want = dsa.select_mask(dsa.index_scores(iq, keys, iw)[0],
                           jnp.asarray(seen), 8)
    np.testing.assert_array_equal(got, np.asarray(want))
    assert (got.sum(axis=1) == 8).all()


def test_a_token_holds_1536_bytes_a_layer_at_the_published_widths():
    full = glm5_config("744B-A40B", num_layers=5, moe_first_dense_layers=1)
    pools = jax.eval_shape(lambda: paged_kv.init_pools(
        full, 20481, 16, dtype=jnp.bfloat16))
    assert [tuple(sorted(p)) for p in pools] == [
        ("index_pages", "latent_pages")] * 5
    assert pools[0]["latent_pages"].shape == (20481, 16, 640)
    assert pools[0]["index_pages"].shape == (20481, 16, 128)
    assert paged_kv.block_bytes(pools) == 5 * 16 * (1280 + 256)
    # 64 heads of keys and values of 256 would hold 42 times that
    assert 64 * (256 + 256) * 2 == 65536
    plan = paged_kv.plan(full, 16, 10, 4160, 512, "pallas", "pallas")
    assert plan.dsa_block_keys == 512 and plan.dsa_table_blocks == 130


def test_what_the_pair_does_not_support_is_refused_by_name(family, engines):
    model, params = family[:2]
    with pytest.raises(ValueError, match="int8 KV pool"):
        paged_kv.init_pools(model.cfg, 4, BS, quantized=True)
    for kw, what in ((dict(int8_kv_cache=True), "int8 KV pool"),
                     (dict(speculative=True, draft_k=2), "speculative"),
                     (dict(host_cache_bytes=1 << 20), "host KV tier")):
        with pytest.raises(ValueError, match=what):
            engines.fresh("glm5", max_model_len=32, **kw)
    with pytest.raises(NotImplementedError, match="selection over latents"):
        model(params, jnp.ones((1, 8), jnp.int32), train=True)
    with pytest.raises(ValueError, match="sliding window"):
        glm5_config("tiny", sliding_window_size=16)
    p = jax.tree_util.tree_map(lambda a: a[0],
                               params["transformer"]["layers"]["attention"])
    kw = dict(freqs=None, position_ids=None, dropout_key=None, train=False)
    with pytest.raises(NotImplementedError, match="legacy decode caches"):
        tfm.attention(jnp.zeros((1, 1, 128)), p, model.cfg,
                      attention_mask=None,
                      kv_cache={"k": None, "v": None, "index": 0}, **kw)
    with pytest.raises(NotImplementedError, match="explicit attention mask"):
        tfm.attention(jnp.zeros((1, 4, 128)), p, model.cfg,
                      attention_mask=jnp.ones((1, 1, 4, 4), bool), **kw)
    # a pool with indexer keys is attended through an indexer, and only
    # such a pool
    pools = paged_kv.init_pools(model.cfg, 4, BS)
    cache = paged_kv.step_caches(pools[:1], jnp.ones((1, 2), jnp.int32),
                                 jnp.zeros(1, jnp.int32),
                                 jnp.ones(1, jnp.int32), "xla")[0]
    with pytest.raises(ValueError, match="only such a pool"):
        cache.attend_latent(jnp.zeros((1, 1, 4, 32)), jnp.zeros((1, 1, 4, 8)),
                            jnp.zeros((1, 1, 32)), jnp.zeros((1, 1, 8)), 1.0)


def test_the_family_wrapper_asserts_its_flags():
    cfg = glm5_config("tiny")
    for bad in (dict(norm_topk_prob=False), dict(dsa_index_heads=0),
                dict(dsa_index_query="input"),
                dict(moe_score_function="softmax"),
                dict(moe_choice_bias=False), dict(moe_shared_experts=0)):
        with pytest.raises(AssertionError):
            Glm5Model(cfg.replace(**bad))
    full = glm5_config("744B-A40B")
    assert (full.num_layers, full.hidden_size, full.num_attention_heads,
            full.num_attention_heads_kv) == (78, 6144, 64, 64)
    assert (full.kv_lora_rank, full.q_lora_rank, full.qk_nope_head_dim,
            full.qk_rope_head_dim, full.qk_head_dim, full.v_head_dim) == (
                512, 2048, 192, 64, 256, 256)
    assert (full.dsa_index_heads, full.dsa_index_head_dim,
            full.dsa_index_rope_dim, full.dsa_topk,
            full.dsa_index_query) == (32, 128, 64, 2048, "compressed")
    assert (full.num_experts, full.moe_top_k, full.expert_hidden_size,
            full.ffn_hidden_size, full.moe_shared_experts,
            full.moe_first_dense_layers) == (256, 8, 2048, 12288, 1, 3)
    assert (full.moe_routed_scale, full.moe_score_function, full.rope_theta,
            full.layernorm_epsilon) == (2.5, "sigmoid", 1e6, 1e-5)
    assert full.padded_vocab_size == 154880
    # a layer's parameters as the issue reckons them: attention 165.02 M
    # (and its two norms' scales), the indexer 9.37 M, the dense MLP
    # 226.49 M; a sparse MLP's router 1.57 M (and 256 of bias), shared
    # 37.75 M, an expert 37.75 M
    held = full.replace(num_experts=16, moe_router_experts=256)
    dense, sparse = (jax.eval_shape(
        lambda k, s=s: tfm.init_layer_params(k, held, jnp.bfloat16,
                                             sparse=s),
        jax.random.PRNGKey(0)) for s in (False, True))

    def size(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))

    indexer = 2048 * 4096 + 6144 * 128 + 6144 * 32 + 2 * 128
    assert size(dense["attention"]["indexer"]) == indexer
    assert size(dense["attention"]) == size(sparse["attention"]) == (
        6144 * 2048 + 2048 + 2048 * 16384 + 6144 * 576 + 512
        + 512 * 64 * 448 + 16384 * 6144 + indexer)
    assert size(dense["mlp"]) == 3 * 6144 * 12288
    assert size(sparse["mlp"]["router"]) == 6144 * 256 + 256
    assert size(sparse["mlp"]["shared"]) == 3 * 6144 * 2048
    assert size(sparse["mlp"]["experts"]) == 16 * 3 * 6144 * 2048
