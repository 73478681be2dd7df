"""LFM2-8B-A1B (``model_type`` ``lfm2_moe``: gated short convolutions of
three taps beside rotating attention of 64-wide heads by a pattern that
does not repeat, two leading dense layers in a stack whose mixers are
stacked by kind, 32 experts under a sigmoid router whose gates are over
``sum + 1e-6``), against the benchmark's plain reference.

Seeded random weights, CPU, float32 on both sides, small size: 8 layers
(conv conv attention conv conv conv attention conv, the published
stack's first eight), the first two dense, hidden 128, 4 query and 2
key/value heads of 64 AS PUBLISHED (so the pool holds two heads a
128-lane row), 8 experts of 64 at 4 a token; contexts of 5 to 156 tokens
over pages of 8 and chunks of 32.  The reference is the file the
benchmark's probe loads (``benchmarks/reference/lfm2.py``: the
convolution as three shifted sums over the whole sequence, no chunk, no
cache), loaded here by path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import BS, kernels, serve, tokens
from megatron_llm_tpu.config import PositionEmbeddingType
from megatron_llm_tpu.models import moe
from megatron_llm_tpu.models.language_model import language_model_forward
from megatron_llm_tpu.models.lfm2 import lfm2_config
from megatron_llm_tpu.models.short_conv import (init_short_conv_params,
                                                short_conv_mixer)
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.pallas import paged_attention as pa
from megatron_llm_tpu.serving import SamplingParams

# float32 on both sides, the same mathematics summed in another order;
# the logits' deviation is some 0.5 and every named fault moves them by
# hundredths at least
LOGIT_TOL = _family.FAMILIES["lfm2"].tol
FAULTS = ("taps_reversed", "state_dropped_at_chunks", "bc_swapped",
          "conv_activation", "bias_in_gates", "no_qk_norm", "no_rope",
          "kv_neighbour", "dense_layer_sparse", "float8")
TINY = dict(use_flash_attn=False)


@pytest.fixture(scope="module")
def family():
    return _family.built("lfm2")


# --- the mixer alone --------------------------------------------------------

def _mixer(seed=0, hidden=64, **kw):
    cfg = lfm2_config("tiny", hidden_size=hidden, **TINY, **kw)
    p = init_short_conv_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    p = jax.tree_util.tree_map(lambda a: a * 4.0, p)
    return cfg, p


def _by_shifted_sums(h, p, cfg):
    """The mixer by hand for one sequence h [n, hidden]."""
    hidden, K = cfg.hidden_size, cfg.conv_taps
    bcx = h @ p["in_proj"]["kernel"]
    B, C, X = (bcx[:, i * hidden:(i + 1) * hidden] for i in range(3))
    z = B * X
    ext = jnp.concatenate([jnp.zeros((K - 1, hidden)), z])
    c = sum(ext[j:j + len(h)] * p["conv"]["kernel"][:, j] for j in range(K))
    if "bias" in p["conv"]:
        c = c + p["conv"]["bias"]
    return (C * c) @ p["out_proj"]["kernel"], z


@pytest.mark.parametrize("n,bias", [(1, False), (2, False), (37, False),
                                    (37, True)])
def test_a_chunk_from_zeros_is_three_shifted_sums(n, bias):
    cfg, p = _mixer(conv_mixer_bias=bias)
    assert ("bias" in p["conv"]) == bias
    h = jax.random.normal(jax.random.PRNGKey(2), (2, n, 64))
    got = short_conv_mixer(h, p, cfg)
    for b in range(2):
        np.testing.assert_allclose(got[b], _by_shifted_sums(h[b], p, cfg)[0],
                                   atol=1e-5, rtol=0)


def _cache(cfg, slots, ctx, valid, rows=None, pool=None):
    pool = pool or paged_kv.init_pools(cfg, 4, BS, num_slots=slots)[0]
    assert set(pool) == {"conv_state"}
    return paged_kv.PagedKVCache(
        pool, jnp.zeros((len(ctx), 1), jnp.int32), jnp.asarray(ctx),
        jnp.asarray(valid), kernel="xla", group=paged_kv.STATE,
        slots=None if rows is None else jnp.asarray(rows))


@pytest.mark.parametrize("first", [1, 2, 20])
def test_two_chunks_with_the_carried_columns_equal_one(first):
    cfg, p = _mixer()
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 37, 64))
    whole = short_conv_mixer(h, p, cfg)
    a, c = short_conv_mixer(h[:, :first], p, cfg,
                            kv_cache=_cache(cfg, 2, [0], [first], rows=[1]))
    assert int(c.context_lens[0]) == first
    b, c = short_conv_mixer(h[:, first:], p, cfg, kv_cache=dataclasses.replace(
        c, valid_lens=jnp.asarray([37 - first])))
    np.testing.assert_allclose(jnp.concatenate([a, b], axis=1), whole,
                               atol=1e-5, rtol=0)
    # the slot holds z at the last two tokens, its neighbours nothing
    z = _by_shifted_sums(h[0], p, cfg)[1]
    np.testing.assert_allclose(c.pool["conv_state"][1], z[-2:], atol=1e-5)
    assert not np.asarray(c.pool["conv_state"][0]).any()


def test_n_steps_equal_a_chunk():
    """The step form ([S, 1, h], row s is slot s) token after token, one
    row idle throughout, against the chunk."""
    cfg, p = _mixer()
    n = 9
    h = jax.random.normal(jax.random.PRNGKey(4), (1, n, 64))
    whole = short_conv_mixer(h, p, cfg)[0]
    cache = _cache(cfg, 3, [0, 0, 0], [1, 0, 1])
    outs = []
    for t in range(n):
        x = jnp.stack([h[0, t], jnp.ones((64,)), 2.0 * h[0, t]])[:, None]
        out, cache = short_conv_mixer(x, p, cfg, kv_cache=cache)
        outs.append(out[0, 0])
    np.testing.assert_allclose(jnp.stack(outs), whole, atol=1e-5, rtol=0)
    assert np.asarray(cache.context_lens).tolist() == [n, 0, n]
    # the idle row wrote nothing, into its slot or the garbage row's
    assert not np.asarray(cache.pool["conv_state"][1]).any()
    assert not np.asarray(cache.pool["conv_state"][3]).any()


def test_padding_rows_and_idle_rows_are_exact():
    """A chunk padded past its valid tokens carries the columns at its
    last VALID tokens; an idle row keeps what its slot held."""
    cfg, p = _mixer()
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 64))
    pool = paged_kv.init_pools(cfg, 4, BS, num_slots=2)[0]
    held = jax.random.normal(jax.random.PRNGKey(6), (2, 64))
    pool = {"conv_state": pool["conv_state"].at[1].set(held)}
    out, c = short_conv_mixer(
        h, p, cfg, kv_cache=_cache(cfg, 2, [0, 7], [11, 0], rows=[0, 1],
                                   pool=pool))
    want, z = _by_shifted_sums(h[0, :11], p, cfg)
    np.testing.assert_allclose(out[0, :11], want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(c.pool["conv_state"][0], z[-2:], atol=1e-5)
    np.testing.assert_array_equal(c.pool["conv_state"][1], held)
    assert np.asarray(c.context_lens).tolist() == [11, 7]
    # one valid token: the columns are [zeros, z_0]
    _, c = short_conv_mixer(
        h, p, cfg, kv_cache=_cache(cfg, 2, [0, 0], [1, 0], rows=[0, 1]))
    z = _by_shifted_sums(h[0, :1], p, cfg)[1]
    np.testing.assert_allclose(c.pool["conv_state"][0],
                               jnp.concatenate([jnp.zeros((1, 64)), z]),
                               atol=1e-5)


# --- the stack --------------------------------------------------------------

@pytest.mark.parametrize("n", [5, 17, 70])
def test_full_forward_matches_the_reference(n):
    """The program's plain (cache-less) forward: the two dense layers and
    the six sparse ones of ONE period that does not repeat, each layer's
    mixer taken by its index among its kind over the WHOLE depth: logits
    at every position against the reference."""
    _family.full_forward_is_the_references("lfm2", n)


@pytest.mark.parametrize("prompt,new,kernel", [
    (64, 10, "off"), (150, 6, "off"), (45, 5, "on")])
def test_the_engine_over_pool_and_state_group_matches_one_full_forward(
        engines, prompt, new, kernel):
    """Chunked prefill (chunks of 32, the last one padded) then decode
    through the engine's own programs, the columns carried in their slot
    across every chunk boundary and step and the keys of 64 two heads a
    row of the pool, against the reference's ONE forward: LOGITS at every
    chunk's last row and every step, and the columns each conv layer
    leaves in the slot; the attention layers through the dense gather
    and (``on``) through both walks' kernels in interpret mode."""
    _family.chunked_prefill_then_decode_is_one_forward(
        engines, "lfm2", prompt, new, kernel)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(fault):
    _family.a_named_fault_is_told("lfm2", fault)


def test_the_gates_are_over_their_sum_plus_epsilon(family):
    """``sum + 1e-6`` against the reference, where it shows: a router
    whose scores are tiny."""
    model, params, ref, weights, cfg = family
    mcfg = model.cfg.replace(moe_choice_bias=False)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (3, 5, 128))
    router = {"router": {"kernel": jnp.zeros((128, 8)).at[0].set(
        -1.0).at[:, 3].add(0.01)}}
    x = x.at[..., 0].set(40.0)          # every score sigmoid(-40): 4e-18
    _, probs, gates, idx = moe._route(x, router, mcfg)
    want = jnp.take_along_axis(probs, idx, -1)
    want = want / (want.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(gates, want, rtol=1e-6)
    assert float(gates.sum(-1).max()) < 1e-10      # not renormalised to 1
    older = moe._route(x, router, mcfg.replace(moe_gate_norm_added=False,
                                               moe_gate_norm_eps=None))[2]
    np.testing.assert_allclose(older.sum(-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("name,eps", [("kanana", 1e-20), ("nemotron_h", 1e-20),
                                      ("trinity", 1e-20), ("mixtral", 1e-9),
                                      ("granite", 1e-9)])
def test_the_older_routers_keep_their_normaliser(name, eps):
    import importlib

    cfg = getattr(importlib.import_module(
        "megatron_llm_tpu.models." + name), name + "_config")("tiny")
    assert cfg.moe_gate_norm_eps == eps and not cfg.moe_gate_norm_added


def test_a_slot_freed_and_taken_again_starts_from_zeros(engines):
    """A request of 150 + 6 tokens, then a short one in the same slot
    with no clearing launch: the second answers as a fresh engine does,
    logits and all."""
    _family.a_slot_is_reused(engines, "lfm2", **kernels("off"))


def test_two_requests_decode_side_by_side(family, engines):
    model, params, ref, weights, cfg = family
    eng = engines("lfm2", **kernels("off"))
    a = eng.submit(tokens(70, seed=1), SamplingParams(max_new_tokens=12,
                                                      temperature=0.0))
    for _ in range(6):
        eng.step()
    b = eng.submit(tokens(37, seed=2), SamplingParams(max_new_tokens=8,
                                                      temperature=0.0))
    while a.finish_reason is None or b.finish_reason is None:
        assert eng.step()
    for req, seed, n in ((a, 1, 70), (b, 2, 37)):
        seq = tokens(n, seed=seed) + list(req.out_tokens)
        want = np.asarray(ref.forward_logits(weights, cfg, seq))
        assert list(req.out_tokens) == [int(t) for t in
                                        want[n - 1:-1].argmax(-1)]


def _layer_outputs(model, params, toks):
    """The stream after every layer of the serving loop's own order (the
    cache-less forward's layers, one at a time)."""
    from megatron_llm_tpu.models import transformer as tfm

    cfg = model.cfg
    outs = []
    x = jnp.take(params["embedding"]["word"]["embedding"],
                 jnp.asarray([toks]), axis=0)
    stack = params["transformer"]
    D = cfg.moe_first_dense_layers
    for i in range(cfg.num_layers):
        src, at = (stack["dense_layers"], i) if i < D else (
            {k: v for k, v in stack["layers"].items()
             if k not in cfg.mixer_counts}, i - D)
        layer_p = jax.tree_util.tree_map(lambda p: p[at], src)
        kind, own = cfg.mixer_index(i)
        layer_p[kind] = jax.tree_util.tree_map(
            lambda p: p[own], stack["layers"][kind])
        x = tfm.transformer_layer(x, layer_p, cfg, layer_type=kind)[0]
        outs.append(np.asarray(x))
    return outs


@pytest.mark.parametrize("kind,swap", [("conv", (1, 5)),
                                       ("attention", (0, 1))])
def test_two_dense_layers_in_a_stack_by_kind_give_every_layer_its_own_weights(
        family, kind, swap):
    """ONE way of counting: a dense layer's mixer is a member of its
    kind's stack by its index in the whole depth.  Exchanging two entries
    of one kind's stack changes the outputs from the earlier of the two
    layers on and no layer's before it, in the plain forward and in the
    layer-by-layer order alike; and the layers that own them are the
    ones ``mixer_index`` names."""
    model, params = family[:2]
    cfg = model.cfg
    owners = [i for i in range(cfg.num_layers)
              if cfg.mixer_index(i)[0] == kind]
    assert [cfg.mixer_index(i)[1] for i in owners] == list(range(len(owners)))
    assert owners[:2] == ([0, 1] if kind == "conv" else [2, 6])
    a, b = swap
    perm = list(range(len(owners)))
    perm[a], perm[b] = perm[b], perm[a]
    swapped = jax.tree_util.tree_map(lambda x: x, params)
    swapped["transformer"]["layers"][kind] = jax.tree_util.tree_map(
        lambda x: x[jnp.asarray(perm)], params["transformer"]["layers"][kind])
    toks = tokens(20, seed=11)
    before = _layer_outputs(model, params, toks)
    after = _layer_outputs(model, swapped, toks)
    first = owners[a]
    for i in range(cfg.num_layers):
        same = np.allclose(before[i], after[i], atol=1e-6)
        assert same == (i < first), (i, first)
    # and the plain forward counts the same way
    got = np.asarray(model(swapped, jnp.asarray([toks], jnp.int32),
                           train=False)[0])
    final = np.asarray(language_model_forward(
        swapped, jnp.asarray([toks], jnp.int32), None, None, cfg)[0][0])
    np.testing.assert_allclose(got, final, atol=1e-6)
    assert np.abs(got - np.asarray(model(
        params, jnp.asarray([toks], jnp.int32), train=False)[0])).max() > 1e-3


def test_the_plain_forward_is_the_serving_loops_layers(family):
    """The cache-less forward's logits are the final norm and the tied
    head over the layer-by-layer stream."""
    model, params, ref, weights, cfg = family
    toks = tokens(20, seed=11)
    x = _layer_outputs(model, params, toks)[-1]
    want = np.asarray(ref.head_block(
        jnp.asarray(x[0]), weights.final_norm(),
        weights.output_rows(0, 512), eps=1e-5))
    got = np.asarray(model(params, jnp.asarray([toks], jnp.int32),
                           train=False)[0])
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)


def test_the_program_without_rotation_is_not_the_reference(family):
    model, params, ref, weights, cfg = family
    still = model.cfg.replace(
        position_embedding_type=PositionEmbeddingType.none)
    toks = tokens(70, seed=5)
    got = np.asarray(language_model_forward(
        params, jnp.asarray([toks], jnp.int32), None, None, still)[0][0])
    want = np.asarray(ref.forward_logits(weights, cfg, toks))
    assert np.abs(got - want).max() > 100 * LOGIT_TOL


# --- the pool at 64-wide heads ---------------------------------------------

def test_the_pool_holds_64_wide_heads_two_a_row():
    cfg = lfm2_config("8b-a1b", num_layers=4, layer_types=(
        "conv", "conv", "attention", "conv"), compute_dtype="bf16")
    assert paged_kv.heads_a_row(cfg) == 2
    pools = jax.eval_shape(lambda: paged_kv.init_pools(
        cfg, 8, 16, num_slots=128))
    assert [sorted(p) for p in pools] == [
        ["conv_state"], ["conv_state"], ["k_pages", "v_pages"],
        ["conv_state"]]
    assert pools[2]["k_pages"].shape == (8, 16, 4, 128)
    # 2,048 B a token an attention layer; 8,192 B a slot a conv layer
    assert paged_kv.block_bytes(pools) == 16 * 2048
    assert paged_kv.state_bytes_per_slot(pools) == 3 * 8192
    assert pools[0]["conv_state"].shape == (129, 2, 2048)
    assert pools[0]["conv_state"].dtype == jnp.bfloat16
    # a head of a whole row, or of a width that is not half of one, and
    # an odd number of heads, lie as they always did
    for kw in (dict(kv_channels=128), dict(kv_channels=32),
               dict(num_attention_heads_kv=1, num_attention_heads=4)):
        assert paged_kv.heads_a_row(cfg.replace(**kw)) == 1


@pytest.mark.parametrize("n,kernel", [(1, "xla"), (1, "pallas"),
                                      (24, "xla"), (24, "pallas")])
def test_the_paged_pool_at_64_wide_heads_is_dense_attention(n, kernel,
                                                            monkeypatch):
    """Both walks (the decode step's and a chunk's) and the dense gather
    over the packed pool against plain causal attention over the same
    keys: 8 query heads over 4 key/value heads of 64, so head h reads
    part ``(h // 2) % 2`` of row ``h // 4``."""
    from megatron_llm_tpu.models.transformer import core_attention

    monkeypatch.setattr(pa, "_INTERPRET", kernel == "pallas")
    nh, g, d, ctx = 8, 4, 64, [19, 0, 40]
    cfg = lfm2_config("tiny", num_attention_heads=nh,
                      num_attention_heads_kv=g, **TINY)
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    total = max(ctx) + n
    q, k, v = (jax.random.normal(kk, (3, total, heads, d))
               for kk, heads in zip(keys, (nh, g, g)))
    pool = paged_kv.init_pools(cfg, 3 * 9 + 1, BS, num_slots=3)[2]
    assert pool["k_pages"].shape[2:] == (2, 128)
    tables = jnp.asarray(1 + np.arange(27).reshape(3, 9), jnp.int32)

    def cache(pool, c, valid):
        return paged_kv.PagedKVCache(pool, tables, jnp.asarray(c),
                                     jnp.asarray(valid), kernel=kernel)

    # the history, written row by row of the batch as a chunk each
    hist = max(ctx)
    _, c = cache(pool, [0, 0, 0], ctx).attend(
        q[:, :hist], k[:, :hist], v[:, :hist], None)
    new = [jnp.stack([a[b, ctx[b]:ctx[b] + n] for b in range(3)])
           for a in (q, k, v)]
    got, c = cache(c.pool, ctx, [n, n, n]).attend(*new, None)
    for b in range(3):
        upto = ctx[b] + n
        want = core_attention(q[b:b + 1, :upto], k[b:b + 1, :upto],
                              v[b:b + 1, :upto], cfg, None, None, False)
        np.testing.assert_allclose(got[b], want[0, ctx[b]:], atol=2e-5,
                                   rtol=0)


def test_the_engine_counts_what_its_conv_layers_do(engines):
    eng = engines("lfm2", **kernels("off"))
    since = _family.counted(eng)
    serve(eng, tokens(70, seed=5), 4)
    s, records = since()
    # six conv layers: three chunks of one live row, three steps of one
    assert s["conv_rows_live"] == 6 * (3 + 3)
    assert s["conv_tokens"] == 6 * (70 + 3)
    assert s["ssm_rows_live"] == s["ssm_tokens"] == 0
    held = [r.ssm_state_bytes_held for r in records]
    assert set(held) == {6 * 2 * 128 * 4}
    assert eng._cache.state_bytes_per_slot == 6 * 2 * 128 * 4
