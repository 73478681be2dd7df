"""Ouro (``ouro``: a llama-style stack of four norms a layer that runs
``loop_steps`` times over shared weights, the final norm after each
pass, cache planes of its own a pass), against the benchmark's plain
reference.

Seeded random weights, CPU, float32 on both sides, small size: hidden
128, 4 query and 4 key-value heads of 32, at TWO shapes: three layers
four times (twelve planes a token) and two layers three times (six).
The reference is the file the benchmark's probe loads
(``benchmarks/reference/ouro.py``), loaded here by path: it keeps no
cache, so a pass attends its own keys by construction, and what the
engine's ``loop_steps x num_layers`` pools are held to is that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import BS, is_greedy, kernels, serve, tokens
from megatron_llm_tpu import config as C
from megatron_llm_tpu.models import transformer as tfm
from megatron_llm_tpu.models.gpt import GPTModel
from megatron_llm_tpu.models.language_model import flops_per_token
from megatron_llm_tpu.models.llama import llama_config
from megatron_llm_tpu.models.ouro import (OuroModel, exit_distribution,
                                          ouro_config)
from megatron_llm_tpu.ops import paged_kv

ROW = _family.FAMILIES["ouro"]
LOGIT_TOL, CHUNK = ROW.tol, ROW.chunk
# the faults of the probe; ``bf16`` is what the program itself does
FAULTS = ("shared_planes", "previous_plane", "three_passes", "norm_once",
          "no_output_norms", "theta_1e4", "float8")
SIZES = {"family": None, "three_passes": "two_layers_three_passes"}


@pytest.fixture(scope="module")
def family():
    return _family.built("ouro")


@pytest.mark.parametrize("either,n", [("family", 5), ("family", 17),
                                      ("family", 70), ("three_passes", 70)])
def test_full_forward_matches_the_reference(either, n):
    """The program's plain (cache-less) forward, the passes around ONE
    scan and the final norm after each: logits at every position against
    the reference, three layers four times and two layers three times."""
    _family.full_forward_is_the_references("ouro", n, SIZES[either])


def test_the_passes_share_one_scan_and_one_set_of_weights(family):
    """``loop_steps`` passes trace ``loop_steps`` scans of ``num_layers``
    steps over the SAME stacked leaves, and the parameter tree holds one
    stack and the exit gate's ``hidden_size + 1`` parameters."""
    model, params = family[:2]
    cfg = model.cfg
    jaxpr = jax.make_jaxpr(lambda p, t: model(p, t, train=False))(
        params, jnp.zeros((1, 8), jnp.int32))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [s.params["length"] for s in scans] == [cfg.num_layers] * 4
    stack = params["transformer"]
    assert set(stack) == {"layers", "final_norm", "exit_gate"}
    assert all(a.shape[0] == cfg.num_layers
               for a in jax.tree_util.tree_leaves(stack["layers"]))
    assert sum(a.size for a in jax.tree_util.tree_leaves(
        stack["exit_gate"])) == cfg.hidden_size + 1
    one = ouro_config("tiny", loop_steps=1)
    assert "exit_gate" not in jax.eval_shape(
        GPTModel(one).init, jax.random.PRNGKey(0))["transformer"]
    assert flops_per_token(cfg) - flops_per_token(one) == 3 * (
        flops_per_token(one) - 6.0 * cfg.padded_vocab_size * cfg.hidden_size)


@pytest.mark.parametrize("either,prompt,new,kernel", [
    ("family", 5, 14, "off"), ("family", 64, 10, "off"),
    ("family", 150, 6, "off"), ("three_passes", 150, 6, "off"),
    ("family", 45, 5, "on")])
def test_the_engine_over_a_pool_a_pass_matches_one_full_forward(
        engines, either, prompt, new, kernel):
    """Chunked prefill then decode through the engine's own programs over
    ``loop_steps x num_layers`` pools against the reference's ONE full
    forward (no cache: a pass attends its own keys), through the dense
    gather and (``on``) the walk's kernels in interpret mode; a launch
    counts a layer's run a pass a live row, and a walk for each.  A seed
    a prompt: the module's engine keeps its prefix cache."""
    eng, since, _ = _family.chunked_prefill_then_decode_is_one_forward(
        engines, "ouro", prompt, new, kernel, size=SIZES[either],
        seed=prompt)
    cfg = eng.model.cfg
    planes = cfg.num_layers * cfg.loop_steps
    assert len(eng._st.pages) == cfg.cache_layers == planes
    stats, records = since()
    launches = -(-prompt // CHUNK) + new - 1
    assert stats["loop_layer_runs"] == stats["walks"] == planes * launches
    assert all(r.loop_layer_runs == planes for r in records)
    assert stats["walks_native"] == (stats["walks"] if kernel == "on" else 0)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(fault):
    """The same comparison against each FAULTY reference: one plane a
    layer shared by the passes, a pass reading the plane before its own,
    a pass dropped, the final norm once, the output norms left out,
    another theta, float8 activations: each far beyond a hundred
    tolerances."""
    _family.a_named_fault_is_told("ouro", fault)


def test_a_fault_planted_in_the_program_is_told_too(family, engines,
                                                    monkeypatch):
    """The likeliest bug, in the PROGRAM's place: every pass handed the
    first pass's planes (one plane a layer shared by the passes).  The
    engine's logits then leave the reference by whole units."""
    model, params, ref, weights, cfg = family
    eng = engines.fresh("ouro", prefix_cache=False)
    L = model.cfg.num_layers
    sound = tfm.transformer_layer
    seen = []

    def shared(h, layer_p, cfg_, **kw):
        cache = kw.get("kv_cache")
        if cache is not None:
            # the plane layer i's first pass was handed, for every pass
            seen.append(cache)
            kw["kv_cache"] = seen[(len(seen) - 1) % L] if len(
                seen) > L else cache
            if len(seen) == L * model.cfg.loop_steps:
                del seen[:]
        return sound(h, layer_p, cfg_, **kw)

    monkeypatch.setattr(tfm, "transformer_layer", shared)
    got = engines.tapped(eng)
    toks = tokens(40, seed=21)
    req = serve(eng, toks, 4)
    seq = toks + list(req.out_tokens)[:-1]
    want = np.asarray(ref.forward_logits(weights, cfg, seq))
    rows = sorted(got)
    apart = np.abs(np.stack([got[t] for t in rows]) - want[rows]).max(-1)
    assert apart[1:].min() > 100 * LOGIT_TOL, apart


def test_the_exit_distribution_is_the_references_and_sums_to_one(family):
    """The exit gate over each pass's normed stream, from the program's
    full forward: the distribution over the passes against the
    reference's, summing to 1; at the published threshold 1.0 every token
    leaves at the last pass, and a threshold under 1 is refused."""
    model, params, ref, weights, cfg = family
    toks = tokens(33, seed=4)
    got = np.asarray(exit_distribution(model, params,
                                       jnp.asarray([toks], jnp.int32)))
    gates = []
    ref.forward_logits(weights, cfg, toks, rows=[0], gates=gates)
    want = np.asarray(ref.exit_distribution(gates[0]))
    assert got.shape == (4, 1, 33) and want.shape == (4, 33)
    np.testing.assert_allclose(got[:, 0], want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(got.sum(axis=0), 1.0, atol=1e-6)
    # a gate that says something: the shaken norm and the drawn kernel
    # leave the passes' shares apart from each other
    assert want.std() > 1e-3 and (want > 0).all()
    assert (ref.exit_pass(want, 1.0) == 4).all()
    assert (ref.exit_pass(want, 0.5) < 4).any()
    with pytest.raises(ValueError, match="early_exit_threshold 0.5 is not "
                                         "implemented"):
        ouro_config("tiny", early_exit_threshold=0.5)
    with pytest.raises(ValueError, match="at least 1"):
        ouro_config("tiny", loop_steps=0)


def _plain(loop_steps=None):
    """The same llama-style config with four norms a layer, built with
    the looped stack's field (1) and without it."""
    tiny = dict(num_layers=3, hidden_size=128, num_attention_heads=4,
                num_attention_heads_kv=4, kv_channels=32,
                ffn_hidden_size=256, padded_vocab_size=512, seq_length=256,
                max_position_embeddings=1024, rope_theta=1e6,
                layernorm_epsilon=1e-6, sublayer_output_norm=True,
                use_flash_attn=False)
    if loop_steps is None:
        return llama_config("tiny", **tiny)
    return ouro_config("tiny", loop_steps=loop_steps, use_flash_attn=False)


def test_one_pass_is_the_model_without_the_field_bit_for_bit():
    """At ``loop_steps`` 1 the wrapper's config is the llama-style one
    with ``sublayer_output_norm``: the same fields, the same parameters
    from the same key, and the same logits BIT FOR BIT on both paths (the
    scan with no cache, the serving loop through the paged cache): the
    passes add nothing to a stack run once."""
    one, plain = _plain(1), _plain()
    assert one == plain
    models = [GPTModel(cfg) for cfg in (one, plain)]
    params = [m.init(jax.random.PRNGKey(0)) for m in models]
    for a, b in zip(*map(jax.tree_util.tree_leaves, params)):
        assert (np.asarray(a) == np.asarray(b)).all()
    toks = jnp.asarray([tokens(24, seed=6)], jnp.int32)
    full = [np.asarray(m(p, toks, train=False)) for m, p in zip(models,
                                                                params)]
    assert (full[0] == full[1]).all()
    served = []
    for m, p in zip(models, params):
        eng = _family.engine(m, p, prefill_chunk=CHUNK, **kernels("off"))
        got = _family.Engines(pytest.MonkeyPatch()).tapped(eng)
        serve(eng, toks[0].tolist(), 3)
        served.append(np.stack([got[t] for t in sorted(got)]))
    assert (served[0] == served[1]).all()
    # and the jaxprs are one: no scope, no operation of a pass
    traced = [str(jax.make_jaxpr(lambda p, t: m(p, t, train=False))(
        p, toks)) for m, p in zip(models, params)]
    assert traced[0] == traced[1] and "loop_pass" not in traced[0]


def test_a_block_holds_every_pass_of_every_layer():
    """``block_bytes`` of a looped model is ``loop_steps`` times the
    one-pass model's, the pools ``cache_layers`` of one shape, and at the
    published widths a token holds 32,768 B a layer: 393,216 B over the
    cell's twelve."""
    one, four = _plain(1), _plain(4)
    pools = [jax.eval_shape(lambda c=c: paged_kv.init_pools(c, 9, BS))
             for c in (one, four)]
    assert (len(pools[0]), len(pools[1])) == (3, 12) == (
        one.cache_layers, four.cache_layers)
    assert paged_kv.block_bytes(pools[1]) == 4 * paged_kv.block_bytes(
        pools[0])
    assert paged_kv.layer_groups(four) is None
    full = ouro_config("2.6B", num_layers=12)
    held = jax.eval_shape(lambda: paged_kv.init_pools(
        full, 897, 16, dtype=jnp.bfloat16))
    assert len(held) == 48 and held[0]["k_pages"].shape == (897, 16, 16, 128)
    assert paged_kv.block_bytes(held) == 16 * 393_216 == 16 * 12 * 32_768
    plan = paged_kv.plan(full.replace(compute_dtype="bf16"), 16, 12, 224,
                         512, "pallas", "pallas")
    assert plan.group_block_bytes == (0, 0) and plan.groups is None


def test_a_prefix_is_adopted_and_a_shared_page_copied_in_every_plane(
        family, engines):
    """A page is a page: a second request with the first one's prompt
    adopts its pages, all ``loop_steps x num_layers`` planes of them, and
    decodes THE SAME LOGITS from them; a third that shares all but its
    last token writes into a shared page's copy (copy-on-write of every
    plane) and answers as the plain forward does."""
    model, params = family[:2]
    eng = engines.fresh("ouro", max_model_len=96)
    assert eng.config.prefix_cache and eng.blocks.prefix_cache_enabled
    got = engines.tapped(eng)
    prompt = tokens(41, seed=11)
    first = list(serve(eng, prompt, 6).out_tokens)
    wrote = {t: a.copy() for t, a in got.items()}
    got.clear()
    held = []
    again = serve(eng, prompt, 6, each_step=lambda eng, req: held.append(
        int(eng.blocks.tables[req.slot][0])) if req.slot is not None
        else None)
    assert again.cached_prompt_tokens >= 32 and list(again.out_tokens) == first
    # the last chunk's row and every step, from adopted pages
    assert sorted(got) == [t for t in sorted(wrote) if t >= 40]
    for t in got:
        np.testing.assert_allclose(got[t], wrote[t], atol=1e-5, rtol=0)
    # the engine's own copy program (what copy-on-write mirrors on the
    # device) moves the adopted page's twelve planes, no two alike
    pages = eng._st.pages
    copied = eng._copy_page(pages, held[0], 0)
    planes = [np.asarray(p["k_pages"][0]) for p in copied]
    assert len(planes) == 12
    for p, mine in zip(pages, planes):
        assert mine.any() and (mine == np.asarray(
            p["k_pages"][held[0]])).all()
    assert len({mine.tobytes() for mine in planes}) == 12
    stats = eng.stats()
    other = prompt[:40] + [(prompt[40] + 1) % 500 + 1]
    assert is_greedy(model, params, other, serve(eng, other, 4).out_tokens)
    assert eng.stats()["prefill_tokens_cached"] > stats[
        "prefill_tokens_cached"]


def test_copy_page_moves_every_plane():
    """``copy_page`` / ``fetch_page`` / ``load_page`` walk the list: a
    page's copy carries the keys and values of every pass of every
    layer."""
    cfg = _plain(4)
    pools = paged_kv.init_pools(cfg, 5, BS)
    pools = [{k: a.at[2].set(i + 1.0 + (k == "v_pages") * 0.5)
              for k, a in p.items()} for i, p in enumerate(pools)]
    copied = paged_kv.copy_page(pools, jnp.int32(2), jnp.int32(4))
    assert len(copied) == 12
    for i, p in enumerate(copied):
        assert float(p["k_pages"][4].min()) == i + 1.0
        assert float(p["v_pages"][4].max()) == i + 1.5
        assert not np.asarray(p["k_pages"][3]).any()
    page = paged_kv.fetch_page(pools, jnp.int32(2))
    back = paged_kv.load_page(paged_kv.init_pools(cfg, 5, BS), page,
                              jnp.int32(1))
    assert [float(p["k_pages"][1].max()) for p in back] == [
        i + 1.0 for i in range(12)]


def test_a_slot_is_reused(engines):
    _family.a_slot_is_reused(engines, "ouro", prefix_cache=False)


def test_the_legacy_contiguous_and_rolling_caches_hold_a_plane_a_pass():
    """``init_kv_caches`` builds ``cache_layers`` caches, so the legacy
    decode stack gives the plain forward's logits, a chunk and then a
    token at a time; so does its rolling ring under a sliding window
    (``ROLLING_CACHE`` is no square of the looped row)."""
    from megatron_llm_tpu.text_generation.generation import (
        _forward_with_cache, init_kv_caches)

    assert C.ROLLING_CACHE not in dict(C.RUNS_WITH)[C.LOOPED]
    cfg = ouro_config("tiny", loop_steps=3, sliding_window_size=8,
                      use_flash_attn=False)
    model = OuroModel(cfg)
    params = _family.shake(model.init(jax.random.PRNGKey(0)),
                           jax.random.PRNGKey(1), **ROW.shake)
    toks = jnp.asarray([tokens(23, seed=9)], jnp.int32)
    want = np.asarray(model(params, toks, train=False))
    for rolling in (False, True):
        caches = init_kv_caches(cfg, 1, 32, rolling=rolling)
        assert len(caches) == cfg.cache_layers == 9
        assert caches[0]["k"].shape[1] == (8 if rolling else 32)
        part, caches = _forward_with_cache(model, params, toks[:, :6],
                                           caches, 0)
        parts = [part]
        for t in range(6, 23):
            part, caches = _forward_with_cache(
                model, params, toks[:, t:t + 1], caches, t)
            parts.append(part)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate(parts, axis=1)), want, atol=LOGIT_TOL,
            rtol=0)


def test_preemption_runs_over_a_pool_a_pass(family, engines):
    """``PREEMPTION`` is no square of the looped row: a request preempted
    mid-prefill and again mid-decode is requeued, prefills its context
    again into every pass's planes (its own pages adopted where the
    prefix cache kept them) and answers as the plain forward does."""
    from megatron_llm_tpu.serving import SamplingParams

    model, params = family[:2]
    assert C.PREEMPTION not in dict(C.RUNS_WITH)[C.LOOPED]
    eng = engines.fresh("ouro", preemption=True, max_model_len=96)
    prompt = tokens(60, seed=31)
    req = eng.submit(prompt, SamplingParams(max_new_tokens=8,
                                            temperature=0.0))
    assert eng.step() and eng.step() and req.prefill_pos == 2 * CHUNK
    eng._preempt(eng._st, req)
    assert req.slot is None and req.preempt_count == 1
    while len(req.out_tokens) < 3:
        assert eng.step()
    eng._preempt(eng._st, req)
    assert req.preempt_count == 2
    while req.finish_reason is None:
        assert eng.step()
        eng.blocks.check_invariants()
    assert is_greedy(model, params, prompt, req.out_tokens)


def test_the_scopes_reach_the_programs_tables(family):
    """``loop_pass`` and ``loop_pass_norm`` are scopes the program tables
    know, and the lowered decode path carries a scope a pass."""
    from megatron_llm_tpu import hlo_collectives

    from _hlo_text import lowered_text

    model, params = family[:2]
    assert {"loop_pass", "loop_pass_norm"} <= set(hlo_collectives.SCOPES)
    assert hlo_collectives.scope_of(
        "jit(step)/loop_pass/pass_2/mlp/dot") == "mlp"
    assert hlo_collectives.scope_of(
        "jit(step)/loop_pass/pass_2/add") == "loop_pass"
    assert hlo_collectives.scope_of(
        "jit(step)/loop_pass_norm/mul") == "loop_pass_norm"
    text = lowered_text(jax.jit(
        lambda p, t: model(p, t, train=False)).lower(
            params, jnp.zeros((1, 8), jnp.int32)))
    for scope in ("loop_pass/pass_0", "loop_pass/pass_3", "loop_pass_norm"):
        assert f"/{scope}/" in text, scope


LOOPED = dict(C.RUNS_WITH)[C.LOOPED]


def test_the_looped_rows_refusals_name_the_loop():
    """A looped model is told by ITS row (before the output norms' row,
    which refuses the same features for another reason)."""
    cfg = ouro_config("tiny")
    assert C.HAS[C.LOOPED](cfg) and C.refusal(cfg) is None
    for what in (C.TRAINING, C.MODEL_PARALLEL, C.VERIFY_STEP, C.INT8_POOL,
                 C.HOST_TIER):
        assert what in LOOPED
        assert C.refusal(cfg, (what,)).startswith(
            f"{C.LOOPED}: not implemented with {what}")
    assert C.refusal(cfg, (C.PREFIX_CACHE, C.PREEMPTION,
                           C.ROLLING_CACHE)) is None


def test_the_family_wrapper_asserts_its_flags_and_its_published_sizes():
    cfg = ouro_config("tiny")
    for bad in (dict(sublayer_output_norm=False), dict(loop_steps=1),
                dict(num_attention_heads_kv=2), dict(tie_embed_logits=True),
                dict(glu_activation=None)):
        with pytest.raises(AssertionError):
            OuroModel(cfg.replace(**bad))
    full = ouro_config("2.6B")
    assert (full.num_layers, full.hidden_size, full.num_attention_heads,
            full.num_attention_heads_kv, full.head_dim,
            full.ffn_hidden_size) == (48, 2048, 16, 16, 128, 5632)
    assert (full.padded_vocab_size, full.rope_theta, full.layernorm_epsilon,
            full.max_position_embeddings) == (49152, 1e6, 1e-6, 65536)
    assert full.loop_steps == 4 and full.early_exit_threshold == 1.0
    assert full.cache_layers == 192 and not full.tie_embed_logits
    # ISSUE 63's arithmetic: a layer 51.39 M, twelve layers with the
    # whole vocabulary, the final norm and the gate 817.99 M
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    layer = jax.eval_shape(lambda k: tfm.init_layer_params(
        k, full, jnp.bfloat16), jax.random.PRNGKey(0))
    assert count(layer["attention"]) == 4 * 2048 * 2048
    assert count(layer["mlp"]) == 3 * 2048 * 5632
    assert count(layer) == 51_388_416
    twelve = jax.eval_shape(OuroModel(full.replace(num_layers=12)).init,
                            jax.random.PRNGKey(0))
    assert count(twelve) == 12 * 51_388_416 + 2 * 49152 * 2048 + 4097
    assert count(twelve) == 817_991_681


def test_the_flags_carry_the_passes_and_the_server_builds_it():
    from megatron_llm_tpu.arguments import (parse_args,
                                            transformer_config_from_args,
                                            validate_args)

    args = validate_args(parse_args(args_list=[
        "--num_layers=3", "--hidden_size=128", "--num_attention_heads=4",
        "--num_attention_heads_kv=4", "--kv_channels=32",
        "--ffn_hidden_size=256", "--sublayer_output_norm", "--loop_steps=4",
        "--rope_theta=1000000", "--layernorm_epsilon=1e-6",
        "--position_embedding_type=rotary", "--glu_activation=swiglu",
        "--no_bias", "--use_rms_norm", "--no_tie_embed_logits",
        "--seq_length=256", "--max_position_embeddings=1024",
        "--padded_vocab_size=512", "--micro_batch_size=1",
        "--global_batch_size=1"]), world_size=1)
    cfg = transformer_config_from_args(args)
    assert cfg.loop_steps == 4 and cfg.cache_layers == 12
    want = ouro_config("tiny")
    for field in ("loop_steps", "sublayer_output_norm", "rope_theta",
                  "layernorm_epsilon", "num_attention_heads_kv",
                  "tie_embed_logits", "early_exit_threshold"):
        assert getattr(cfg, field) == getattr(want, field), field
    OuroModel(cfg)
    import finetune

    preset = finetune.MODEL_DEFAULTS["ouro"]
    assert preset["loop_steps"] == 4 and preset["sublayer_output_norm"]
    assert finetune._CKPT_ARG_MAP["loop_steps"] == "loop_steps"
    assert "ouro" in finetune.__doc__
