"""NVIDIA-Nemotron-3-Nano (``model_type`` ``nemotron_h``: layers of ONE
sublayer each by ``hybrid_override_pattern``, a Mamba-2 mixer of several
groups, attention with no positions, ungated relu^2 experts under a
sigmoid router of which a share is held), against the benchmark's plain
reference.

Seeded random weights, CPU, float32 on both sides, small size: 14 layers
(``MEMEM*EMEMEM*E``), hidden 128, 4 query and 2 key/value heads of 32, 8
state-space heads of 16 in 2 groups over a state of 16, the router's 8
experts of which 4 are held at 3 a token, an expert's width 96 (which no
multiple of 128 divides), a shared MLP of 192; contexts of 5 to 156
tokens over pages of 8 and chunks of 32.  The reference is the file the
benchmark's probe loads (``benchmarks/reference/nemotron_h.py``: the
recurrence one token at a time, an expert at a time, no chunk, no
cache), loaded here by path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _family
from _family import BS, kernels, serve, tokens
from megatron_llm_tpu import config as C
from megatron_llm_tpu.models import mamba, moe
from megatron_llm_tpu.models.language_model import language_model_forward
from megatron_llm_tpu.models.nemotron_h import (NANO_PATTERN, NemotronHModel,
                                                nemotron_h_config)
from megatron_llm_tpu.ops import paged_kv
from megatron_llm_tpu.ops.layernorm import rms_norm
from megatron_llm_tpu.ops.pallas import grouped_matmul as gm
from megatron_llm_tpu.ops.pallas import paged_attention as pa
from megatron_llm_tpu.serving import SamplingParams

# float32 on both sides, the same mathematics summed in another order (a
# chunked scan and a step against a recurrence over tokens, a grouped
# matmul against an expert at a time); the logits' deviation is some 0.5
# and every named fault moves them by hundredths
ROW = _family.FAMILIES["nemotron_h"]
LOGIT_TOL, CHUNK = ROW.tol, ROW.chunk
FAULTS = ("norm_whole", "group_zero", "expert_swiglu", "expert_relu",
          "bias_in_gates", "no_scale", "rope_on", "no_shared",
          "second_norm", "no_D", "gate_after_norm", "no_conv_bias",
          "state_dropped_at_chunks", "float8")
TINY_PATTERN = "MEMEM*EMEMEM*E"


@pytest.fixture(scope="module")
def family():
    return _family.built("nemotron_h")


def test_the_tiny_preset_is_the_pattern_and_its_params_are_by_kind(family):
    model, params = family[:2]
    cfg = model.cfg
    assert cfg.layer_types == C.pattern_layer_types(TINY_PATTERN)
    assert cfg.one_sublayer and cfg.state_space
    assert cfg.mixer_counts == {"mamba": 6, "attention": 2, "moe": 6}
    assert cfg.num_sparse_layers == 6
    assert [cfg.mixer_index(i) for i in (0, 1, 5, 6, 13)] == [
        ("mamba", 0), ("moe", 0), ("attention", 0), ("moe", 2), ("moe", 5)]
    layers = params["transformer"]["layers"]
    # one norm a layer, no second norm, no MLP on a mixer layer
    assert sorted(layers) == ["attention", "input_norm", "mamba", "moe"]
    assert layers["input_norm"]["scale"].shape == (14, 128)
    assert layers["moe"]["experts"]["w_in"].shape == (6, 4, 128, 96)
    assert layers["moe"]["experts"]["w_out"].shape == (6, 4, 96, 128)
    assert layers["moe"]["shared"]["dense_h_to_4h"]["kernel"].shape == (
        6, 128, 192)
    assert layers["moe"]["router"]["kernel"].shape == (6, 128, 8)
    assert layers["mamba"]["in_proj"]["kernel"].shape == (
        6, 128, 128 + (128 + 2 * 2 * 16) + 8)
    assert "lm_head" in params
    specs = model.param_specs(params)
    assert (jax.tree_util.tree_structure(specs, is_leaf=lambda x: isinstance(
        x, tuple)) == jax.tree_util.tree_structure(params))


@pytest.mark.parametrize("n", [5, 16, 17, 70])
def test_full_forward_matches_the_reference(n):
    """The program's plain (cache-less) forward, a scan over the one
    period with each layer's sublayer taken by its index among its kind:
    logits at every position against the reference."""
    _family.full_forward_is_the_references("nemotron_h", n)


@pytest.mark.parametrize("prompt,new,kernel", [
    (5, 14, "off"), (64, 10, "off"), (150, 6, "off"), (45, 5, "on")])
def test_the_engine_through_its_cache_matches_one_full_forward(
        engines, prompt, new, kernel):
    """Chunked prefill (chunks of 32, the last one padded) then decode
    through the engine's own programs, the state carried in its slot
    across every chunk boundary and step and the expert layers with no
    cache entry, against the reference's ONE forward: logits at every
    chunk's last row and every step, and the state the slot is left
    with; the attention layers through the dense gather and (``on``)
    through the walk's kernels, the experts through the grouped matmul's
    kernel, in interpret mode."""
    _family.chunked_prefill_then_decode_is_one_forward(
        engines, "nemotron_h", prompt, new, kernel)


def test_the_pools_hold_nothing_for_an_expert_layer(family, engines):
    """A 'moe' layer has no cache entry: its pool is a pytree of no
    arrays, its group ``NONE``; the FULL group is the 2 attention
    layers, the STATE group the 6 mixers, and the routing histogram has
    a row an EXPERT layer."""
    model, params = family[:2]
    eng = engines("nemotron_h", **kernels("off"))
    groups = eng._layer_groups
    assert groups == tuple({"M": paged_kv.STATE, "*": paged_kv.FULL,
                            "E": paged_kv.NONE}[c] for c in TINY_PATTERN)
    pools = eng._st.pages
    assert [p == {} for p in pools] == [g == paged_kv.NONE for g in groups]
    assert len(paged_kv.paged_pools(pools)) == 2
    assert sum(map(paged_kv.is_state, pools)) == 6
    swapped = paged_kv.with_paged(pools, ["a", "b"])
    assert [p for p in swapped if isinstance(p, str)] == ["a", "b"]
    assert [i for i, p in enumerate(swapped) if isinstance(p, str)] == [5, 12]
    caches = paged_kv.step_caches(
        pools, eng._cache.tables(eng.blocks), jnp.zeros((2,), jnp.int32),
        jnp.ones((2,), jnp.int32), "xla", groups)
    _, new = jax.jit(lambda params, caches: language_model_forward(
        params, jnp.ones((2, 1), jnp.int32), jnp.zeros((2, 1), jnp.int32),
        None, model.cfg, rng_key=None, train=False, kv_caches=caches))(
            params, caches)
    assert [c.moe_counts is not None for c in new] == [
        g == paged_kv.NONE for g in groups]
    assert paged_kv.routing_of(new).shape == (6, 8)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_named_fault_fails_by_many_tolerances(fault):
    """The gated norm over the whole width, group 0's B and C for every
    head, a gated or a plain-relu expert, the bias in the gates, the
    scale left out, a rotation, the shared MLP left out, a second norm
    on a mixer layer, and Granite's (``D``, the gate's place, the
    convolution's bias, a chunk's state not handed on, float8)."""
    _family.a_named_fault_is_told("nemotron_h", fault)


def test_rows_join_and_leave_and_a_slot_is_reused(family, engines):
    """Continuous batching over the state and the pages: a request
    admitted while another decodes, the first leaving while the second
    goes on, a third taking the freed slot with no clearing launch: each
    decodes as if alone, held to the reference's logits at every step."""
    model, params, ref, weights, cfg = family
    eng = engines("nemotron_h", **kernels("off"))
    got = engines.tapped(eng, by_slot=True)
    sp = lambda n: SamplingParams(max_new_tokens=n, temperature=0.0)
    a = eng.submit(tokens(70, seed=1), sp(6))
    for _ in range(5):
        eng.step()
    b = eng.submit(tokens(37, seed=2), sp(14))
    while a.finish_reason is None:
        assert eng.step()
    c = eng.submit(tokens(21, seed=4), sp(5))
    while b.finish_reason is None or c.finish_reason is None:
        assert eng.step()
        eng.blocks.check_invariants()
    steps = [t for t in got if isinstance(t, tuple)]
    # two rows were live in one step at some time, and one at another
    by_step = {}
    for s, t in steps:
        by_step.setdefault(s, []).append(t)
    assert len(by_step) == 2
    for req, seed, n in ((a, 1, 70), (b, 2, 37), (c, 4, 21)):
        seq = tokens(n, seed=seed) + list(req.out_tokens)
        want = np.asarray(ref.forward_logits(weights, cfg, seq))
        assert list(req.out_tokens) == [int(t) for t in
                                        want[n - 1:-1].argmax(-1)]
        np.testing.assert_allclose(got[n - 1], want[n - 1], atol=LOGIT_TOL,
                                   rtol=0)
    # the third request decoded in the slot the first had left
    slot_a = [s for s, ts in by_step.items() if 70 in ts]
    slot_c = [s for s, ts in by_step.items() if 21 in ts]
    assert slot_a == slot_c
    seq = tokens(21, seed=4) + list(c.out_tokens)
    want = np.asarray(ref.forward_logits(weights, cfg, seq))
    for t in range(21, 21 + 4):
        np.testing.assert_allclose(got[(slot_c[0], t)], want[t],
                                   atol=LOGIT_TOL, rtol=0)


def test_a_finished_requests_slot_holds_the_references_state(engines):
    """What the benchmark's probe reads: after a request of chunks (the
    last one padded) and steps, its slot holds the state the reference
    is left with by the prompt and every answer token but the last, in
    every state-space layer."""
    eng = engines("nemotron_h", **kernels("off"))
    toks = tokens(70, seed=9)
    req = serve(eng, toks, 6)
    apart = _family.state_apart("nemotron_h", eng, req.slot,
                                toks + list(req.out_tokens)[:-1])
    assert len(apart) == 6 and max(apart) < 1e-5, apart


# ---------------------------------------------------------------------------
# the mixer's groups
# ---------------------------------------------------------------------------

def test_the_gated_norm_by_group_is_the_references(family):
    ref = family[2]
    y = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 128), jnp.float32)
    y = y * jnp.linspace(0.1, 9.0, 128)     # groups of unlike size
    scale = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(4), (128,))
    got = mamba.gated_group_norm(y, scale, 2, 1e-5)
    want = ref.group_rms_norm(y.reshape(10, 128), scale, 1e-5, 2)
    np.testing.assert_allclose(np.asarray(got).reshape(10, 128),
                               np.asarray(want), rtol=1e-6, atol=1e-6)
    whole = ref.group_rms_norm(y.reshape(10, 128), scale, 1e-5, 1)
    assert np.abs(np.asarray(want) - np.asarray(whole)).max() > 0.1


def test_one_group_is_todays_norm_bit_for_bit():
    """``mamba_n_groups`` 1 (Granite) takes ``rms_norm`` itself: the
    same jaxpr, so the same program, and the same bits."""
    y = jax.random.normal(jax.random.PRNGKey(5), (3, 4, 64), jnp.float32)
    scale = jnp.linspace(0.5, 1.5, 64).astype(jnp.bfloat16)
    one = lambda v: mamba.gated_group_norm(v, scale, 1, 1e-5)
    today = lambda v: rms_norm(v, scale, eps=1e-5)
    assert str(jax.make_jaxpr(one)(y)) == str(jax.make_jaxpr(today)(y))
    assert np.array_equal(np.asarray(one(y)), np.asarray(today(y)))


@pytest.mark.parametrize("steps", [None, "xla", "pallas"], ids=[
    "the_scan_alone", "then_the_xla_step", "then_the_steps_kernel"])
def test_the_scan_and_the_step_share_b_and_c_by_group(family, steps,
                                                      monkeypatch):
    """Head i uses group i // (heads / groups), in the chunked scan and
    in the step alike: a mixer layer alone against the reference's
    recurrence, and the fault that gives every head group 0's.  The
    whole sequence as one chunk, or its first 32 tokens as a chunk into
    slot 1 of 3 and the last 8 as steps beside two idle rows, on the XLA
    path and through the in-place kernel (interpret mode)."""
    monkeypatch.setattr(pa, "_INTERPRET", steps == "pallas")
    model, params, ref, weights, cfg = family
    w = weights.layer(0)
    hn = jax.random.normal(jax.random.PRNGKey(6), (40, 128), jnp.float32)
    names = ("in_proj", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D",
             "gate_norm", "out_proj")
    kw = dict(n_heads=8, d_head=16, d_state=16, n_groups=2, d_conv=4,
              eps=1e-5)
    want, _ = ref.mamba_out(hn, {n: w[n] for n in names}, **kw,
                            faults=frozenset())
    wrong, _ = ref.mamba_out(hn, {n: w[n] for n in names}, **kw,
                             faults=frozenset({"group_zero"}))
    own = jax.tree_util.tree_map(
        lambda a: a[0], params["transformer"]["layers"]["mamba"])
    if steps is None:
        got = mamba.mamba_mixer(hn[None], own, model.cfg)[0]
    else:
        pool = next(p for p in paged_kv.init_pools(
            model.cfg, 4, BS, num_slots=3) if paged_kv.is_state(p))

        def cache(context, valid, rows=None):
            tables = {paged_kv.FULL: jnp.zeros((len(context), 2), jnp.int32)}
            if rows is not None:
                tables[paged_kv.STATE] = jnp.asarray(rows, jnp.int32)
            return paged_kv.step_caches(
                [pool], tables, jnp.asarray(context, jnp.int32),
                jnp.asarray(valid, jnp.int32), steps, (paged_kv.STATE,))[0]

        out, new = mamba.mamba_mixer(hn[None, :32], own, model.cfg,
                                     kv_cache=cache([0], [32], rows=[1]))
        got = [out[0]]
        for t in range(32, 40):
            pool = new.pool
            out, new = mamba.mamba_mixer(
                jnp.tile(hn[None, t:t + 1], (3, 1, 1)), own, model.cfg,
                kv_cache=cache([0, t, 0], [0, 1, 0]))
            got.append(out[1])
        got = jnp.concatenate(got)
        # the idle rows' slots are as they were allocated
        assert not np.asarray(new.pool["ssm_state"])[[0, 2]].any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)
    assert np.abs(np.asarray(got) - np.asarray(wrong)).max() > 1e-2


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _moe_layer(params, j):
    return jax.tree_util.tree_map(
        lambda a: a[j], params["transformer"]["layers"]["moe"])


def test_the_ungated_experts_through_the_dropless_path_are_the_references(
        family):
    """relu(x W_up)^2 W_down, two matrices an expert, under the sigmoid
    router's bias and scale, the held share: ``moe_mlp_dropless`` on one
    expert layer against the reference's expert at a time."""
    model, params, ref, weights, cfg = family
    hn = jax.random.normal(jax.random.PRNGKey(7), (1, 50, 128), jnp.float32)
    got, _, counts = moe.moe_mlp_dropless(hn, _moe_layer(params, 0),
                                          model.cfg)
    want, _, chose, _ = ref.moe_out(hn[0], weights.layer(1), weights, cfg,
                                    1, {}, frozenset())
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5, rtol=0)
    assert counts.shape == (8,) and int(counts.sum()) == 150
    assert np.array_equal(np.asarray(counts),
                          np.bincount(np.asarray(chose).ravel(), minlength=8))
    for fault in ("expert_swiglu", "expert_relu", "bias_in_gates",
                  "no_scale", "no_shared"):
        wrong = ref.moe_out(hn[0], weights.layer(1), weights, cfg, 1, {},
                            frozenset({fault}))[0]
        assert np.abs(np.asarray(got[0]) - np.asarray(wrong)).max() > 1e-2


def test_the_shares_add_up_to_the_uncut_layer(family):
    """Experts 0-3 and 4-7 of 8, the shared MLP counted once, are the
    uncut reference's layer under sigmoid routing with the bias and the
    scale: what each chip of the deployment computes before the
    exchange, summed."""
    model, params, ref = family[:3]
    whole_cfg = nemotron_h_config("tiny", use_flash_attn=False,
                                  num_experts=8, moe_router_experts=None)
    whole = _family.shaken("nemotron_h", NemotronHModel(whole_cfg))
    layer = _moe_layer(whole, 0)
    hn = jax.random.normal(jax.random.PRNGKey(8), (1, 60, 128), jnp.float32)
    rcfg = {**ROW.ref_cfg(whole_cfg, CHUNK), "experts_first": 0}
    weights = _family.load("nemotron_h_from_program").ProgramWeights(whole,
                                                                     rcfg)
    want = ref.moe_out(hn[0], weights.layer(1), weights, rcfg, 1, {},
                       frozenset(), held=range(8))[0]
    shared = moe._shared_mlp(hn, layer, whole_cfg)
    total = -shared.astype(jnp.float32)        # counted once of two
    for first in (0, 4):
        half_cfg = whole_cfg.replace(num_experts=4, moe_router_experts=8,
                                     moe_experts_first=first)
        half = {**layer, "experts": jax.tree_util.tree_map(
            lambda a: a[first:first + 4], layer["experts"])}
        out, _, counts = moe.moe_mlp_dropless(hn, half, half_cfg)
        assert int(counts.sum()) == 60 * 3
        total = total + out
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=3e-5, rtol=0)
    # and each half alone is not the layer
    assert np.abs(np.asarray(out[0]) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("k,n", [(128, 96), (96, 128), (256, 1856 // 8)])
def test_the_grouped_matmul_takes_a_width_no_multiple_of_128_divides(k, n):
    """``_divisors``' whole-width branch: an expert's width that no
    multiple of 128 divides is one block; the kernel in interpret mode
    against a dot a group."""
    assert gm._divisors(96) == [96] and gm._divisors(1856) == [1856]
    assert gm._divisors(2688) == [128, 384, 896, 2688]
    rows = jax.random.normal(jax.random.PRNGKey(0), (70, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (5, k, n), jnp.float32)
    sizes = jnp.asarray([9, 0, 31, 17, 3], jnp.int32)
    before = gm._INTERPRET
    gm._INTERPRET = True
    try:
        got = np.asarray(gm.grouped_matmul(rows, w, sizes))
    finally:
        gm._INTERPRET = before
    ends = np.cumsum(np.asarray(sizes))
    for g, (a, b) in enumerate(zip(ends - np.asarray(sizes), ends)):
        np.testing.assert_allclose(got[a:b], np.asarray(rows[a:b] @ w[g]),
                                   rtol=1e-5, atol=1e-4)


def test_the_published_width_is_laid_out_at_whole_lanes():
    """What ``stats()['moe_expert_tiles']`` reports at 2688 x 1856:
    ``w_up`` LAID OUT at 1920 columns (the TPU's compiler lays ``[..,
    2688, 1856]`` out with 2688 minor and copies all the experts into
    the kernel's layout at every launch otherwise: ``moe.laid_width``),
    ``w_down`` walked in ``[1856, tn]``, under the kernel's VMEM budget;
    the rule touches no width that is whole lanes, none within one row
    of lanes, and no GLU's."""
    cfg = nemotron_h_config("nano-30b-a3b", num_layers=1,
                            layer_types=("moe",), compute_dtype="bf16")
    assert moe.laid_width(cfg) == 1920
    tiles = gm.moe_expert_tiles(cfg)
    assert (tiles["w_in"]["k"], tiles["w_in"]["n"]) == (2688, 1920)
    assert (tiles["w_out"]["k"], tiles["w_out"]["n"]) == (1856, 2688)
    assert tiles["w_out"]["tk"] == 1856
    assert tiles["w_in"]["tk"] in (128, 384, 896, 2688)
    assert tiles["w_in"]["tn"] in (128, 384, 640, 1920)
    assert tiles["w_out"]["tn"] in (128, 384, 896, 2688)
    for t in tiles.values():
        assert t["vmem_bytes"] <= gm._VMEM_BUDGET
    shapes = jax.eval_shape(lambda: moe.init_moe_mlp_params(
        jax.random.PRNGKey(0), cfg.replace(num_experts=2, moe_top_k=2,
                                           moe_router_experts=None),
        jnp.bfloat16))
    assert shapes["experts"]["w_in"].shape == (2, 2688, 1920)
    assert shapes["experts"]["w_out"].shape == (2, 1856, 2688)
    for width, glu, laid in ((96, None, 96), (768, None, 768),
                             (160, None, 256), (1000, "swiglu", 1000)):
        assert moe.laid_width(cfg.replace(
            ffn_hidden_size=width, glu_activation=glu,
            mlp_activation="gelu" if glu else "relu2")) == laid


def test_a_laid_out_width_computes_the_width(family):
    """An expert layer whose width (160) is laid out at 256: the columns
    past the width are zeros at init, what they give is dropped before
    ``w_down`` whatever they hold, and the layer is the reference's at
    160 through the adapter that undoes the layout; the kernel in
    interpret mode and XLA's ragged dot alike."""
    ref = family[2]
    cfg = nemotron_h_config("tiny", use_flash_attn=False, num_experts=8,
                            moe_router_experts=None, ffn_hidden_size=160)
    params = _family.shaken("nemotron_h", NemotronHModel(cfg))
    layer = _moe_layer(params, 0)
    w_in = layer["experts"]["w_in"]
    assert w_in.shape == (8, 128, 256) and not np.asarray(w_in[..., 160:]).any()
    assert np.asarray(w_in[..., :160]).all()
    rcfg = ROW.ref_cfg(cfg, CHUNK)
    weights = _family.load("nemotron_h_from_program").ProgramWeights(params,
                                                                     rcfg)
    assert weights.expert(1, 3)["w_up"].shape == (128, 160)
    hn = jax.random.normal(jax.random.PRNGKey(9), (1, 40, 128), jnp.float32)
    want = ref.moe_out(hn[0], weights.layer(1), weights, rcfg, 1, {},
                       frozenset())[0]
    # columns that are NOT zero change nothing: they are dropped
    dirty = {**layer, "experts": {**layer["experts"],
                                  "w_in": w_in.at[..., 160:].set(3.0)}}
    for given, interpret in ((layer, False), (dirty, False), (layer, True)):
        before = gm._INTERPRET
        gm._INTERPRET = interpret
        try:
            got = moe.moe_mlp_dropless(hn, given, cfg)[0]
        finally:
            gm._INTERPRET = before
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   atol=3e-5, rtol=0)


# ---------------------------------------------------------------------------
# the pattern, the table, the counter
# ---------------------------------------------------------------------------

def test_the_published_pattern_is_23_23_6_and_a_dense_layer_is_refused():
    types = C.pattern_layer_types(NANO_PATTERN)
    assert len(types) == 52
    assert [types.count(k) for k in ("mamba", "moe", "attention")] == [
        23, 23, 6]
    cfg = nemotron_h_config("nano-30b-a3b")
    assert cfg.mixer_counts == {"mamba": 23, "attention": 6, "moe": 23}
    assert cfg.mamba_d_inner == 4096 != 2 * cfg.hidden_size
    assert cfg.mamba_conv_dim == 6144
    # the cell's 14 layers are the pattern's first 14
    assert NANO_PATTERN[:14] == TINY_PATTERN
    with pytest.raises(ValueError, match="a dense MLP layer"):
        C.pattern_layer_types("MEM-M*E")
    with pytest.raises(ValueError, match="letters of"):
        C.pattern_layer_types("MEMX")
    with pytest.raises(ValueError, match="needs num_experts > 1"):
        nemotron_h_config("tiny", num_experts=0, moe_router_experts=None,
                          moe_shared_experts=0, moe_choice_bias=False)


def test_the_flags_carry_the_pattern():
    from megatron_llm_tpu.arguments import (parse_args,
                                            transformer_config_from_args,
                                            validate_args)

    args = validate_args(parse_args(args_list=[
        "--num_layers=14", "--hidden_size=128", "--num_attention_heads=4",
        "--hybrid_override_pattern=" + TINY_PATTERN, "--num_experts=4",
        "--mlp_activation=relu2", "--position_embedding_type=none",
        "--moe_score_function=sigmoid", "--moe_choice_bias=1",
        "--moe_choice_bias_std=0.02",
        "--no_bias", "--use_rms_norm", "--seq_length=64",
        "--max_position_embeddings=64", "--padded_vocab_size=512",
        "--micro_batch_size=1", "--global_batch_size=1"]), world_size=1)
    cfg = transformer_config_from_args(args)
    assert cfg.layer_types == C.pattern_layer_types(TINY_PATTERN)
    assert cfg.mlp_activation == "relu2" and cfg.one_sublayer
    # a fresh model's choice bias is drawn at the spread the flag gives
    # (0.1 without it: moe._CHOICE_BIAS_STD)
    assert cfg.moe_choice_bias_std == 0.02
    drawn = lambda c: np.asarray(moe.init_moe_mlp_params(
        jax.random.PRNGKey(0), c, jnp.float32)["router"]["choice_bias"])
    narrow, wide = drawn(cfg), drawn(cfg.replace(moe_choice_bias_std=None))
    np.testing.assert_allclose(narrow * 5, wide, rtol=1e-6)
    assert 0.05 < wide.std() < 0.2 and moe._CHOICE_BIAS_STD == 0.1


SQUARES = dict(C.RUNS_WITH)[C.ONE_SUBLAYER]


@pytest.mark.parametrize("what", SQUARES,
                         ids=[w.split(" (")[0] for w in SQUARES])
def test_every_square_of_the_new_row_is_refused_by_name(family, engines,
                                                        what):
    """Training, tensor and pipeline parallelism, the verify step, the
    int8 pool, the host tier, preemption; the prefix cache turned off;
    another layer type beside the three: each one sentence of
    ``config.refusal`` that names both."""
    model, params = family[:2]
    cfg = model.cfg
    if what == C.OTHER_TYPES:
        with pytest.raises(ValueError, match="goes with 'mamba' and"):
            cfg.replace(layer_types=cfg.layer_types[:-1] + ("full",))
        return
    said = C.refusal(cfg, (what,))
    assert said.startswith(C.ONE_SUBLAYER) and what in said
    if what == C.TRAINING:
        with pytest.raises(NotImplementedError) as raised:
            model(params, jnp.ones((1, 8), jnp.int32), train=True)
        assert str(raised.value) == said
    elif what == C.MODEL_PARALLEL:
        from megatron_llm_tpu.models import gpt

        mp = pytest.MonkeyPatch()
        mp.setattr(gpt, "_vocab_unsharded", lambda: False)
        try:
            with pytest.raises(ValueError) as raised:
                NemotronHModel(cfg)
        finally:
            mp.undo()
        assert str(raised.value) == said
    elif what == C.PREFIX_CACHE:
        eng = engines.fresh("nemotron_h", prefix_cache=True)
        assert not eng.config.prefix_cache
    else:
        on = {C.VERIFY_STEP: dict(speculative=True, draft_k=2),
              C.INT8_POOL: dict(int8_kv_cache=True),
              C.HOST_TIER: dict(host_cache_bytes=1 << 20),
              C.PREEMPTION: dict(preemption=True)}[what]
        with pytest.raises(ValueError) as raised:
            engines.fresh("nemotron_h", **on)
        assert str(raised.value) == said


@pytest.mark.parametrize("step_kernel,moved", [("xla", 6 * 5),
                                               ("pallas", 6 * 2)])
def test_the_rows_a_step_moves_against_a_hand_count(family, step_kernel,
                                                    moved):
    """``ssm_rows_moved``: the rows x state-space layers whose state a
    decode launch's program reads and writes.  Four slots of which two
    decode: the step's kernel moves those two a layer, the XLA step all
    four and the garbage row; a chunk's launch counts none."""
    plan = paged_kv.plan(family[0].cfg, BS, 4, 8, CHUNK, "xla", step_kernel)

    class Record:
        kind = "decode"

    d = Record()
    plan.account(d, np.array([9, 0, 40, 0]), np.array([1, 0, 1, 0]), 1, 3)
    assert (d.ssm_rows_live, d.ssm_rows_moved, d.ssm_tokens) == (12, moved, 12)
    d = Record()
    d.kind = "prefill"
    plan.account(d, np.array([32]), np.array([20]), CHUNK, 3)
    assert (d.ssm_rows_live, d.ssm_tokens) == (6, 120)
    assert not hasattr(d, "ssm_rows_moved")


def test_the_held_experts_touched_against_a_hand_count(family, engines):
    """``moe_experts_touched_held``: of the experts this chip holds
    (the router's 2-5), those with at least one live assignment, summed
    over the expert layers; ``moe_experts_touched`` counts over all
    eight and cannot say it."""
    model = family[0]
    plan = paged_kv.plan(model.cfg, BS, 2, 8, CHUNK, "xla", "xla")

    class Record:
        pass

    counts = np.zeros((6, 8), np.int64)
    counts[0] = [3, 0, 1, 0, 0, 2, 0, 0]      # held 2 and 5: 2
    counts[1] = [0, 0, 0, 0, 0, 0, 4, 2]      # none held
    counts[2] = [1, 1, 1, 1, 1, 1, 0, 0]      # all four held
    d = Record()
    plan.account_routing(d, counts)
    assert d.moe_experts_touched == 3 + 2 + 6
    assert d.moe_experts_touched_held == 2 + 0 + 4
    assert d.moe_assignments == 18 and d.moe_assignments_held == 3 + 0 + 4
    assert d.moe_expert_slots == 48
    # and through an engine: the total is every launch's, in stats()
    eng = engines("nemotron_h", **kernels("off"))
    since = _family.counted(eng)
    serve(eng, tokens(40, seed=6), 4)
    stats, records = since()
    assert 0 < stats["moe_experts_touched_held"] <= stats[
        "moe_experts_touched"]
    assert stats["moe_experts_touched_held"] <= 6 * 4 * (2 + 3)
    assert stats["moe_experts_touched_held"] == sum(
        r.moe_experts_touched_held for r in records)
    # a whole model holds every expert it touches
    whole = nemotron_h_config("tiny", use_flash_attn=False, num_experts=8,
                              moe_router_experts=None)
    d = Record()
    paged_kv.plan(whole, BS, 2, 8, CHUNK, "xla", "xla").account_routing(d, counts)
    assert d.moe_experts_touched_held == d.moe_experts_touched == 11
