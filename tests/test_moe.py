"""Mixture-of-experts: routing semantics, dense parity, sharding, and the
model/trainer integration (TPU-native extension — the reference has no MoE,
SURVEY §2.2 "expert parallel: absent")."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import DTYPES, TransformerConfig
from megatron_llm_tpu.models.moe import (
    init_moe_mlp_params,
    moe_capacity,
    moe_mlp,
    moe_mlp_specs,
)
from megatron_llm_tpu.models.transformer import mlp as dense_mlp


def _cfg(**kw):
    base = dict(
        num_layers=2, hidden_size=32, num_attention_heads=4,
        ffn_hidden_size=64, num_experts=4, moe_top_k=2,
        glu_activation="swiglu", add_bias_linear=False,
        # ample capacity: every token always fits its expert buffer
        moe_capacity_factor=8.0,
    )
    base.update(kw)
    return TransformerConfig(**base)


def test_identical_experts_match_dense_mlp():
    """With every expert holding the same weights and top-1 routing (gate
    renormalizes to 1.0), the MoE layer must equal the dense MLP."""
    cfg = _cfg(moe_top_k=1)
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    # copy expert 0 into all experts
    p["experts"]["w_in"] = jnp.broadcast_to(
        p["experts"]["w_in"][:1], p["experts"]["w_in"].shape)
    p["experts"]["w_out"] = jnp.broadcast_to(
        p["experts"]["w_out"][:1], p["experts"]["w_out"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))

    dense_p = {
        "dense_h_to_4h": {"kernel": p["experts"]["w_in"][0]},
        "dense_4h_to_h": {"kernel": p["experts"]["w_out"][0]},
    }
    want = dense_mlp(x, dense_p, cfg)
    got, aux = moe_mlp(x, p, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert aux.shape == (2,) and np.isfinite(np.asarray(aux)).all()


def test_uniform_router_aux_loss_is_one():
    """Zero router weights -> uniform probs; Switch load balance
    E * sum_e(frac_e * 1/E) == sum_e frac_e == 1 exactly."""
    cfg = _cfg()
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    p["router"]["kernel"] = jnp.zeros_like(p["router"]["kernel"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    _, aux = moe_mlp(x, p, cfg)
    np.testing.assert_allclose(float(aux[0]), 1.0, atol=1e-5)


def test_capacity_dropping_zeroes_overflow_tokens():
    """A capacity of 1 with a router forced to a single expert keeps only
    the first token per batch row; every later token's MLP output is 0."""
    cfg = _cfg(moe_top_k=1, moe_capacity_factor=1e-9, moe_min_capacity=1)
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    # bias router hard toward expert 2 via a huge weight column
    wr = np.zeros(p["router"]["kernel"].shape, np.float32)
    wr[0, 2] = 1e6          # logits ~ x[..., 0] * 1e6 -> same sign everywhere
    p["router"]["kernel"] = jnp.asarray(wr)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))) + 0.1
    assert moe_capacity(cfg, 8) == 1
    out, _ = moe_mlp(x, p, cfg)
    out = np.asarray(out)
    # token 0 got the buffer slot; tokens 1.. were dropped (zero output)
    assert np.abs(out[:, 0]).max() > 0
    np.testing.assert_allclose(out[:, 1:], 0.0, atol=1e-6)


def test_grads_reach_router_and_all_experts():
    cfg = _cfg()
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))

    def loss(p):
        out, aux = moe_mlp(x, p, cfg)
        return jnp.sum(out * out) + aux[0]

    g = jax.grad(loss)(p)
    assert float(jnp.linalg.norm(g["router"]["kernel"])) > 0
    per_expert = jnp.linalg.norm(
        g["experts"]["w_in"].reshape(cfg.num_experts, -1), axis=-1)
    assert (np.asarray(per_expert) > 0).all(), per_expert


def test_sharded_matches_unsharded(utils):
    """dp-sharded experts + batch-sharded tokens produce the same numbers
    as the single-device run (GSPMD all-to-all dispatch is semantics-free)."""
    from megatron_llm_tpu import topology
    from megatron_llm_tpu.parallel import sharding as sh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = _cfg(num_experts=8)
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
    want, aux_want = moe_mlp(x, p, cfg)         # no mesh constraints active

    topology.initialize_model_parallel()        # dp=8 mesh
    try:
        specs = moe_mlp_specs(p, stacked=False)
        p_sh = sh.shard_params(p, specs)
        # expert dim (8) really lands on the dp axis
        w_in_shard = p_sh["experts"]["w_in"].sharding.spec
        assert w_in_shard[0] == "dp", w_in_shard
        x_sh = jax.device_put(
            x, sh.make_shardings(("batch", None, None)))
        got, aux_got = jax.jit(lambda x, p: moe_mlp(x, p, cfg))(x_sh, p_sh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(aux_got), np.asarray(aux_want),
                                   atol=1e-6)
    finally:
        topology.destroy_model_parallel()


def test_gpt_model_moe_train_and_decode(utils):
    """GPTModel integration: (loss, aux) contract, flops accounting, and
    the kv-cache decode path (aux dropped)."""
    from megatron_llm_tpu.models.gpt import GPTModel

    cfg = _cfg(
        seq_length=32, max_position_embeddings=32, padded_vocab_size=64,
        tie_embed_logits=True, hidden_dropout=0.0, attention_dropout=0.0,
        use_flash_attn=False,
    )
    dense_cfg = dataclasses.replace(cfg, num_experts=0)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 32)))
    labels = jnp.roll(toks, -1, -1)

    loss_tok, aux = model(params, toks, labels=labels, train=True)
    assert loss_tok.shape == (2, 32)
    assert aux.shape == (2,)
    # aux accumulates one lb term per layer, each ~1 near-uniform at init
    assert 0.5 * cfg.num_layers < float(aux[0]) < 2.0 * cfg.num_layers

    assert model.flops_per_token() > GPTModel(dense_cfg).flops_per_token()

    # generation contract: logits without labels, aux dropped
    logits = model(params, toks)
    assert logits.shape == (2, 32, 64)

    g = jax.grad(
        lambda p: jnp.mean(model(p, toks, labels=labels)[0])
        + 1e-2 * model(p, toks, labels=labels)[1][0]
    )(params)
    layers = g["transformer"]["layers"]
    assert float(jnp.linalg.norm(layers["mlp"]["router"]["kernel"])) > 0
    assert float(jnp.linalg.norm(layers["mlp"]["experts"]["w_in"])) > 0


def test_non_gpt_families_reject_moe():
    from megatron_llm_tpu.models.bert import BertModel
    from megatron_llm_tpu.models.t5 import T5Model

    cfg = _cfg(num_tokentypes=2)
    with pytest.raises(NotImplementedError, match="GPT family"):
        BertModel(cfg)
    with pytest.raises(NotImplementedError, match="GPT family"):
        T5Model(cfg)


def test_moe_kv_cache_decode_matches_full_forward(utils):
    """Incremental MoE decode (capacity floor covers s=1 routing) must
    reproduce the one-shot causal forward logits."""
    from megatron_llm_tpu.models.mixtral import MixtralModel, mixtral_config
    from megatron_llm_tpu.text_generation.generation import (
        _forward_with_cache,
        init_kv_caches,
    )

    cfg = mixtral_config(
        "tiny", num_layers=2, seq_length=64, max_position_embeddings=64,
        padded_vocab_size=64, use_flash_attn=False,
        moe_capacity_factor=8.0,
    )
    model = MixtralModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    toks = jnp.asarray(rng.randint(0, 64, (2, 10)))

    full_logits = model(params, toks, train=False)

    caches = init_kv_caches(model.cfg, 2, 16)
    logits_p, caches = _forward_with_cache(model, params, toks[:, :4],
                                           caches, 0)
    parts = [logits_p]
    for t in range(4, 10):
        lg, caches = _forward_with_cache(model, params, toks[:, t:t + 1],
                                         caches, t)
        parts.append(lg)
    inc_logits = jnp.concatenate(parts, axis=1)
    np.testing.assert_allclose(np.asarray(inc_logits),
                               np.asarray(full_logits), atol=2e-4)


def test_zero1_shards_moe_expert_state(utils):
    """ZeRO-1 state sharding must dp-shard the (large) expert optimizer
    moments, not silently replicate them."""
    from megatron_llm_tpu import topology
    from megatron_llm_tpu.config import TrainConfig
    from megatron_llm_tpu.models.mixtral import MixtralModel, mixtral_config
    from megatron_llm_tpu.optimizer import MegatronOptimizer
    from megatron_llm_tpu.parallel import sharding as sh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = mixtral_config(
        "tiny", num_layers=2, seq_length=32, max_position_embeddings=32,
        padded_vocab_size=256, num_experts=8, hidden_size=64,
        ffn_hidden_size=176, num_attention_heads=4,
        num_attention_heads_kv=2, use_flash_attn=False,
    )
    model = MixtralModel(cfg)
    topology.initialize_model_parallel(tensor_model_parallel_size=2)  # dp=4
    try:
        params = model.init(jax.random.PRNGKey(0))
        params = sh.shard_params(params, model.param_specs(params))
        opt = MegatronOptimizer(TrainConfig(lr=1e-3))
        opt_state = opt.init(params)
        opt_state = opt.shard_zero1(opt_state, model.param_specs(params),
                                    params, 4, min_bytes=16 << 10)
        w_in_spec = opt_state.exp_avg[
            "transformer"]["layers"]["mlp"]["experts"]["w_in"].sharding.spec
        assert "dp" in jax.tree_util.tree_leaves(list(w_in_spec)), w_in_spec
    finally:
        topology.destroy_model_parallel()


# ---------------------------------------------------------------------------
# the router's other forms: sigmoid scores, a choice bias, a scale; the
# shared MLP
# ---------------------------------------------------------------------------

def _hand_router(bias, **kw):
    """One token whose four router logits are given: the router's kernel
    is the identity on the token's first four values."""
    cfg = _cfg(num_experts=4, moe_top_k=2, hidden_size=4,
               num_attention_heads=1, ffn_hidden_size=8,
               moe_score_function="sigmoid", moe_choice_bias=bias is not None,
               **kw)
    p = {"router": {"kernel": jnp.eye(4)}}
    if bias is not None:
        p["router"]["choice_bias"] = jnp.asarray(bias, jnp.float32)
    return cfg, p


def test_sigmoid_choice_the_bias_turns_the_choice_and_not_the_gate():
    """A hand-worked token: logits (2, 1, 0, -1), scores sigmoid of them
    (0.881, 0.731, 0.5, 0.269).  Without a bias experts 0 and 1 are
    chosen.  A bias of +0.5 on expert 3 lifts it to 0.769, over expert 1:
    experts 0 and 3 are chosen, and the gates are their SCORES, 0.881 and
    0.269 (not 0.769), renormalised to 0.766 and 0.234."""
    from megatron_llm_tpu.models.moe import _route

    x = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    s = 1 / (1 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    cfg, p = _hand_router(None)
    _, probs, gates, idx = _route(x, p, cfg)
    np.testing.assert_allclose(np.asarray(probs[0]), s, rtol=1e-6)
    assert np.asarray(idx[0]).tolist() == [0, 1]
    np.testing.assert_allclose(np.asarray(gates[0]), s[:2] / s[:2].sum(),
                               rtol=1e-6)
    cfg, p = _hand_router([0.0, 0.0, 0.0, 0.5])
    _, _, gates, idx = _route(x, p, cfg)
    assert np.asarray(idx[0]).tolist() == [0, 3]
    np.testing.assert_allclose(np.asarray(gates[0]),
                               s[[0, 3]] / s[[0, 3]].sum(), rtol=1e-6)
    assert abs(float(gates[0, 1]) - 0.2339) < 1e-3
    # as the scores give them where the family does not renormalise
    cfg, p = _hand_router([0.0, 0.0, 0.0, 0.5], norm_topk_prob=False)
    np.testing.assert_allclose(np.asarray(_route(x, p, cfg)[2][0]),
                               s[[0, 3]], rtol=1e-6)


def test_the_routed_scale_multiplies_the_renormalised_gates():
    from megatron_llm_tpu.models.moe import _route

    x = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    cfg, p = _hand_router([0.0, 0.0, 0.0, 0.5], moe_routed_scale=2.448)
    gates = np.asarray(_route(x, p, cfg)[2][0])
    np.testing.assert_allclose(gates.sum(), 2.448, rtol=1e-6)
    np.testing.assert_allclose(gates[1] / gates[0], 0.26894 / 0.88080,
                               rtol=1e-4)


def test_a_softmax_models_routing_is_bit_for_bit_what_it_was():
    """A model that sets none of the new fields routes by the lines it
    always did: the softmax over all experts, its ``top_k`` largest,
    renormalised; and its parameters hold no bias and no shared MLP."""
    from megatron_llm_tpu.models.moe import _route

    cfg = _cfg()
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert set(p) == {"router", "experts"} and set(p["router"]) == {"kernel"}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    logits, probs, gates, idx = _route(x, p, cfg)
    want_probs = jax.nn.softmax(
        jnp.einsum("...h,he->...e", x, p["router"]["kernel"]), axis=-1)
    want_gates, want_idx = jax.lax.top_k(want_probs, 2)
    want_gates = want_gates / jnp.maximum(
        jnp.sum(want_gates, axis=-1, keepdims=True), 1e-9)
    assert (np.asarray(probs) == np.asarray(want_probs)).all()
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    assert (np.asarray(gates) == np.asarray(want_gates)).all()


@pytest.mark.parametrize("train", [False, True])
def test_the_shared_mlp_is_counted_once(train):
    """With ``moe_shared_experts`` the layer's output is the routed sum
    plus ONE ungated MLP of that many experts' width over every token, on
    the dropless path and on the capacity path alike."""
    from megatron_llm_tpu.models.moe import moe_mlp_dropless

    cfg = _cfg(moe_shared_experts=2)
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert p["shared"]["dense_h_to_4h"]["kernel"].shape == (32, 2 * 2 * 64)
    assert p["shared"]["dense_4h_to_h"]["kernel"].shape == (2 * 64, 32)
    p["shared"] = jax.tree_util.tree_map(lambda w: w * 20.0, p["shared"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    run = ((lambda q, c: moe_mlp(x, q, c)[0]) if train else
           (lambda q, c: moe_mlp_dropless(x, q, c)[0]))
    routed = run({k: v for k, v in p.items() if k != "shared"},
                 cfg.replace(moe_shared_experts=0))
    shared = dense_mlp(x, p["shared"], cfg)
    assert np.abs(np.asarray(shared)).max() > 0.05
    np.testing.assert_allclose(np.asarray(run(p, cfg)),
                               np.asarray(routed + shared), atol=1e-5)
    specs = moe_mlp_specs(p, stacked=False, cfg=cfg)
    assert specs["shared"]["dense_h_to_4h"]["kernel"] == (None, "ffn")


# ---------------------------------------------------------------------------
# one chip's share of a layer's experts (cfg.moe_router_experts)
# ---------------------------------------------------------------------------

def _share(full_cfg, full, first, held):
    """(config, parameters) of the layer that holds experts
    ``first .. first + held`` of ``full``'s."""
    cfg = full_cfg.replace(num_experts=held,
                           moe_router_experts=full_cfg.num_experts,
                           moe_experts_first=first)
    p = dict(full, experts=jax.tree_util.tree_map(
        lambda w: w[first:first + held], full["experts"]))
    return cfg, p


@pytest.mark.parametrize("parts", [(4, 4), (2, 6), (3, 3, 2)])
def test_the_halves_sum_to_the_whole(parts):
    """THE SHARE TEST: the routed sums of the shares of a layer's experts
    (each computed by a layer that holds only its own, under the gates the
    router gave over ALL the token's choices) plus the shared MLP ONCE are
    the uncut layer, the program's and the plain reference's alike; with
    some tokens not live."""
    import importlib.util
    import os

    from megatron_llm_tpu.models.moe import moe_mlp_dropless

    cfg = _cfg(num_experts=8, moe_top_k=3, moe_shared_experts=2)
    full = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    full = jax.tree_util.tree_map(lambda w: w * 6.0, full)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    live = jnp.arange(24)[None, :] < jnp.asarray([24, 17])[:, None]
    whole, _, counts = moe_mlp_dropless(x, full, cfg, live)
    shared = jnp.where(live[..., None], dense_mlp(x, full["shared"], cfg),
                       0.0)
    total, first, seen = 0.0, 0, 0
    for held in parts:
        c, p = _share(cfg, full, first, held)
        assert p["experts"]["w_in"].shape[0] == held
        out, _, router_counts = moe_mlp_dropless(x, p, c, live)
        # the histogram stays the ROUTER's, over all eight
        assert (np.asarray(router_counts) == np.asarray(counts)).all()
        out = jnp.where(live[..., None], out, 0.0) - shared
        assert np.abs(np.asarray(out)).max() > 0.05
        total = total + out
        seen += int(np.asarray(counts)[first:first + held].sum())
        first += held
    assert seen == int(np.asarray(counts).sum()) == 3 * (24 + 17)
    whole = jnp.where(live[..., None], whole, 0.0)
    np.testing.assert_allclose(np.asarray(total + shared),
                               np.asarray(whole), atol=2e-5)
    # and the uncut REFERENCE's layer (benchmarks/reference/granite.py),
    # every expert computed, on the live tokens of the first row
    spec = importlib.util.spec_from_file_location(
        "ref_granite", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "reference", "granite.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    class Weights:
        @staticmethod
        def expert(i, e):
            w_in = full["experts"]["w_in"][e]
            return {"w1": w_in[:, :64], "w3": w_in[:, 64:],
                    "w2": full["experts"]["w_out"][e]}

    sh_in = full["shared"]["dense_h_to_4h"]["kernel"]
    w = {"ffn_norm": jnp.ones((32,)), "gate": full["router"]["kernel"],
         "shared_w1": sh_in[:, :128], "shared_w3": sh_in[:, 128:],
         "shared_w2": full["shared"]["dense_4h_to_h"]["kernel"]}
    # the reference norms its input: hand it the normed rows
    xn = ref.rms_norm(x[0], w["ffn_norm"], 1e-5)
    uncut, _, chose, _ = ref.moe_out(
        x[0], w, Weights, {"num_experts_per_tok": 3, "rms_norm_eps": 1e-5,
                           "num_local_experts": 8}, 0, {}, frozenset(),
        held=range(8))
    mine, _, _ = moe_mlp_dropless(xn[None], full, cfg)
    np.testing.assert_allclose(np.asarray(mine[0]), np.asarray(uncut),
                               atol=2e-5)
    assert chose.shape == (24, 3)


def test_a_layer_that_holds_all_its_experts_traces_todays_program():
    """``moe_router_experts`` None, or equal to ``num_experts``: the
    dropless layer's program is, equation for equation, the one a
    configuration without the field traces."""
    from megatron_llm_tpu.models.moe import moe_mlp_dropless

    cfg = _cfg(num_experts=8, moe_top_k=3)
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.zeros((2, 8, 32))
    live = jnp.ones((2, 8), bool)
    plain = str(jax.make_jaxpr(
        lambda q: moe_mlp_dropless(x, q, cfg, live))(p))
    same = str(jax.make_jaxpr(lambda q: moe_mlp_dropless(
        x, q, cfg.replace(moe_router_experts=8), live))(p))
    assert plain == same and not cfg.holds_a_share
    share, ps = _share(cfg, p, 2, 4)
    assert share.holds_a_share and share.routed_experts == 8
    assert str(jax.make_jaxpr(lambda q: moe_mlp_dropless(
        x, q, share, live))(ps)) != plain


def test_a_share_keeps_the_gates_of_all_the_choices_and_refuses_training():
    """A held choice's gate is what the router gave it over ALL the
    token's choices (no renormalising over the held ones), a token none
    of whose choices is held gets the shared MLP alone, and the capacity
    einsum refuses the share by name."""
    from megatron_llm_tpu.models.moe import moe_mlp_dropless

    cfg = _cfg(num_experts=4, moe_top_k=2)
    full = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    full = dict(full, experts=jax.tree_util.tree_map(lambda w: w * 6.0,
                                                     full["experts"]))
    # a router that always chooses experts 0 and 2, logits 2a and a
    full["router"] = {"kernel": jnp.zeros((32, 4)).at[0].set(
        jnp.asarray([2.0, -5.0, 1.0, -5.0]))}
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 32)) * 0.1
    x = x.at[..., 0].set(jnp.asarray([1.0, 2.0, 3.0]))
    c01, p01 = _share(cfg, full, 0, 2)
    c23, p23 = _share(cfg, full, 2, 2)
    c13, p13 = _share(cfg, full, 1, 2)
    whole, _, counts = moe_mlp_dropless(x, full, cfg)
    assert np.asarray(counts).tolist() == [3, 0, 3, 0]
    first = moe_mlp_dropless(x, p01, c01)[0]
    second = moe_mlp_dropless(x, p23, c23)[0]
    np.testing.assert_allclose(np.asarray(first + second),
                               np.asarray(whole), atol=1e-6)
    # experts 1-2 hold the second choice only: the same as experts 2-3
    np.testing.assert_allclose(np.asarray(moe_mlp_dropless(x, p13, c13)[0]),
                               np.asarray(second), atol=1e-6)
    # expert 0 alone under a gate of 1, times the gate the router gave it
    # over BOTH choices (softmax of 2 and 1), not over the held one
    alone = cfg.replace(num_experts=2, moe_top_k=1)
    only0 = moe_mlp_dropless(x, dict(p01, router={
        "kernel": full["router"]["kernel"][:, :2]}), alone)[0]
    gate = jax.nn.softmax(jnp.asarray([1.0, 2.0, 3.0])[:, None]
                          * jnp.asarray([2.0, 1.0])[None, :], axis=-1)[:, 0]
    assert np.abs(np.asarray(first)).max() > 0.01
    np.testing.assert_allclose(np.asarray(first),
                               np.asarray(gate[None, :, None] * only0),
                               atol=1e-6)
    with pytest.raises(NotImplementedError, match="moe_router_experts"):
        moe_mlp(x, p01, c01)
    with pytest.raises(ValueError, match="must lie among"):
        cfg.replace(moe_router_experts=4, moe_experts_first=3)


# ---------------------------------------------------------------------------
# the combine: the experts' rows back to their tokens, summed under the gates
# ---------------------------------------------------------------------------

def _combine_case(k, some_dead, share, shared, dtype, monkeypatch):
    """A dropless layer run eagerly with ``_grouped_matmul`` patched to
    fill the rows past ``sum(group_sizes)`` with NaN and to keep what it
    returned -> (out [T, h] fp32, the plain reference [T, h] fp32, the
    tokens that must come out as the shared MLP alone)."""
    from megatron_llm_tpu.models import moe

    cfg = _cfg(num_experts=8, moe_top_k=k, compute_dtype=dtype,
               params_dtype=dtype, moe_shared_experts=2 if shared else 0)
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, DTYPES[dtype])
    p = jax.tree_util.tree_map(lambda w: w * 6.0, p)
    if share:
        cfg, p = _share(cfg, p, 2, 3)       # experts 2, 3, 4 of the eight
    b, s = 2, 12
    T = b * s
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, 32))
    live = (jnp.arange(s)[None, :] < jnp.asarray([s, 7])[:, None]
            if some_dead else None)

    returned = []
    real = moe._grouped_matmul

    def poisoned(rows, weights, group_sizes):
        y = real(rows, weights, group_sizes)
        past = jnp.arange(rows.shape[0]) >= jnp.sum(group_sizes)
        returned.append(jnp.where(past[:, None], jnp.nan, y))
        return returned[-1]

    monkeypatch.setattr(moe, "_grouped_matmul", poisoned)
    out, _, _ = moe.moe_mlp_dropless(x, p, cfg, live)
    monkeypatch.undo()
    y = np.asarray(returned[1].astype(jnp.float32))         # [T*k, h] sorted
    assert y.shape == (T * k, 32) and returned[1].dtype == DTYPES[dtype]

    # the routing, by hand: who is live, whose expert is held, the order
    _, _, gates, idx = moe._route(x.reshape(T, 32), p, cfg)
    gates, idx = np.asarray(gates), np.asarray(idx)
    alive = (np.ones(T, bool) if live is None
             else np.asarray(live).reshape(T))
    first = cfg.moe_experts_first if share else 0
    held = alive[:, None] & (idx >= first) & (idx < first + cfg.num_experts)
    key = np.where(held, idx - first, cfg.num_experts + 1).reshape(T * k)
    where = np.empty(T * k, int)
    where[np.argsort(key, kind="stable")] = np.arange(T * k)
    assert np.isnan(y[held.sum():]).all() and np.isfinite(
        y[:held.sum()]).all()
    want = np.zeros((T, 32), np.float32)
    for j in range(k):                      # a choice at a time, in fp32
        for t in range(T):
            if held[t, j]:
                want[t] += gates[t, j] * y[where[t * k + j]]
    assert gates.dtype == np.float32 and want.dtype == np.float32
    alone = ~held.any(axis=1)
    if shared:
        want += np.asarray(dense_mlp(x, p["shared"], cfg).astype(
            jnp.float32)).reshape(T, 32)
    assert out.dtype == jnp.float32
    return np.asarray(out).reshape(T, 32), want, alone


@pytest.mark.parametrize("dtype", ["fp32", "bf16"])
@pytest.mark.parametrize("shared", [False, True])
@pytest.mark.parametrize("share", [False, True])
@pytest.mark.parametrize("some_dead", [False, True])
@pytest.mark.parametrize("k", [1, 2, 3, 6])
def test_the_combine_is_the_gated_sum_of_the_rows_the_experts_returned(
        k, some_dead, share, shared, dtype, monkeypatch):
    """THE COMBINE TEST: the routed sum is ``sum_j gates[t, j] *
    float32(y_of(t, j))`` over the rows the second grouped matmul
    returned, in ITS dtype, to fp32 rounding (only the order of a sum of
    ``k`` fp32 terms is the program's own); a dead token and a token none
    of whose experts is held come out as the shared MLP alone, EXACT
    zeros without one, whatever lies in the rows no group owns."""
    out, want, alone = _combine_case(k, some_dead, share, shared, dtype,
                                     monkeypatch)
    assert np.isfinite(out).all()
    scale = np.abs(want).max()
    assert scale > 0.05
    np.testing.assert_allclose(out, want, rtol=0, atol=4e-7 * k * scale)
    if some_dead or (share and k <= 2):
        assert alone.any()
    if not shared:
        assert (out[alone] == 0.0).all()
    else:
        assert (out[alone] == want[alone]).all()


def test_nothing_in_float32_has_a_row_an_assignment_under_moe_combine():
    """THE MECHANISM: in the lowered program of a bf16 layer no fp32 array
    under the scope ``moe_combine`` has ``T * k`` rows or the shape ``[T,
    k, h]`` in either order (the largest there is ``[T, h]``, the sum),
    and the rows move once, as ``[k * T, h]`` in the experts' dtype."""
    from _hlo_text import lowered_text
    from megatron_llm_tpu.hlo_collectives import instructions
    from megatron_llm_tpu.models.moe import moe_mlp_dropless

    cfg = _cfg(num_experts=8, moe_top_k=3, compute_dtype="bf16",
               params_dtype="bf16", moe_shared_experts=1)
    p = init_moe_mlp_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    b, s, h, k = 2, 20, 32, 3
    T = b * s
    x = jnp.zeros((b, s, h), jnp.bfloat16)
    live = jnp.ones((b, s), bool)
    rows = [r for r in instructions(lowered_text(jax.jit(
        lambda q: moe_mlp_dropless(x, q, cfg, live)).lower(p)))
            if r["scope"] == "moe_combine"]
    assert len(rows) > 3 * k
    fp32 = [r for r in rows if r["dtype"] == "f32"]
    assert max(math.prod(r["shape"]) for r in fp32) == T * h
    assert any(r["shape"] == (T, h) for r in fp32)
    for r in fp32:
        assert T * k not in r["shape"], r
        assert not {T, k, h} <= set(r["shape"]), r
    moved = [r for r in rows if r["shape"] == (k * T, h)]
    assert [r["opcode"] for r in moved] == ["gather"]
    assert moved[0]["dtype"] == "bf16"
