"""Mixture-of-experts: routing semantics, dense parity, sharding, and the
model/trainer integration (TPU-native extension — the reference has no MoE,
SURVEY §2.2 "expert parallel: absent")."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.config import TransformerConfig
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
