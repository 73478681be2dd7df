"""The two forms of a gated MLP's first projection (``parallel/glu_pairs.py``,
PR 50): the trainer's paired tree computes what the flat tree computes, and
only the flat tree ever leaves the trainer.

WHERE the pair saves collectives is held beside the step's other placements
(``test_dp_grad_reduction.py::test_a_gated_mlp_trades_nothing_over_tp``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu import checkpointing, topology
from megatron_llm_tpu.config import ParallelConfig, TrainConfig
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.models.transformer import init_mlp_params, mlp
from megatron_llm_tpu.ops.activations import GLU_ACTIVATIONS
from megatron_llm_tpu.optimizer import MegatronOptimizer
from megatron_llm_tpu.optimizer.optimizer import (
    _no_weight_decay,
    map_param_trees,
)
from megatron_llm_tpu.parallel import glu_pairs, sharding as sh
from megatron_llm_tpu.training import build_train_step
from test_dp_grad_reduction import _close, _mesh, _sequences

SEQ, VOCAB = 32, 128


def _llama(**kw):
    return LlamaModel(llama_config(
        "tiny", seq_length=SEQ, max_position_embeddings=SEQ,
        padded_vocab_size=VOCAB, **kw))


def _first(tree):
    return tree["transformer"]["layers"]["mlp"][glu_pairs.FIRST]


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("activation", sorted(GLU_ACTIVATIONS))
def test_mlp_on_a_paired_kernel_is_mlp_on_its_flat_reshape(activation, bias):
    cfg = dataclasses.replace(
        _llama().cfg, glu_activation=activation, add_bias_linear=bias)
    flat = init_mlp_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    if bias:    # (born zero)
        flat[glu_pairs.FIRST]["bias"] = jax.random.normal(
            jax.random.PRNGKey(1), flat[glu_pairs.FIRST]["bias"].shape)
    paired = glu_pairs.pair({"mlp": flat})["mlp"]
    f = cfg.ffn_hidden_size
    assert paired[glu_pairs.FIRST]["kernel"].shape == (2, cfg.hidden_size, f)
    # [gate | up] -> [0] the gate, [1] the up
    np.testing.assert_array_equal(
        paired[glu_pairs.FIRST]["kernel"][1],
        flat[glu_pairs.FIRST]["kernel"][:, f:])
    if bias:
        np.testing.assert_array_equal(
            paired[glu_pairs.FIRST]["bias"][0],
            flat[glu_pairs.FIRST]["bias"][:f])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.hidden_size))
    np.testing.assert_array_equal(
        jax.jit(lambda p: mlp(x, p, cfg))(paired),
        jax.jit(lambda p: mlp(x, p, cfg))(flat))
    back = glu_pairs.flat({"mlp": paired})["mlp"]
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(flat)):
        np.testing.assert_array_equal(a, b)


def test_what_has_no_pair_keeps_its_form():
    """A non-gated MLP, the experts' shared MLP, a projection with LoRA
    leaves or int8 scales, an adapter tree's placeholders."""
    from megatron_llm_tpu.quantization import quantize_linear_weights_int8

    k = jnp.zeros((2, 8, 32))
    gated = {glu_pairs.FIRST: {"kernel": k},
             glu_pairs.SECOND: {"kernel": jnp.zeros((2, 16, 8))}}
    plain = {glu_pairs.FIRST: {"kernel": k},
             glu_pairs.SECOND: {"kernel": jnp.zeros((2, 32, 8))}}
    lora = {**gated, glu_pairs.FIRST: {
        "kernel": k, "lora_A": jnp.zeros((2, 8, 2)),
        "lora_B": jnp.zeros((2, 2, 32)), "lora_scale": jnp.ones((2,))}}
    int8 = quantize_linear_weights_int8(gated, min_params=1)
    assert "kernel_q" in int8[glu_pairs.FIRST]
    tree = {"a": {"mlp": gated}, "b": {"mlp": plain}, "c": {"mlp": lora},
            "d": {"mlp": int8},
            "e": {"mlp": {"experts": {}, "shared": gated}},
            "f": {"mlp": {glu_pairs.FIRST: None, glu_pairs.SECOND: None}}}
    assert glu_pairs.count(tree) == (0, 3)
    paired = glu_pairs.pair(tree)
    assert glu_pairs.count(paired) == (1, 2)
    assert paired["a"]["mlp"][glu_pairs.FIRST]["kernel"].ndim == 4
    for name in "bcdef":
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: a is b, paired[name], tree[name])), name
    assert glu_pairs.count(glu_pairs.flat(paired)) == (0, 3)


def test_a_host_snapshot_changes_form_on_the_host():
    """A rescue save flattens host numpy from the watchdog's thread
    (``resilience.save_rescue``): no device array comes of it."""
    k = np.arange(2 * 3 * 8, dtype=np.float32).reshape(2, 3, 8)
    tree = {"mlp": {
        glu_pairs.FIRST: {"kernel": k, "bias": np.arange(16.).reshape(2, 8)},
        glu_pairs.SECOND: {"kernel": np.zeros((2, 4, 3), np.float32)}}}
    paired = glu_pairs.pair(tree)
    first = paired["mlp"][glu_pairs.FIRST]
    assert first["kernel"].shape == (2, 2, 3, 4)
    assert first["bias"].shape == (2, 2, 4)
    np.testing.assert_array_equal(first["kernel"][:, 1], k[..., 4:])
    back = glu_pairs.flat(paired)["mlp"][glu_pairs.FIRST]
    for name, leaf in tree["mlp"][glu_pairs.FIRST].items():
        assert type(first[name]) is type(back[name]) is np.ndarray
        np.testing.assert_array_equal(back[name], leaf)


def test_the_trainers_tree_is_the_flat_init_reshaped():
    """``sh.init_params(model, key)`` stays the public tree (the
    benchmark's reference reads it); with the trainer's form the same
    numbers are born paired, on the pair's own shards, in one program."""
    _mesh(tp=2, dp=2)
    model = _llama()
    key = jax.random.PRNGKey(7)
    flat = sh.init_params(model, key)
    assert glu_pairs.count(flat) == (0, 1)
    held = sh.init_params(model, key, form=glu_pairs.for_trainer)
    assert glu_pairs.count(held) == (1, 0)
    # a shard of F holds both halves of its columns
    assert tuple(_first(held)["kernel"].sharding.spec) == (
        "pp", None, None, "tp")
    assert model.param_specs(held)["transformer"]["layers"]["mlp"][
        glu_pairs.FIRST]["kernel"][-1] == "ffn"
    for a, b in zip(jax.tree_util.tree_leaves(glu_pairs.flat(held)),
                    jax.tree_util.tree_leaves(flat)):
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        np.testing.assert_array_equal(a, b)
    # at tp 1 nothing is converted
    _mesh(tp=1, dp=2)
    assert glu_pairs.count(sh.init_params(
        model, key, form=glu_pairs.for_trainer)) == (0, 1)


def _two_steps(paired: bool, weight_decay=0.0):
    """Two SGD steps of lr 0.5 at tp 2 (sequence parallel) x dp 2 on the
    tree in one form: each step's metrics and gradient (flat form)."""
    mesh = _mesh(tp=2, dp=2)
    model = _llama()
    params = sh.init_params(
        model, jax.random.PRNGKey(0),
        form=glu_pairs.for_trainer if paired else None)
    assert glu_pairs.count(params) == ((1, 0) if paired else (0, 1))
    tc = TrainConfig(micro_batch_size=1, global_batch_size=8, lr=0.5,
                     optimizer="sgd", sgd_momentum=0.0, clip_grad=1.0,
                     weight_decay=weight_decay)
    pc = ParallelConfig(tensor_model_parallel_size=2, data_parallel_size=2,
                        sequence_parallel=True)
    opt = MegatronOptimizer(tc)
    dsh = NamedSharding(mesh, P(None, "dp", None))
    batch = {k: jax.device_put(jnp.asarray(v).reshape(4, 2, SEQ), dsh)
             for k, v in zip(("tokens", "labels", "loss_mask"),
                             _sequences("uneven_mask"))}
    step = build_train_step(model, opt, pc, 4)
    opt_state, out = opt.init(params), []
    for i in range(2):
        before = jax.device_get(glu_pairs.flat(params))
        params, opt_state, m = step(params, opt_state, batch,
                                    jax.random.PRNGKey(i), 0.5, weight_decay)
        after = jax.device_get(glu_pairs.flat(params))
        out.append(({k: float(v) for k, v in m.items()},
                    jax.tree_util.tree_map(lambda a, b: a - b, before,
                                           after)))
    return out


@pytest.mark.parametrize("weight_decay", [0.0, 0.1], ids=["", "decayed"])
def test_two_steps_on_the_paired_tree_are_the_flat_trees(weight_decay):
    """Loss, gradient norm (the clip's) and every leaf's update, with the
    weight decay's mask applied to the kernel in either form."""
    for (mp, gp), (mf, gf) in zip(_two_steps(True, weight_decay),
                                  _two_steps(False, weight_decay)):
        assert set(mp) == set(mf)
        for k in mf:
            assert mp[k] == pytest.approx(mf[k], rel=1e-5, abs=1e-7), k
        for a, b in zip(jax.tree_util.tree_leaves(gp),
                        jax.tree_util.tree_leaves(gf)):
            _close(a, b)


def test_the_weight_decay_mask_reads_the_paired_leaf_as_the_flat():
    cfg = dataclasses.replace(_llama().cfg, add_bias_linear=True)
    flat = {"layers": {"mlp": jax.eval_shape(
        lambda k: init_mlp_params(k, cfg, jnp.float32),
        jax.random.PRNGKey(0))}}

    def mask(tree):
        return {jax.tree_util.keystr(p): _no_weight_decay(p, leaf)
                for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}

    paired = jax.eval_shape(glu_pairs.pair, flat)
    assert glu_pairs.count(paired) == (1, 0)
    assert mask(paired) == mask(flat)
    assert not mask(flat)["['layers']['mlp']['dense_h_to_4h']['kernel']"]
    assert mask(flat)["['layers']['mlp']['dense_h_to_4h']['bias']"]


# ---------------------------------------------------------------------------
# the doors
# ---------------------------------------------------------------------------

def _first_loss(model, params, tp):
    mesh = topology.get_mesh()
    pc = ParallelConfig(tensor_model_parallel_size=tp,
                        data_parallel_size=2, sequence_parallel=tp > 1)
    opt = MegatronOptimizer(TrainConfig(micro_batch_size=2,
                                        global_batch_size=8))
    dsh = NamedSharding(mesh, P(None, "dp", None))
    batch = {k: jax.device_put(jnp.asarray(v).reshape(2, 4, SEQ), dsh)
             for k, v in zip(("tokens", "labels", "loss_mask"),
                             _sequences("dense"))}
    return float(build_train_step(model, opt, pc, 2, forward_only=True)(
        params, batch, None))


def test_a_checkpoint_from_a_tp2_trainer_is_the_public_tree(tmp_path):
    """Saved from the paired tree (with its optimizer's trees), the
    checkpoint holds ``dense_h_to_4h.kernel [L, h, 2F]`` in ``[gate | up]``
    order, every leaf bit for bit the flat init's, and loads at tp 1 and
    at tp 2 to the same first loss."""
    _mesh(tp=2, dp=2)
    model = _llama()
    key = jax.random.PRNGKey(3)
    held = sh.init_params(model, key, form=glu_pairs.for_trainer)
    opt = MegatronOptimizer(TrainConfig(micro_batch_size=2,
                                        global_batch_size=8, bf16=True),
                            params_dtype=jnp.bfloat16)
    opt_state = opt.init(held)
    assert glu_pairs.count(opt_state.master_params) == (1, 0)
    want = _first_loss(model, held, tp=2)
    checkpointing.save_checkpoint(str(tmp_path), 1, held, opt_state)

    cfg = model.cfg
    loaded, _, _ = checkpointing.load_checkpoint(str(tmp_path))
    assert _first(loaded)["kernel"].shape == (
        cfg.num_layers, cfg.hidden_size, 2 * cfg.ffn_hidden_size)
    public = jax.device_get(sh.init_params(model, key))
    for a, b in zip(jax.tree_util.tree_leaves(loaded),
                    jax.tree_util.tree_leaves(public)):
        np.testing.assert_array_equal(np.asarray(a), b)
    # the optimizer's trees went out flat too: a flat template takes them
    flat_opt = jax.eval_shape(opt.init, public)
    _, opt_loaded, _ = checkpointing.load_checkpoint(
        str(tmp_path), load_params=False, opt_state_template=flat_opt)
    assert glu_pairs.count(opt_loaded.master_params) == (0, 1)
    assert glu_pairs.count(map_param_trees(
        glu_pairs.pair, opt_loaded).exp_avg_sq) == (1, 0)

    for tp in (1, 2):
        _mesh(tp=tp, dp=2)
        placed = glu_pairs.for_trainer(
            sh.shard_params(loaded, model.param_specs(loaded)))
        assert glu_pairs.count(placed) == ((1, 0) if tp == 2 else (0, 1))
        if tp == 2:
            kernel = _first(placed)["kernel"]
            assert kernel.sharding.is_equivalent_to(
                _first(held)["kernel"].sharding, kernel.ndim)
        assert _first_loss(model, placed, tp) == pytest.approx(
            want, rel=1e-5)


def _lora_step_runs(adapter, lora):
    opt = MegatronOptimizer(TrainConfig(
        micro_batch_size=2, global_batch_size=8, lr=1e-2, optimizer="adam"))
    pc = ParallelConfig(tensor_model_parallel_size=2, data_parallel_size=2,
                        sequence_parallel=True)
    dsh = NamedSharding(topology.get_mesh(), P(None, "dp", None))
    batch = {k: jax.device_put(jnp.asarray(v).reshape(2, 4, SEQ), dsh)
             for k, v in zip(("tokens", "labels", "loss_mask"),
                             _sequences("dense"))}
    step = build_train_step(adapter, opt, pc, 2)
    moved, _, m = step(lora, opt.init(lora), batch, jax.random.PRNGKey(1),
                       1e-2, 0.0)
    assert np.isfinite(float(m["lm loss"]))
    assert float(jnp.abs(jax.tree_util.tree_leaves(moved)[1]).max()) > 0


def test_a_projection_with_lora_leaves_stays_flat_and_trains():
    from megatron_llm_tpu.lora import LoraAdapter, attach_lora

    _mesh(tp=2, dp=2)
    model = _llama(use_flash_attn=False)
    base = sh.init_params(model, jax.random.PRNGKey(0))
    adapter = LoraAdapter(model, base)
    lora = adapter.init_lora(4, jax.random.PRNGKey(1),
                             targets=("dense", glu_pairs.FIRST))
    lora = sh.shard_params(lora, adapter.param_specs(lora))
    attached = attach_lora(base, lora)
    assert glu_pairs.count(glu_pairs.for_trainer(attached)) == (0, 1)
    assert _first(glu_pairs.for_trainer(attached))["kernel"].ndim == 3
    _lora_step_runs(adapter, lora)


def test_a_projection_with_int8_scales_stays_flat_and_trains():
    """Adapters on the attention over a frozen int8 base."""
    from megatron_llm_tpu.lora import LoraAdapter
    from megatron_llm_tpu.quantization import (
        quantize_linear_weights_int8,
        quantize_param_specs,
    )

    _mesh(tp=2, dp=2)
    model = _llama(use_flash_attn=False)
    params = model.init(jax.random.PRNGKey(0))
    qparams = quantize_linear_weights_int8(params, min_params=1)
    base = glu_pairs.for_trainer(sh.shard_params(
        qparams, quantize_param_specs(model.param_specs(params), qparams)))
    assert "kernel_q" in _first(base) and glu_pairs.count(base) == (0, 1)
    lora = LoraAdapter(model, params).init_lora(4, jax.random.PRNGKey(1))
    adapter = LoraAdapter(model, base)
    adapter.param_specs = lambda l: LoraAdapter(model, params).param_specs(l)
    _lora_step_runs(adapter, sh.shard_params(lora, adapter.param_specs(lora)))
