"""End-to-end training smoke: loss decreases; checkpoint save/resume
reproduces the exact state (reference analogue: getting-started run +
checkpointing.py semantics)."""

import dataclasses
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu import checkpointing, topology
from megatron_llm_tpu.config import ParallelConfig, TrainConfig
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.optimizer import MegatronOptimizer
from megatron_llm_tpu.optimizer.optimizer import map_param_trees
from megatron_llm_tpu.parallel import glu_pairs, sharding as sh
from megatron_llm_tpu.training import build_train_step, pretrain


def _setup(utils, tp=2):
    cfg = llama_config("tiny", seq_length=32, max_position_embeddings=32,
                       padded_vocab_size=128)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = utils.initialize_model_parallel(tp=tp)
    params = sh.shard_params(params, model.param_specs(params))
    rng = np.random.RandomState(0)
    fixed = jnp.asarray(rng.randint(0, 128, size=(2, 8, 32)))
    dsh = NamedSharding(mesh, P(None, "dp", None))

    def it():
        while True:
            toks = jax.device_put(fixed, dsh)
            yield {
                "tokens": toks,
                "labels": jnp.roll(toks, -1, axis=-1),
                "loss_mask": jax.device_put(jnp.ones_like(fixed, jnp.float32), dsh),
            }

    return cfg, model, params, mesh, it


def test_loss_decreases(utils):
    cfg, model, params, mesh, it = _setup(utils)
    tc = TrainConfig(micro_batch_size=2, global_batch_size=16, train_iters=12,
                     lr=1e-2, optimizer="adam", seed=3)
    pc = ParallelConfig(tensor_model_parallel_size=2, data_parallel_size=4,
                        sequence_parallel=True)
    losses = []
    params, opt_state, _ = pretrain(
        model, params, tc, pc, it(), log_interval=0,
        on_metrics=lambda i, m: losses.append(float(m["lm loss"])),
    )
    opt = MegatronOptimizer(tc)
    step = build_train_step(model, opt, pc, 2, forward_only=True)
    final = float(step(params, next(it()), None))
    assert final < 2.0, f"loss did not decrease: {final}"


@pytest.mark.parametrize("paired", [False, True], ids=["flat", "paired"])
def test_checkpoint_resume_exact(utils, paired):
    """``paired``: the tree as finetune.py holds it at tp 2
    (parallel/glu_pairs.py), saved flat, killed, loaded flat and paired
    again: the run goes on bit for bit."""
    cfg, model, params, mesh, it = _setup(utils)
    if paired:
        params = glu_pairs.for_trainer(params)
        assert glu_pairs.count(params) == (1, 0)
    tc = TrainConfig(micro_batch_size=2, global_batch_size=16, train_iters=4,
                     lr=1e-3, optimizer="adam", seed=5)
    pc = ParallelConfig(tensor_model_parallel_size=2, data_parallel_size=4,
                        sequence_parallel=True)

    d = tempfile.mkdtemp()
    try:
        # run 2 iters, save, run 2 more
        p2, o2, _ = pretrain(model, params, dataclasses.replace(tc, train_iters=2),
                             pc, it(), log_interval=0)
        checkpointing.save_checkpoint(d, 2, p2, o2)

        # abstract templates of the flat tree the checkpoint holds
        # (shape/dtype/sharding metadata, read before p2's buffers are
        # donated)
        def tmpl(tree):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding),
                glu_pairs.flat(tree))

        p_tmpl, o_tmpl = tmpl(p2), map_param_trees(tmpl, o2)
        p4a, _, _ = pretrain(model, p2, tc, pc, it(), log_interval=0,
                             start_iteration=2, opt_state=o2)

        # load from checkpoint and run the same 2 iters
        pl, ol, meta = checkpointing.load_checkpoint(
            d, params_template=p_tmpl, opt_state_template=o_tmpl)
        assert meta["iteration"] == 2
        assert glu_pairs.count(pl) == (0, 1)
        if paired:
            pl = glu_pairs.for_trainer(pl)
            ol = map_param_trees(glu_pairs.for_trainer, ol)
        pl = sh.shard_params(pl, model.param_specs(pl))
        p4b, _, _ = pretrain(model, pl, tc, pc, it(), log_interval=0,
                             start_iteration=2, opt_state=ol)

        for a, b in zip(jax.tree_util.tree_leaves(p4a),
                        jax.tree_util.tree_leaves(p4b)):
            if paired:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            else:
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(jnp.asarray(b)), atol=1e-6)
    finally:
        shutil.rmtree(d)
