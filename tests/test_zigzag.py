"""Zigzag ring attention (cp algorithm #3): redistribution round trip,
op-level exactness vs full attention, GQA/window, gradients, and parity
with the plain ring.  (Load-balanced causal CP — the reference has no
context parallelism at all; SURVEY §5.7.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu import topology
from megatron_llm_tpu.ops.pallas.flash_attention import _reference_attention
from megatron_llm_tpu.parallel.ring_attention import (
    context_parallel_attention,
)
from megatron_llm_tpu.parallel.zigzag_ring import (
    _from_zigzag,
    _to_zigzag,
    zigzag_context_attention,
)


def _qkv(b=2, s=128, nh=4, ng=4, d=32, seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s, nh, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, s, ng, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, s, ng, d).astype(np.float32)) * 0.3
    return q, k, v


def test_zigzag_redistribution_round_trip(utils):
    """to_zigzag places half-chunk pair (r, 2P-1-r) on rank r, and
    from_zigzag restores the contiguous layout exactly."""
    utils.initialize_model_parallel(tp=1, pp=1, cp=4)
    x = jnp.arange(2 * 64 * 1 * 1, dtype=jnp.float32).reshape(2, 64, 1, 1)

    def body(xl):
        low, high = _to_zigzag(xl, topology.CP_AXIS, 4)
        g = jax.lax.axis_index(topology.CP_AXIS)
        # low must be global half-chunk g, high chunk 2P-1-g (cs = 8)
        cs = 8
        lo_ok = jnp.all(low[:, :, 0, 0] == xl_global_chunk(x, g, cs))
        hi_ok = jnp.all(high[:, :, 0, 0]
                        == xl_global_chunk(x, 2 * 4 - 1 - g, cs))
        back = _from_zigzag(low, high, topology.CP_AXIS, 4)
        return back, jnp.stack([lo_ok, hi_ok])

    def xl_global_chunk(x_full, c, cs):
        return jax.lax.dynamic_slice_in_dim(
            x_full[:, :, 0, 0], c * cs, cs, axis=1)

    mesh = topology.get_mesh()
    spec = P(None, "cp", None, None)
    back, oks = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=spec,
        out_specs=(spec, P("cp")), check_vma=False))(x)
    assert bool(jnp.all(oks)), np.asarray(oks)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


@pytest.mark.parametrize("window", [None, 48])
def test_zigzag_matches_full_attention(utils, window):
    utils.initialize_model_parallel(tp=1, pp=1, cp=4)
    q, k, v = _qkv()
    ref = _reference_attention(q, k, v, True, window, 0.125)
    out = jax.jit(
        lambda q, k, v: zigzag_context_attention(
            q, k, v, causal=True, sliding_window=window,
            softmax_scale=0.125))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_zigzag_gqa(utils):
    utils.initialize_model_parallel(tp=1, pp=1, cp=4)
    q, k, v = _qkv(nh=8, ng=2)
    ref = _reference_attention(q, k, v, True, None, 0.125)
    out = jax.jit(
        lambda q, k, v: zigzag_context_attention(
            q, k, v, causal=True, softmax_scale=0.125))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_zigzag_matches_ring(utils):
    utils.initialize_model_parallel(tp=1, pp=1, cp=4)
    q, k, v = _qkv(seed=3)
    ring = jax.jit(
        lambda q, k, v: context_parallel_attention(
            q, k, v, causal=True, softmax_scale=0.125))(q, k, v)
    zig = jax.jit(
        lambda q, k, v: zigzag_context_attention(
            q, k, v, causal=True, softmax_scale=0.125))(q, k, v)
    np.testing.assert_allclose(np.asarray(zig), np.asarray(ring),
                               atol=2e-5)


def test_zigzag_grads_match_reference(utils):
    utils.initialize_model_parallel(tp=1, pp=1, cp=4)
    q, k, v = _qkv(s=64)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    g_ref = jax.grad(loss(lambda q, k, v: _reference_attention(
        q, k, v, True, None, 0.125)), argnums=(0, 1, 2))(q, k, v)
    g_zig = jax.jit(jax.grad(loss(lambda q, k, v: zigzag_context_attention(
        q, k, v, causal=True, softmax_scale=0.125)),
        argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_zig, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5)


def test_zigzag_model_loss_matches_ring(utils):
    """Model-level: --context_parallel_algo=zigzag trains to the same
    loss as ring on identical weights/batch (cp=2 x dp=2 x tp=2)."""
    from megatron_llm_tpu.config import ParallelConfig, TrainConfig
    from megatron_llm_tpu.models.llama import LlamaModel, llama_config
    from megatron_llm_tpu.optimizer import MegatronOptimizer
    from megatron_llm_tpu.parallel import sharding as sh
    from megatron_llm_tpu.training import build_train_step

    def run(algo):
        utils.initialize_model_parallel(tp=2, pp=1, cp=2)
        try:
            cfg = llama_config(
                "tiny", num_layers=2, seq_length=32,
                max_position_embeddings=32, padded_vocab_size=128,
                params_dtype="bf16", compute_dtype="bf16",
                context_parallel_algo=algo)
            model = LlamaModel(cfg)
            params = model.init(jax.random.PRNGKey(0))
            params = sh.shard_params(params, model.param_specs(params))
            tc = TrainConfig(micro_batch_size=1, global_batch_size=2,
                             train_iters=0, lr=1e-3, optimizer="adam",
                             bf16=True, clip_grad=1.0)
            opt = MegatronOptimizer(tc, params_dtype=jnp.bfloat16)
            os_ = opt.init(params)
            pc = ParallelConfig(tensor_model_parallel_size=2,
                                data_parallel_size=2,
                                context_parallel_size=2,
                                sequence_parallel=True)
            step = build_train_step(model, opt, pc, 1)
            rng = np.random.RandomState(0)
            toks = jnp.asarray(rng.randint(0, 128, (1, 2, 32)))
            batch = {"tokens": toks,
                     "labels": jnp.roll(toks, -1, -1),
                     "loss_mask": jnp.ones_like(toks, jnp.float32)}
            _, _, metrics = step(params, os_, batch,
                                 jax.random.PRNGKey(0), 1e-3, 0.0)
            return float(metrics["lm loss"])
        finally:
            utils.destroy_model_parallel()

    loss_ring = run("ring")
    loss_zig = run("zigzag")
    assert np.isfinite(loss_zig)
    assert abs(loss_zig - loss_ring) < 1e-3, (loss_zig, loss_ring)


def test_zigzag_q_chunked_exact(utils):
    """Interior q-chunking (qc < half-chunk) stays exact — the memory
    bound that lets zigzag run at long local sequences."""
    utils.initialize_model_parallel(tp=1, pp=1, cp=4)
    q, k, v = _qkv(seed=5)
    ref = _reference_attention(q, k, v, True, None, 0.125)
    out = jax.jit(
        lambda q, k, v: zigzag_context_attention(
            q, k, v, causal=True, softmax_scale=0.125,
            q_chunk_size=8))(q, k, v)   # cs=16 -> 2 chunks per sub-block
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
