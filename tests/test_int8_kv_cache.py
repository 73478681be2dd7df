"""int8 KV cache: quantized decode tracks the fp cache within the
per-entry quantization error, at half (vs bf16) / quarter (vs fp32)
the cache bytes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.text_generation.generation import (
    _forward_with_cache,
    generate_tokens,
    init_kv_caches,
)


@pytest.fixture(scope="module")
def model_and_params():
    cfg = llama_config("tiny", num_layers=2, seq_length=64,
                       max_position_embeddings=64, padded_vocab_size=64,
                       use_flash_attn=False)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_init_shapes_and_bytes(model_and_params):
    model, _ = model_and_params
    cfg = model.cfg
    fp = init_kv_caches(cfg, 2, 32)
    q8 = init_kv_caches(cfg, 2, 32, quantized=True)
    assert q8[0]["k_q"].dtype == jnp.int8
    assert q8[0]["k_q"].shape == fp[0]["k"].shape
    assert q8[0]["k_scale"].shape == fp[0]["k"].shape[:-1]
    d = fp[0]["k"].shape[-1]
    # int8 payload = 1 byte/entry + scales (1 fp32 per d entries):
    # vs fp32 k/v that is a 4x -> ~(1 + 4/d)x reduction
    q_bytes = q8[0]["k_q"].nbytes + q8[0]["k_scale"].nbytes
    assert q_bytes < fp[0]["k"].nbytes / 2


def test_forward_drift_bounded(model_and_params):
    """Prefill + one decode step through the int8 cache stays close to
    the fp cache logits."""
    model, params = model_and_params
    toks = jnp.asarray([[3, 5, 7, 9, 11, 13]], jnp.int32)
    nxt = jnp.asarray([[2]], jnp.int32)
    lf_all = []
    for quant in (False, True):
        caches = init_kv_caches(model.cfg, 1, 16, quantized=quant)
        _, caches = _forward_with_cache(model, params, toks, caches, 0)
        logits, _ = _forward_with_cache(model, params, nxt, caches,
                                        toks.shape[1])
        lf_all.append(np.asarray(logits[0, -1], np.float32))
    fp, q8 = lf_all
    scale = float(np.std(fp)) + 1e-6
    assert float(np.max(np.abs(q8 - fp))) / scale < 0.2


def test_generation_runs_and_keeps_prompt(model_and_params):
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 0]], jnp.int32)
    lens = jnp.asarray([4, 3], jnp.int32)
    out, n, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        max_new_tokens=8, min_prompt_len=3, greedy=True,
        int8_kv_cache=True)
    assert out.shape == (2, 12)
    # prompt survives (row 1's 4th slot is generated, not the pad)
    np.testing.assert_array_equal(np.asarray(out[0, :4]),
                                  np.asarray(toks[0]))
    assert int(jnp.asarray(n).reshape(-1)[0]) > 0


def test_chunked_prefill_path(model_and_params):
    """The micro-batched prefill reshape handles the quantized cache
    layout (generic over cache keys)."""
    model, params = model_and_params
    toks = jnp.asarray([[1, 2, 3, 4]] * 4, jnp.int32)
    lens = jnp.full((4,), 4, jnp.int32)
    out_plain, _, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        max_new_tokens=4, min_prompt_len=4, greedy=True,
        int8_kv_cache=True)
    out_chunked, _, _ = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(0),
        max_new_tokens=4, min_prompt_len=4, greedy=True,
        int8_kv_cache=True, batch_times_seqlen_threshold=8)
    np.testing.assert_array_equal(np.asarray(out_plain),
                                  np.asarray(out_chunked))


def test_rolling_plus_int8_refused(model_and_params):
    model, _ = model_and_params
    cfg = model.cfg.replace(sliding_window_size=8)
    with pytest.raises(AssertionError):
        init_kv_caches(cfg, 1, 32, rolling=True, quantized=True)


def test_paged_int8_sliding_window_drift_bounded(model_and_params):
    """int8 PAGED pools combined with a sliding window (the serving
    engine's XLA gather branch) track the float linear cache within the
    quantization drift bound — the window mask and the in-gather
    dequant compose."""
    from megatron_llm_tpu.models.language_model import language_model_forward
    from megatron_llm_tpu.models.llama import LlamaModel
    from megatron_llm_tpu.ops import paged_kv

    model, params = model_and_params
    wcfg = model.cfg.replace(sliding_window_size=8)
    toks = jnp.asarray([[3, 5, 7, 9, 11, 13, 2, 4, 6, 8, 10, 12]],
                       jnp.int32)                  # 12 tokens > window 8
    nxt = jnp.asarray([[2]], jnp.int32)
    # baseline: float LINEAR cache through the same windowed config
    wmodel = LlamaModel(wcfg)
    caches = init_kv_caches(wcfg, 1, 16)
    _, caches = _forward_with_cache(wmodel, params, toks, caches, 0)
    logits_fp, _ = _forward_with_cache(wmodel, params, nxt, caches,
                                       toks.shape[1])
    fp = np.asarray(logits_fp[0, -1], np.float32)
    # int8 paged pools: prefill then one decode step through the paged
    # branch (block table covers 13 tokens at block_size 8 -> 2 pages)
    bs, M = 8, 2
    pages = paged_kv.init_pools(wcfg, 1 + M, bs, quantized=True)
    bt = jnp.asarray(np.arange(1, M + 1)[None, :], jnp.int32)
    caches = paged_kv.step_caches(
        pages, bt, jnp.zeros((1,), jnp.int32),
        jnp.asarray([toks.shape[1]], jnp.int32), "xla")
    positions = jnp.arange(toks.shape[1])[None, :]
    _, caches = language_model_forward(params, toks, positions, None,
                                       wcfg, rng_key=None, train=False,
                                       kv_caches=caches)
    caches = paged_kv.step_caches(
        paged_kv.pools_of(caches), bt,
        jnp.asarray([toks.shape[1]], jnp.int32),
        jnp.ones((1,), jnp.int32), "xla")
    logits_q, _ = language_model_forward(
        params, nxt, jnp.asarray([[toks.shape[1]]], jnp.int32), None,
        wcfg, rng_key=None, train=False, kv_caches=caches)
    q8 = np.asarray(logits_q[0, -1], np.float32)
    scale = float(np.std(fp)) + 1e-6
    assert float(np.max(np.abs(q8 - fp))) / scale < 0.2
