"""FLOP and byte counts against hand-worked numbers for one Mistral
layer (hidden 4096, 32 heads of 128, 8 KV heads, FFN 14336, vocab
32000)."""
import json
import os

import pytest

from conftest import BENCH
from harness import roofline, spec

CFG = {"hidden_size": 4096, "num_attention_heads": 32,
       "num_key_value_heads": 8, "intermediate_size": 14336,
       "vocab_size": 32000, "num_hidden_layers": 1, "sliding_window": 4096}
MOE = dict(CFG, num_local_experts=8, num_experts_per_tok=2,
           sliding_window=None)

# by hand: q 4096x4096, k and v 4096x1024 each, o 4096x4096
ATTN = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096          # 41,943,040
MLP = 3 * 4096 * 14336                                      # 176,160,768
HEAD = 32000 * 4096                                         # 131,072,000
KV_TOKEN = 2 * 8 * 128 * 2                                  # 4096 bytes


def test_parameter_counts():
    assert roofline.attention_params(CFG) == ATTN == 41_943_040
    assert roofline.expert_params(CFG) == MLP == 176_160_768
    assert roofline.layer_params(CFG) == ATTN + MLP == 218_103_808
    assert roofline.layer_params(MOE) == ATTN + 8 * MLP + 4096 * 8
    assert roofline.active_layer_params(MOE) == ATTN + 2 * MLP + 4096 * 8
    full = dict(CFG, num_hidden_layers=32)
    # Mistral-7B: 7,241,732,096 parameters
    assert roofline.total_params(full) == 32 * (ATTN + MLP) + 2 * HEAD \
        + 65 * 4096 == 7_241_732_096
    assert roofline.kv_bytes_per_token(CFG) == KV_TOKEN


def test_decode_step_one_layer():
    flops, nbytes = roofline.decode_step_cost(CFG, [99, 299])
    # two rows; each sees its cache plus the new token: 100 and 300 keys
    want_flops = 2 * 2 * (ATTN + MLP + HEAD) + 4 * 32 * 128 * 400
    want_bytes = (ATTN + MLP) * 2 + HEAD * 2 + (400 + 2) * KV_TOKEN
    assert flops == pytest.approx(want_flops)
    assert nbytes == pytest.approx(want_bytes)


def test_decode_context_is_cut_by_the_window():
    a, _ = roofline.decode_step_cost(CFG, [4095])
    b, _ = roofline.decode_step_cost(CFG, [9000])
    assert a == b


def test_prefill_chunk_one_layer():
    flops, nbytes = roofline.prefill_chunk_cost(CFG, 128, 64)
    seen = sum(128 + i + 1 for i in range(64))              # 10,272
    assert flops == pytest.approx(2 * 64 * (ATTN + MLP)
                                  + 4 * 32 * 128 * seen)
    assert nbytes == pytest.approx((ATTN + MLP) * 2
                                   + (192 + 64) * KV_TOKEN)


def test_experts_touched():
    assert roofline.experts_touched(CFG, 10) == 1.0
    assert roofline.experts_touched(MOE, 1) == pytest.approx(
        8 * (1 - (7 / 8) ** 2))
    assert roofline.experts_touched(MOE, 64) == pytest.approx(8.0, abs=1e-6)


def test_train_flops_per_token():
    # window >= sequence: a query sees (s + 1) / 2 keys on average
    got = roofline.train_flops_per_token(CFG, 4096)
    want = 3 * (2 * (ATTN + MLP + HEAD) + 4 * 32 * 128 * 4097 / 2)
    assert got == pytest.approx(want)


def test_least_seconds_says_which_bound():
    peaks = spec.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    t, bound = roofline.least_seconds(197e12, 819e9 * 2, peaks)
    assert (t, bound) == (2.0, "bandwidth")
    t, bound = roofline.least_seconds(197e12 * 3, 819e9, peaks)
    assert (t, bound) == (3.0, "compute")
    # a 16-layer Mistral decode step is bound by reading 7 GB of weights
    cfg = dict(CFG, num_hidden_layers=16)
    t, bound = roofline.least_seconds(
        *roofline.decode_step_cost(cfg, [500] * 12), peaks)
    assert bound == "bandwidth" and 0.008 < t < 0.010


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        spec.peaks_for("some other chip")
