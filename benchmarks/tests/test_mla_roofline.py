"""``harness/mla_roofline.py``'s arithmetic and the source that reads the
launch records' latent-attention fields, on made-up records and a made-up
trace."""
import types

import pytest

from conftest import ROOT  # noqa: F401 - puts the repo on sys.path
from harness import mla_roofline, spec
from harness.context import Run
from harness.trace import DeviceTrace, Reduced
from harness.window import CounterSnapshot, Window

share = spec.load_module("sources", "mla_roofline_share")

KANANA = {"hidden_size": 2048, "num_attention_heads": 32,
          "num_key_value_heads": 32, "num_hidden_layers": 8,
          "kv_lora_rank": 512, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_a_decode_launchs_least_time_is_the_larger_of_bytes_and_operations():
    assert mla_roofline.key_bytes(KANANA) == 1152
    assert mla_roofline.absorbed_flops_per_key(KANANA) == 69632
    # nine rows at 17,000 tokens of context, 8 layers
    keys = 8 * 9 * 17001
    secs = mla_roofline.decode_least_seconds(KANANA, keys, PEAKS)
    # 1,152 B over 819 GB/s is 1.41 ns a key, 69,632 operations over 197
    # TFLOP/s 0.35 ns: the bytes bound it
    assert secs == pytest.approx(keys * 1152 / 819e9)
    assert 1.6e-3 < secs < 1.8e-3
    # a chip with a quarter of the MXU would be bound by the operations
    slow = dict(PEAKS, bf16_flops_per_s=197e12 / 8)
    assert mla_roofline.decode_least_seconds(KANANA, keys, slow) == \
        pytest.approx(keys * 69632 / (197e12 / 8))


def test_a_chunks_least_cost_is_the_expanded_forms_products():
    assert mla_roofline.expanded_flops_per_pair(KANANA) == 20480
    # a chunk of 512 at 16,384 tokens: query j sees 16,385 + j keys
    pairs = 8 * sum(16385 + j for j in range(512))
    secs = mla_roofline.prefill_least_seconds(KANANA, pairs, 16896, 8, PEAKS)
    assert secs == pytest.approx(pairs * 20480 / 197e12)
    assert 6.9e-3 < secs < 7.2e-3
    # a chunk of ONE live row over a long context reads more than it
    # multiplies: the context's latents once, a layer
    one = mla_roofline.prefill_least_seconds(KANANA, 8 * 6144, 6144, 8,
                                             PEAKS)
    assert one == pytest.approx(6144 * 1152 * 8 / 819e9)


def _rec(kind, begin, **fields):
    fields = {"mla_keys_live": 0, "mla_pairs": 0, "start": 0, "valid": 0,
              **fields}
    return types.SimpleNamespace(kind=kind, begin=begin, **fields)


def _run():
    run = Run(cell=types.SimpleNamespace(config=dict(KANANA)), seed=0,
              seconds=10.0, traced=True, rehearsal=False, process_start=0.0)
    run.window = Window(CounterSnapshot(10.0, {}), CounterSnapshot(20.0, {}))
    run.peaks = PEAKS
    run.model_shape = {}
    run.setup_parts["traced"] = (20.0, 23.0)
    return run


@pytest.fixture
def records(monkeypatch):
    recs = [
        _rec("decode", 19.0, mla_keys_live=999),
        _rec("decode", 20.5, mla_keys_live=8 * 9 * 17001),
        _rec("prefill", 21.0, start=16384, valid=512,
             mla_pairs=8 * sum(16385 + j for j in range(512))),
        _rec("decode", 22.0, mla_keys_live=8 * 2 * 9000),
        _rec("prefill", 24.0, start=0, valid=512, mla_pairs=1),
    ]
    prof = types.SimpleNamespace(records=lambda: recs)
    monkeypatch.setattr(share._loop, "profiler", lambda: prof)
    return recs


def test_shares_of_the_stretch_by_program(records):
    run = _run()
    ops = [("%mla_attention_decode.1 = bf16[8] custom-call()", 0.0, 0.004),
           ("%mla_attention_prefill.4 = bf16[8] custom-call()", 0.01, 0.04),
           ("%fusion.9 = f32[4]", 0.05, 0.5)]
    run.trace = Reduced((0.0, 1.0), [DeviceTrace("/device:TPU:0", ops)], {})
    decode = sum(mla_roofline.decode_least_seconds(KANANA, k, PEAKS)
                 for k in (8 * 9 * 17001, 8 * 2 * 9000))
    got = share.read(run, "decode", "^mla_attention_decode")
    assert got == pytest.approx(100.0 * decode / 0.004)
    assert 0 < got < 100.0
    prefill = mla_roofline.prefill_least_seconds(
        KANANA, 8 * sum(16385 + j for j in range(512)), 16896, 8, PEAKS)
    got = share.read(run, "prefill", "^mla_attention_prefill")
    assert got == pytest.approx(100.0 * prefill / 0.03)
    assert 0 < got < 100.0
    # nothing to read: no such operation, a record without the fields
    # (the parent), a model with no latent pool, no trace
    assert share.read(run, "decode", "^no_such_kernel") is None
    del records[1].mla_keys_live
    assert share.read(run, "decode", "^mla_attention_decode") is None
    for r in records:
        r.mla_keys_live = r.mla_pairs = 0
    assert share.read(run, "prefill", "^mla_attention_prefill") is None
    run.cell.config.pop("kv_lora_rank")
    assert share.read(run, "prefill", "^mla_attention_prefill") is None
    run.trace = None
    assert share.read(run, "decode", "^mla_attention_decode") is None
