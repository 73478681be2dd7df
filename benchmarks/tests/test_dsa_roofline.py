"""``harness/dsa_roofline.py``'s arithmetic and the source that reads the
launch records' sparse-attention fields, on made-up records and a made-up
trace."""
import types

import pytest

from conftest import ROOT  # noqa: F401 - puts the repo on sys.path
from harness import dsa_roofline, spec
from harness.context import Run
from harness.trace import DeviceTrace, Reduced
from harness.window import CounterSnapshot, Window

share = spec.load_module("sources", "dsa_roofline_share")

KEYE = {"hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "num_hidden_layers": 7,
        "sa_config": {"indexer_head_dim": 64, "topk": 2048}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_a_decode_launchs_least_bytes():
    # six rows at 18,000 tokens of context, 7 layers
    live = 7 * 6 * 18001
    chosen = 7 * 6 * 2048
    assert dsa_roofline.index_key_bytes(KEYE) == 128
    assert dsa_roofline.kv_bytes(KEYE) == 2048
    assert dsa_roofline.decode_bytes(KEYE, live, chosen) == \
        live * 128 + chosen * 2048
    secs = dsa_roofline.decode_least_seconds(KEYE, live, chosen, PEAKS)
    assert secs == pytest.approx((live * 128 + chosen * 2048) / 819e9)
    assert 3.2e-4 < secs < 3.4e-4
    # under the top-k every live key is a selected key
    assert dsa_roofline.decode_bytes(KEYE, 7 * 100, 7 * 100) == \
        700 * (128 + 2048)


def test_a_chunks_least_cost_is_under_the_selection():
    # a chunk of 512 at 16,384 tokens: every query attends 2,048 keys
    flops, nbytes = dsa_roofline.prefill_cost(KEYE, 16384, 512)
    assert flops == 4.0 * 32 * 128 * 512 * 2048 * 7
    assert nbytes == 2048 * 2048 * 7
    assert dsa_roofline.prefill_least_seconds(KEYE, 16384, 512, PEAKS) == \
        pytest.approx(flops / 197e12)
    # the first chunk: query j attends j + 1 keys
    flops0, nbytes0 = dsa_roofline.prefill_cost(KEYE, 0, 512)
    assert flops0 == 4.0 * 32 * 128 * (512 * 513 // 2) * 7
    assert nbytes0 == 512 * 2048 * 7
    # a short last chunk counts its real rows only
    assert dsa_roofline.prefill_cost(KEYE, 4096, 10)[0] == \
        4.0 * 32 * 128 * 10 * 2048 * 7


def _rec(kind, begin, **fields):
    return types.SimpleNamespace(kind=kind, begin=begin, **fields)


def _run():
    run = Run(cell=types.SimpleNamespace(config=dict(KEYE)), seed=0,
              seconds=10.0, traced=True, rehearsal=False, process_start=0.0)
    run.window = Window(CounterSnapshot(10.0, {}), CounterSnapshot(20.0, {}))
    run.peaks = PEAKS
    run.model_shape = {}
    run.setup_parts["traced"] = (20.0, 23.0)
    return run


@pytest.fixture
def records(monkeypatch):
    recs = [
        _rec("decode", 19.0, dsa_keys_live=999, dsa_keys_selected=999,
             start=0, valid=0),
        _rec("decode", 20.5, dsa_keys_live=7 * 6 * 18001,
             dsa_keys_selected=7 * 6 * 2048, start=0, valid=0),
        _rec("prefill", 21.0, dsa_keys_live=1, dsa_keys_selected=1,
             start=16384, valid=512),
        _rec("decode", 22.0, dsa_keys_live=7 * 2 * 9000,
             dsa_keys_selected=7 * 2 * 2048, start=0, valid=0),
        _rec("prefill", 24.0, dsa_keys_live=1, dsa_keys_selected=1,
             start=0, valid=512),
    ]
    prof = types.SimpleNamespace(records=lambda: recs)
    monkeypatch.setattr(share._loop, "profiler", lambda: prof)
    return recs


def test_shares_of_the_stretch_by_program(records):
    run = _run()
    ops = [("%dsa_index_scores_decode.1 = f32[8] custom-call()", 0.0, 0.002),
           ("%dsa_select_decode.2 = f32[8] custom-call()", 0.002, 0.003),
           ("%paged_attention_sparse_decode.3 = bf16[8] custom-call()",
            0.003, 0.010),
           ("%dsa_select_prefill.4 = f32[8] custom-call()", 0.01, 0.04),
           ("%paged_attention_prefill_masked.5 = bf16[8] custom-call()",
            0.04, 0.05),
           ("%fusion.9 = f32[4]", 0.05, 0.5)]
    run.trace = Reduced((0.0, 1.0), [DeviceTrace("/device:TPU:0", ops)], {})
    decode = sum(dsa_roofline.decode_least_seconds(KEYE, a, b, PEAKS)
                 for a, b in ((7 * 6 * 18001, 7 * 6 * 2048),
                              (7 * 2 * 9000, 7 * 2 * 2048)))
    got = share.read(
        run, "decode",
        "^(dsa_index_scores_decode|dsa_select_decode"
        "|paged_attention_sparse_decode)")
    assert got == pytest.approx(100.0 * decode / 0.010)
    prefill = dsa_roofline.prefill_least_seconds(KEYE, 16384, 512, PEAKS)
    got = share.read(run, "prefill", "^paged_attention_prefill_masked")
    assert got == pytest.approx(100.0 * prefill / 0.010)
    assert 0 < got < 100.0
    # nothing to read: no such operation, a record without the fields
    # (the parent), a model with no indexer, no trace
    assert share.read(run, "decode", "^no_such_kernel") is None
    del records[1].dsa_keys_live
    assert share.read(run, "decode", "^dsa_select_decode") is None
    for r in records:
        r.dsa_keys_live = r.dsa_keys_selected = 0
    assert share.read(run, "prefill",
                      "^paged_attention_prefill_masked") is None
    run.cell.config.pop("sa_config")
    assert share.read(run, "prefill",
                      "^paged_attention_prefill_masked") is None
    run.trace = None
    assert share.read(run, "decode", "^dsa_select_decode") is None
