"""``harness/ssm_roofline.py``'s arithmetic and the two sources that read
the launch records' state-space fields, on made-up records and a made-up
attribution."""
import types

import pytest

from conftest import ROOT  # noqa: F401 - puts the repo on sys.path
from harness import spec, ssm_roofline
from harness.context import Run
from harness.window import CounterSnapshot, Window

share = spec.load_module("sources", "ssm_roofline_share")
mean = spec.load_module("sources", "loop_record_mean")

GRANITE = {"hidden_size": 4096, "num_hidden_layers": 10,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
           "mamba_n_groups": 1, "mamba_d_conv": 4}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_a_live_row_is_its_state_and_its_columns_read_and_written_once():
    assert ssm_roofline.row_bytes(GRANITE) == 4194304 + 50688
    # sixteen live rows in nine state-space layers
    secs = ssm_roofline.decode_least_seconds(GRANITE, 9 * 16, PEAKS)
    assert secs == pytest.approx(2 * 9 * 16 * 4244992 / 819e9)
    assert 1.4e-3 < secs < 1.6e-3
    assert ssm_roofline.decode_least_seconds(GRANITE, 0, PEAKS) == 0.0


def _rec(kind, begin, **fields):
    return types.SimpleNamespace(kind=kind, begin=begin, **fields)


def _row(scope):
    return {"scope": scope, "role": scope, "edge": "", "opcode": "fusion",
            "root": "multiply"}


def test_the_share_counts_decode_launches_wholly_inside_the_stretch():
    recs = [_rec("decode", 0.0, ssm_rows_live=9 * 16),
            _rec("prefill", 0.1, ssm_rows_live=9),
            _rec("decode", 0.2, ssm_rows_live=9 * 20),
            _rec("decode", 0.9, ssm_rows_live=9 * 24)]     # cut by the end
    rows = [(recs[0], 0.00, 0.05), (recs[1], 0.10, 0.15),
            (recs[2], 0.20, 0.25), (recs[3], 0.90, 1.10)]
    ops = [("%f.1", 0.01, 0.014, _row("ssm_step"), 0, "own"),
           ("%f.2", 0.02, 0.021, _row("ssm_conv"), 0, "own"),
           ("%f.3", 0.03, 0.040, _row("ssm_out_proj"), 0, "own"),
           ("%f.4", 0.11, 0.140, _row("ssm_scan"), 1, "own"),
           ("%f.1", 0.21, 0.215, _row("ssm_step"), 2, "own"),
           ("%f.9", 0.22, 0.230, None, 2, None),
           ("%f.1", 0.95, 0.990, _row("ssm_step"), 3, "own")]
    both = share.least_and_measured(GRANITE, rows, ops, (0.0, 1.0),
                                    ("ssm_conv", "ssm_step"), PEAKS)
    least = sum(ssm_roofline.decode_least_seconds(GRANITE, n, PEAKS)
                for n in (9 * 16, 9 * 20))
    assert both[0] == pytest.approx(least)
    assert both[1] == pytest.approx(0.004 + 0.001 + 0.005)
    assert 0 < 100 * both[0] / both[1] < 100
    # a record without the field (the parent), no state advanced, no
    # operation under the scopes: nothing to read
    del recs[2].ssm_rows_live
    assert share.least_and_measured(GRANITE, rows, ops, (0.0, 1.0),
                                    ("ssm_step",), PEAKS) is None
    recs[2].ssm_rows_live = recs[0].ssm_rows_live = 0
    assert share.least_and_measured(GRANITE, rows, ops, (0.0, 1.0),
                                    ("ssm_step",), PEAKS) is None
    recs[0].ssm_rows_live = 9
    assert share.least_and_measured(GRANITE, rows, ops, (0.0, 1.0),
                                    ("no_such_scope",), PEAKS) is None


def test_the_share_reads_nothing_without_a_trace_or_a_state_space_model():
    run = Run(cell=types.SimpleNamespace(config=dict(GRANITE)), seed=0,
              seconds=10.0, traced=True, rehearsal=False, process_start=0.0)
    run.model_shape = {}
    run.peaks = PEAKS
    assert share.read(run, ["ssm_step"]) is None            # no trace
    run.trace = types.SimpleNamespace(devices=[], window=(0.0, 1.0))
    assert share.read(run, ["ssm_step"]) is None            # no table
    run.cell.config.pop("mamba_n_heads")
    assert share.read(run, ["ssm_step"]) is None


def test_the_mean_of_a_field_over_the_counted_window(monkeypatch):
    recs = [_rec("decode", 5.0, ssm_state_bytes_held=99),
            _rec("decode", 11.0, ssm_state_bytes_held=10 * 38202624),
            _rec("prefill", 12.0, ssm_state_bytes_held=20 * 38202624),
            _rec("decode", 25.0, ssm_state_bytes_held=1)]
    prof = types.SimpleNamespace(records=lambda: recs)
    monkeypatch.setattr(mean._loop, "profiler", lambda: prof)
    run = Run(cell=types.SimpleNamespace(config={}), seed=0, seconds=10.0,
              traced=False, rehearsal=False, process_start=0.0)
    assert mean.read(run, "ssm_state_bytes_held") is None   # no window
    run.window = Window(CounterSnapshot(10.0, {}), CounterSnapshot(20.0, {}))
    assert mean.read(run, "ssm_state_bytes_held", scale=1e-9) == (
        pytest.approx(15 * 38202624e-9))
    assert mean.read(run, "ssm_state_bytes_held", kinds=["prefill"]) == (
        20 * 38202624)
    assert mean.read(run, "no_such_field") is None          # the parent
    for r in recs:
        r.ssm_state_bytes_held = 0
    assert mean.read(run, "ssm_state_bytes_held") is None   # no such layer
