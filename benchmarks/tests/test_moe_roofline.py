"""``harness/moe_roofline.py``'s arithmetic and the two sources that read
the launch records' routing fields, on made-up records and a made-up
trace."""
import types

import pytest

from conftest import ROOT  # noqa: F401 - puts the repo on sys.path
from harness import moe_roofline, spec
from harness.context import Run
from harness.trace import DeviceTrace, Reduced
from harness.window import CounterSnapshot, Window

ratio = spec.load_module("sources", "loop_record_ratio")
share = spec.load_module("sources", "moe_roofline_share")

OLMOE = {"hidden_size": 2048, "intermediate_size": 1024,
         "num_hidden_layers": 8, "num_local_experts": 64,
         "num_experts_per_tok": 8}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
EXPERT = 3 * 2048 * 1024            # one expert's three matrices


def test_cost_of_a_launchs_expert_matrices():
    # a decode step of 16 rows, 8 layers: 1,024 assignments; say 440 of
    # the 512 experts touched
    flops, nbytes = moe_roofline.expert_matrices_cost(OLMOE, 1024, 440)
    assert flops == 2.0 * 1024 * EXPERT
    assert nbytes == (440 * EXPERT + 1024 * (2 * 2048 + 3 * 1024)) * 2
    secs, bound = moe_roofline.least_seconds(OLMOE, 1024, 440, PEAKS)
    assert bound == "bandwidth"
    assert secs == pytest.approx(nbytes / 819e9)
    assert 6.7e-3 < secs < 6.8e-3
    # what is not touched is not counted: two rows touch at most 128
    assert moe_roofline.expert_matrices_cost(OLMOE, 128, 128)[1] < \
        0.3 * nbytes


def _rec(kind, begin, **fields):
    return types.SimpleNamespace(kind=kind, begin=begin, **fields)


def _run(opened=10.0, closed=20.0):
    run = Run(cell=types.SimpleNamespace(config=dict(OLMOE)), seed=0,
              seconds=10.0, traced=True, rehearsal=False, process_start=0.0)
    run.window = Window(CounterSnapshot(opened, {}),
                        CounterSnapshot(closed, {}))
    run.peaks = PEAKS
    run.model_shape = {}
    return run


@pytest.fixture
def records(monkeypatch):
    recs = [
        _rec("decode", 9.0, moe_experts_touched=99, moe_expert_slots=512,
             moe_assignments=99, moe_busiest_expert_assignments=99),
        _rec("decode", 11.0, moe_experts_touched=400, moe_expert_slots=512,
             moe_assignments=1024, moe_busiest_expert_assignments=40),
        _rec("prefill", 12.0, moe_experts_touched=512, moe_expert_slots=512,
             moe_assignments=4096, moe_busiest_expert_assignments=120),
        _rec("decode", 13.0, moe_experts_touched=112, moe_expert_slots=512,
             moe_assignments=128, moe_busiest_expert_assignments=16),
        _rec("decode", 25.0, moe_experts_touched=1, moe_expert_slots=512,
             moe_assignments=8, moe_busiest_expert_assignments=8),
    ]
    prof = types.SimpleNamespace(records=lambda: recs)
    monkeypatch.setattr(ratio._loop, "profiler", lambda: prof)
    monkeypatch.setattr(share._loop, "profiler", lambda: prof)
    return recs


def test_ratio_over_the_launches_of_the_window(records):
    run = _run()
    # decode launches that began in [10, 20): 400 + 112 of 2 x 512
    assert ratio.read(run, "moe_experts_touched", "moe_expert_slots",
                      kinds=["decode", "verify"], scale=100.0) == \
        pytest.approx(100.0 * 512 / 1024)
    # every kind: busiest x 64 over assignments
    assert ratio.read(run, "moe_busiest_expert_assignments",
                      "moe_assignments", scale=64.0) == \
        pytest.approx(64.0 * (40 + 120 + 16) / (1024 + 4096 + 128))


def test_ratio_reads_nothing_without_the_fields_or_a_denominator(records):
    run = _run()
    del records[1].moe_experts_touched          # a program before PR 26
    assert ratio.read(run, "moe_experts_touched", "moe_expert_slots") is None
    for r in records:
        r.moe_assignments = 0                   # a dense model
    assert ratio.read(run, "moe_busiest_expert_assignments",
                      "moe_assignments") is None
    assert ratio.read(_run(30.0, 40.0), "moe_assignments",
                      "moe_expert_slots") is None     # no launch


def test_roofline_share_of_the_stretch(records):
    run = _run()
    run.setup_parts["traced"] = (10.5, 14.0)
    least = sum(moe_roofline.least_seconds(OLMOE, a, e, PEAKS)[0]
                for a, e in ((1024, 400), (4096, 512), (128, 112)))
    ops = [("%moe_experts.3 = bf16[512,2048] custom-call(...)", 0.0, 0.010),
           ("%moe_experts.4 = bf16[512,2048] custom-call(...)", 0.02, 0.03),
           ("%fusion.9 = f32[4]", 0.03, 0.5)]
    run.trace = Reduced((0.0, 1.0), [DeviceTrace("/device:TPU:0", ops)], {})
    got = share.read(run, "^moe_experts")
    assert got == pytest.approx(100.0 * least / 0.020)
    assert got < 100.0
    # nothing to read: no such operation, no fields, no trace
    assert share.read(run, "^no_such_kernel") is None
    del records[2].moe_assignments
    assert share.read(run, "^moe_experts") is None
    run.trace = None
    assert share.read(run, "^moe_experts") is None
