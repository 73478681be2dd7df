"""The trace reducer on a small recorded trace.

``recorded_mistral_chat.xplane.pb`` is the first 0.37 s of the traced
stretch of a run of mistral-7b-serve.chat on a TPU v5e (PR 23): the
device's ``XLA Ops`` and ``XLA Modules`` lines with the instruction
names cut to their left-hand sides, and the host's ``bench.*``
annotations; three prefill chunks and three decode steps, the last
decode step cut by the end of the recording.
"""
import os

import pytest
from jax.profiler import ProfileData

from harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
PB = os.path.join(HERE, "recorded_mistral_chat.xplane.pb")


@pytest.fixture(scope="module")
def profile():
    return ProfileData.from_file(PB)


@pytest.fixture(scope="module")
def reduced(profile):
    return trace.reduce_profile(profile)


def _modules(profile, prefix):
    dev = next(p for p in profile.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    return [e.duration_ns * 1e-9 for e in line.events
            if e.name.startswith(prefix)]


def test_planes_and_annotations_found(reduced):
    assert [d.name for d in reduced.devices] == ["/device:TPU:0"]
    assert {k: len(v) for k, v in reduced.annotations.items()} == {
        "bench.prefill_step": 3, "bench.decode_step": 3}
    assert len(reduced.devices[0].ops) > 4000


def test_busy_is_a_union_and_idle_share_follows(reduced):
    ops = reduced.devices[0].ops
    busy = reduced.busy_s
    # async pairs overlap other ops: the union is below the plain sum
    assert busy < sum(e - s for _, s, e in ops)
    assert busy <= reduced.window_s
    idle = reduced.idle_share_by_device()["/device:TPU:0"]
    assert idle == pytest.approx(1 - busy / reduced.window_s)
    assert 0.05 < idle < 0.15          # the run it was cut from read 10.2%


def test_time_under_an_annotation_is_the_program_it_dispatched(
        reduced, profile):
    """The jitted call returns before the device runs it, so the device
    time is taken up to the next annotated span; it must agree with the
    device's own module durations."""
    decode = reduced.under_annotation("bench.decode_step")
    modules = _modules(profile, "jit__decode_impl")
    assert decode[:2] == pytest.approx(modules[:2], rel=2e-3)
    assert decode[0] == pytest.approx(0.0943, abs=5e-4)
    assert decode[2] < decode[0]        # cut by the recording's end
    prefill = reduced.under_annotation("bench.prefill_step")
    chunk = _modules(profile, "jit__prefill_impl")
    # a chunk that ends a prompt also runs the first-token sampler
    assert prefill[1] == pytest.approx(chunk[1], rel=5e-3)
    assert all(0.016 < p < 0.018 for p in prefill)


def test_op_families_and_top_ops(reduced):
    assert trace.op_family("%attention.16") == "attention"
    assert trace.op_family("%bitcast_add_fusion.3 = bf16[1]") == \
        "bitcast_add_fusion"
    assert trace.op_family("slice-done.12") == "slice-done"
    top = reduced.top_ops(3)
    assert top[0][0] == "attention" and top[0][1] > 0.19
    assert reduced.op_seconds("^attention$") == pytest.approx(top[0][1])


def test_gaps_are_named_by_what_the_host_was_doing(reduced):
    gaps = dict(reduced.idle_gaps())
    assert set(gaps) <= {"after_bench.prefill_step",
                         "after_bench.decode_step", "in_bench.prefill_step",
                         "in_bench.decode_step", "before_any_annotation"}
    assert sum(gaps.values()) == pytest.approx(
        reduced.window_s - reduced.busy_s, rel=1e-6)
    assert gaps["after_bench.prefill_step"] > gaps["in_bench.prefill_step"]


def test_interval_arithmetic():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert trace.total([(0, 3), (5, 6)]) == 4
    assert trace.clip([(0, 3), (5, 6)], 2, 5.5) == 1.5
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]


def test_exposed_collective_time():
    dev = trace.DeviceTrace("/device:TPU:0", [
        ("%all-gather-start.1", 0.0, 1.0), ("%fusion.1", 0.5, 2.0),
        ("%all-reduce.2", 2.0, 3.0), ("%all-gather-done.1", 3.0, 3.5),
        ("%while.7", 0.0, 3.6)])        # the scan that encloses them all
    r = trace.Reduced((0.0, 4.0), [dev], {})
    # the first half second of the gather, the whole reduce and the done;
    # the enclosing loop neither hides them nor counts as an operation
    assert r.collective_exposed_by_device() == {
        "/device:TPU:0": pytest.approx(2.0)}
    assert "while" not in dict(r.top_ops())
    assert r.busy_s == pytest.approx(3.6)


def test_a_trace_with_no_device_reads_as_nothing(tmp_path):
    assert trace.reduce_dir(str(tmp_path)) is None
