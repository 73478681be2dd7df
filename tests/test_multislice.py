"""Multi-slice elastic runtime (multislice.py): slice mesh axis,
hierarchical ICI-then-DCN reduction parity, elastic resume across
dp x slice shapes, run-shape detection, and per-slice attribution.

Single-process coverage on the 8-virtual-device mesh; the slice axis
spanning a real process boundary is tests/test_multihost_cpu.py's job.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu import checkpointing, multislice, topology
from megatron_llm_tpu.config import ParallelConfig, TrainConfig
from megatron_llm_tpu.models.llama import LlamaModel, llama_config
from megatron_llm_tpu.optimizer import MegatronOptimizer
from megatron_llm_tpu.parallel import sharding as sh
from megatron_llm_tpu.tracing import StragglerDetector
from megatron_llm_tpu.training import build_train_step


# ---------------------------------------------------------------------------
# topology: the slice mesh axis
# ---------------------------------------------------------------------------

def test_slice_mesh_axis(utils):
    mesh = utils.initialize_model_parallel(num_slices=2)
    assert dict(mesh.shape) == {"slice": 2, "pp": 1, "dp": 4, "cp": 1,
                                "tp": 1}
    assert topology.get_num_slices() == 2
    assert topology.get_world_size() == 8
    assert topology.data_axes() == ("slice", "dp")
    assert multislice.host_slice_map(1, 2) == [0]   # one host, all slices


def test_single_slice_is_default(utils):
    utils.initialize_model_parallel()
    assert topology.get_num_slices() == 1
    assert topology.data_axes() == ("dp",)


def test_slice_divisibility_validated(utils):
    with pytest.raises(RuntimeError):
        utils.initialize_model_parallel(num_slices=3)    # 8 % 3 != 0
    with pytest.raises(RuntimeError):
        utils.initialize_model_parallel(tp=2, pp=2, num_slices=4)


def test_slice_with_model_parallel(utils):
    mesh = utils.initialize_model_parallel(tp=2, num_slices=2)
    assert mesh.shape["slice"] == 2
    assert mesh.shape["tp"] == 2
    assert mesh.shape["dp"] == 2


# ---------------------------------------------------------------------------
# hierarchical (ICI-then-DCN) reduction
# ---------------------------------------------------------------------------

def test_hierarchical_allreduce_matches_flat(utils):
    utils.initialize_model_parallel(num_slices=2)
    mesh = topology.get_mesh()
    # integer-valued floats: both reduction orders are exact, so the
    # staged result must be bit-identical to the flat one
    x = np.arange(8 * 5, dtype=np.float32).reshape(8, 5)
    xs = jax.device_put(x, NamedSharding(mesh, P(("slice", "dp"))))
    hier = np.asarray(multislice.hierarchical_allreduce(xs))
    flat = np.asarray(multislice.flat_allreduce(xs))
    np.testing.assert_array_equal(hier, flat)
    np.testing.assert_array_equal(hier, x.sum(0))


def _tiny_model():
    cfg = llama_config("tiny", num_layers=2, seq_length=32,
                       max_position_embeddings=32, padded_vocab_size=128)
    return LlamaModel(cfg)


def _global_batch(mesh, num_micro=2, gb=8, seed=0):
    rng = np.random.RandomState(seed)
    toks = jnp.asarray(
        rng.randint(0, 128, (num_micro, gb, 32)).astype(np.int32))
    dsh = NamedSharding(mesh, P(None, topology.data_axes(), None))
    return {
        "tokens": jax.device_put(toks, dsh),
        "labels": jax.device_put(jnp.roll(toks, -1, axis=-1), dsh),
        "loss_mask": jax.device_put(jnp.ones(toks.shape, jnp.float32), dsh),
    }


def test_train_step_parity_hierarchical_vs_flat(utils):
    """The staged (ICI-then-DCN) reduction must reproduce the flat
    reduction: same loss, same grad norm, same updated params (up to
    reduction-order float noise)."""
    utils.initialize_model_parallel(num_slices=2)   # slice=2 x dp=4
    mesh = topology.get_mesh()
    model = _tiny_model()
    tc = TrainConfig(micro_batch_size=1, global_batch_size=16, lr=1e-3,
                     optimizer="adam")
    opt = MegatronOptimizer(tc)
    batch = _global_batch(mesh)
    key = jax.random.PRNGKey(0)

    results = {}
    for name, hier in (("hier", True), ("flat", False)):
        pc = ParallelConfig(data_parallel_size=4, num_slices=2,
                            multislice_hierarchical=hier)
        params = _fresh(model, mesh)
        opt_state = opt.init(params)
        step = build_train_step(model, opt, pc, 2)
        p, _, m = step(params, opt_state, batch, key, 1e-3, 0.0)
        results[name] = (jax.device_get(p), float(m["lm loss"]),
                         float(m["grad_norm"]))

    (p_h, loss_h, gn_h), (p_f, loss_f, gn_f) = results["hier"], results["flat"]
    assert abs(loss_h - loss_f) < 1e-6
    assert abs(gn_h - gn_f) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(p_h),
                    jax.tree_util.tree_leaves(p_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# elastic resume: different dp x slice product from the same checkpoint
# ---------------------------------------------------------------------------

def _run_steps(model, params, opt, opt_state, pc, mesh, n, start=0,
               num_micro=1):
    step = build_train_step(model, opt, pc, num_micro)
    key = jax.random.PRNGKey(7)
    losses = []
    for i in range(start, start + n):
        batch = _global_batch(mesh, num_micro=num_micro, gb=4, seed=100 + i)
        params, opt_state, m = step(params, opt_state, batch,
                                    jax.random.fold_in(key, i), 1e-3, 0.0)
        losses.append(float(m["lm loss"]))
    return params, opt_state, losses


def _fresh(model, mesh):
    params = model.init(jax.random.PRNGKey(0))
    return sh.shard_params(params, model.param_specs(params))


def _resume(model, opt, d, mesh):
    """Two-phase cross-mesh restore (the finetune.py pattern): params via
    a template carrying THIS mesh's shardings, then the optimizer state
    against a freshly-initialized template."""
    tmpl = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        _fresh(model, mesh))
    params, _, meta = checkpointing.load_checkpoint(d, params_template=tmpl)
    params = sh.shard_params(params, model.param_specs(params))
    opt_tmpl = opt.init(params)
    _, opt_state, _ = checkpointing.load_checkpoint(
        d, load_params=False, opt_state_template=opt_tmpl)
    return params, opt_state, meta


@pytest.mark.parametrize("resume_shape", [
    pytest.param(dict(devices=1, dp=1, slices=1), marks=pytest.mark.slow),
    pytest.param(dict(devices=4, dp=4, slices=1), marks=pytest.mark.slow),
    # tier-1 keeps the slice-count change — the headline elastic case
    dict(devices=4, dp=2, slices=2),
])
def test_elastic_resume_parity(resume_shape):
    """Train at dp=2/slice=1, save, resume at a different dp x slice
    product — the loss trajectory and final params must match the
    uninterrupted run."""
    model = _tiny_model()
    tc = TrainConfig(micro_batch_size=1, global_batch_size=4, lr=1e-3,
                     optimizer="adam")
    opt = MegatronOptimizer(tc)
    d = tempfile.mkdtemp()
    try:
        # --- reference run: 4 uninterrupted steps at dp=2 ---
        topology.destroy_model_parallel()
        mesh = topology.initialize_model_parallel(
            devices=jax.devices()[:2])
        pc = ParallelConfig(data_parallel_size=2)
        params = _fresh(model, mesh)
        opt_state = opt.init(params)
        params, opt_state, l12 = _run_steps(model, params, opt, opt_state,
                                            pc, mesh, 2)
        checkpointing.save_checkpoint(d, 2, params, opt_state,
                                      consumed_samples=8)
        _, _, ref_losses = _run_steps(model, params, opt, opt_state, pc,
                                      mesh, 2, start=2)

        # --- elastic resume at a different shape ---
        topology.destroy_model_parallel()
        n = resume_shape["devices"]
        sl = resume_shape["slices"]
        mesh2 = topology.initialize_model_parallel(
            devices=jax.devices()[:n], num_slices=sl)
        pc2 = ParallelConfig(data_parallel_size=resume_shape["dp"],
                             num_slices=sl,
                             multislice_hierarchical=sl > 1)
        params2, opt_state2, meta = _resume(model, opt, d, mesh2)
        assert meta["iteration"] == 2
        assert meta["consumed_samples"] == 8
        _, _, res_losses = _run_steps(model, params2, opt, opt_state2, pc2,
                                      mesh2, 2, start=2)

        np.testing.assert_allclose(res_losses, ref_losses, rtol=2e-5,
                                   atol=2e-6)

        # the save recorded the producing shape; the resumed shape is a
        # detectable change
        old = multislice.read_run_shape(d)
        assert old is not None and old["data_parallel_size"] == 2 \
            and old["num_slices"] == 1
        args = argparse.Namespace(
            world_size=n, num_slices=sl,
            data_parallel_size=resume_shape["dp"],
            tensor_model_parallel_size=1, pipeline_model_parallel_size=1,
            context_parallel_size=1, global_batch_size=4,
            micro_batch_size=1)
        ev = multislice.detect_elastic_resume(d, args)
        assert ev is not None and ev["kind"] == "elastic_resume"
        changed = ev["changed"]
        assert "data_parallel_size" in changed or "num_slices" in changed
    finally:
        topology.destroy_model_parallel()
        shutil.rmtree(d)


# ---------------------------------------------------------------------------
# run-shape persistence + announcement
# ---------------------------------------------------------------------------

def _shape_args(**kw):
    base = dict(world_size=8, num_slices=2, data_parallel_size=4,
                tensor_model_parallel_size=1, pipeline_model_parallel_size=1,
                context_parallel_size=1, global_batch_size=8,
                micro_batch_size=1)
    base.update(kw)
    return argparse.Namespace(**base)


def test_run_shape_roundtrip(tmp_path):
    shape = multislice.run_shape_from_args(_shape_args())
    path = multislice.write_run_shape(str(tmp_path), shape)
    assert path and os.path.exists(path)
    assert multislice.read_run_shape(str(tmp_path)) == shape
    # same shape -> no event
    assert multislice.detect_elastic_resume(str(tmp_path),
                                            _shape_args()) is None
    # changed dp x slice -> event with the delta
    ev = multislice.detect_elastic_resume(
        str(tmp_path), _shape_args(num_slices=1, data_parallel_size=8))
    assert ev["changed"]["num_slices"] == {"from": 2, "to": 1}
    assert ev["changed"]["data_parallel_size"] == {"from": 4, "to": 8}


def test_run_shape_absent_is_not_a_change(tmp_path):
    assert multislice.read_run_shape(str(tmp_path)) is None
    assert multislice.detect_elastic_resume(str(tmp_path),
                                            _shape_args()) is None


def test_announce_elastic_resume_emits_jsonl(tmp_path):
    multislice.write_run_shape(
        str(tmp_path), multislice.run_shape_from_args(_shape_args()))

    class FakeStream:
        def __init__(self):
            self.records = []

        def emit(self, rec):
            self.records.append(rec)

    stream = FakeStream()
    ev = multislice.announce_elastic_resume(
        str(tmp_path), _shape_args(num_slices=4, data_parallel_size=2),
        iteration=10, consumed_samples=80, stream=stream)
    assert ev is not None
    assert stream.records and stream.records[0]["kind"] == "elastic_resume"
    assert stream.records[0]["iteration"] == 10
    assert stream.records[0]["consumed_samples"] == 80


# ---------------------------------------------------------------------------
# per-slice attribution
# ---------------------------------------------------------------------------

def test_host_slice_map_contiguous_blocks():
    assert multislice.host_slice_map(8, 2) == [0, 0, 0, 0, 1, 1, 1, 1]
    assert multislice.host_slice_map(4, 4) == [0, 1, 2, 3]
    assert multislice.host_slice_map(2, 1) == [0, 0]
    assert multislice.host_slice_map(1, 4) == [0]   # virtual-device run


def test_slice_times_and_worst_slice():
    # hosts 0-1 are slice 0, hosts 2-3 slice 1; slice 1's host 3 lags
    times = multislice.slice_times([0.10, 0.11, 0.10, 0.35], [0, 0, 1, 1])
    assert times == {0: 0.11, 1: 0.35}
    ws = multislice.worst_slice(times)
    assert ws["slice"] == 1
    assert ws["secs"] == pytest.approx(0.35)
    assert ws["lag_secs"] == pytest.approx(0.24)
    assert multislice.worst_slice({0: 0.1}) is None   # nothing to compare


def test_straggler_detector_names_slice():
    printed = []
    det = StragglerDetector(threshold=1.5, min_secs=0.001,
                            printer=printed.append,
                            host_slice_map=[0, 0, 1, 1])
    events = det.check({"train-step": [0.10, 0.10, 0.10, 0.40]},
                       iteration=20)
    assert len(events) == 1
    assert events[0]["host"] == 3
    assert events[0]["slice"] == 1
    assert any("slice 1 host 3" in line for line in printed)
    # without a map the event carries no slice field (single-job runs)
    det2 = StragglerDetector(threshold=1.5, min_secs=0.001,
                             printer=lambda *_: None)
    ev2 = det2.check({"train-step": [0.10, 0.10, 0.10, 0.40]}, iteration=21)
    assert "slice" not in ev2[0]


# ---------------------------------------------------------------------------
# offline aggregation: tools/telemetry_report.py + tools/trace_report.py
# ---------------------------------------------------------------------------

def _load_tool(name):
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _slice_stream(path):
    """Synthetic schema-4 stream: slice 1 is the chronic straggler."""
    with open(path, "w") as f:
        f.write(json.dumps({
            "kind": "elastic_resume", "iteration": 10,
            "consumed_samples": 80,
            "changed": {"num_slices": {"from": 1, "to": 2}},
        }) + "\n")
        for i in (10, 20, 30):
            f.write(json.dumps({
                "schema": 4, "kind": "log", "iteration": i,
                "lm_loss": 2.0, "step_time_secs": 0.2,
                "slice_times": {"0": 0.10, "1": 0.10 + 0.05 * (i // 10)},
                "worst_slice": {"slice": 1, "secs": 0.10 + 0.05 * (i // 10),
                                "median_other_secs": 0.10,
                                "lag_secs": 0.05 * (i // 10),
                                "ratio": 1.0 + 0.5 * (i // 10)},
                "goodput": {"goodput_pct": 90.0,
                            "slice_stall_secs": {"1": 0.5 * (i // 10)}},
            }) + "\n")
        f.write(json.dumps({
            "kind": "preempt_rescue", "iteration": 30, "exit_code": 17,
            "saved": True,
        }) + "\n")


def test_telemetry_report_per_slice_aggregation(tmp_path):
    stream = tmp_path / "telemetry.jsonl"
    _slice_stream(str(stream))
    tr = _load_tool("telemetry_report")

    records = tr.load_records(str(tmp_path))
    slices = tr.slice_aggregates(records)
    assert set(slices) == {"0", "1"}
    assert slices["1"]["times_worst"] == 3
    assert slices["1"]["stall_secs"] == pytest.approx(1.5)   # cumulative
    assert slices["1"]["max_step_secs"] == pytest.approx(0.25)
    assert slices["0"]["times_worst"] == 0
    table = tr.slice_table(slices)
    assert "slice" in table and "stall secs" in table

    fleet = tr.fleet_events(str(tmp_path))
    assert [e["kind"] for e in fleet] == ["elastic_resume",
                                         "preempt_rescue"]

    # single-slice stream: no slice section, graceful
    assert tr.slice_aggregates(
        [{"kind": "log", "iteration": 1, "step_time_secs": 0.1}]) is None

    # end to end through the CLI (human + json modes)
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "telemetry_report.py"),
         str(tmp_path)], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "per-slice attribution" in r.stdout
    assert "elastic resume at iteration 10" in r.stdout
    assert "preemption rescue at iteration 30" in r.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "telemetry_report.py"),
         str(tmp_path), "--json"], capture_output=True, text=True,
        timeout=120)
    doc = json.loads(r.stdout)
    assert doc["slices"]["1"]["times_worst"] == 3
    assert len(doc["fleet_events"]) == 2


def test_trace_report_slice_column(tmp_path):
    tr = _load_tool("trace_report")
    trace = {"traceEvents": [
        {"ph": "i", "name": "straggler", "ts": 1_000_000.0,
         "args": {"iteration": 20, "host": 3, "slice": 1,
                  "section": "train-step", "secs": 0.4,
                  "median_secs": 0.1, "ratio": 4.0}},
        {"ph": "i", "name": "straggler", "ts": 2_000_000.0,
         "args": {"iteration": 30, "host": 0,
                  "section": "train-step", "secs": 0.3,
                  "median_secs": 0.1, "ratio": 3.0}},
    ]}
    timeline = tr.straggler_timeline(trace)
    assert timeline[0]["slice"] == 1
    assert timeline[1]["slice"] is None     # single-job event: no slice
    out = tr.render(trace, top_n=5, trend=[])
    assert "slice 1 host 3" in out
    assert "host 0" in out


def test_goodput_slice_stall_in_summary():
    from megatron_llm_tpu.tracing import GoodputAccounter
    clock = [0.0]
    g = GoodputAccounter(clock=lambda: clock[0])
    clock[0] = 10.0
    g.add("step", 8.0)
    g.add_slice_stall(1, 0.75)
    g.add_slice_stall(1, 0.25)
    s = g.summary()
    assert s["slice_stall_secs"] == {"1": 1.0}
    # no stalls recorded -> key absent (single-job schema unchanged)
    assert "slice_stall_secs" not in GoodputAccounter(
        clock=lambda: 1.0).summary()
