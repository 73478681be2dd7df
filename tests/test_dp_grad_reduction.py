"""The train step reduces the data-parallel gradient once a step, in fp32,
after the microbatch scan (``training.build_train_step``, PR 31).

Two things are held: WHERE the reduction sits in the compiled program (no
``while`` body holds a reduction over the dp groups on anything larger than
a few scalars; the ones after the loops are f32), and that the NUMBERS are
those of the plain gradient of the same objective.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu import hlo_collectives, topology
from megatron_llm_tpu.config import ParallelConfig, TrainConfig
from megatron_llm_tpu.optimizer import MegatronOptimizer
from megatron_llm_tpu.optimizer.optimizer import global_grad_norm
from megatron_llm_tpu.parallel import glu_pairs, sharding as sh
from megatron_llm_tpu.training import build_train_step, default_loss_func


def _mesh(tp, dp):
    topology.destroy_model_parallel()
    return topology.initialize_model_parallel(
        tp, devices=jax.devices()[:tp * dp])


# ---------------------------------------------------------------------------
# where the reduction sits
# ---------------------------------------------------------------------------

def _cell_step(num_micro=4, seq=128, **model_kw):
    """The benchmark's training cell at its rehearsal widths (``benchmarks/
    configs/mistral-7b-train-tp2dp2.json``), in bf16 as on the chip: tp 2
    with sequence parallelism x dp 2, 2 scanned layers; the parameters in
    the form ``finetune.py`` holds them in."""
    from megatron_llm_tpu.models.gpt import GPTModel
    from megatron_llm_tpu.models.mistral import MistralModel, mistral_config

    mesh = _mesh(tp=2, dp=2)
    cfg = mistral_config(
        "tiny", padded_vocab_size=512, seq_length=seq,
        max_position_embeddings=512, params_dtype="bf16",
        compute_dtype="bf16", recompute_granularity="selective",
        **model_kw)
    # (MistralModel asserts its family's own flags: swiglu among them)
    model = (GPTModel if model_kw else MistralModel)(cfg)
    params = sh.init_params(model, jax.random.PRNGKey(0),
                            form=glu_pairs.for_trainer)
    tc = TrainConfig(micro_batch_size=1, global_batch_size=2 * num_micro,
                     lr=1e-4, bf16=True)
    pc = ParallelConfig(tensor_model_parallel_size=2, data_parallel_size=2,
                        sequence_parallel=True)
    opt = MegatronOptimizer(tc, params_dtype=jnp.bfloat16)
    dsh = NamedSharding(mesh, P(None, "dp", None))
    toks = jax.device_put(jnp.asarray(np.random.RandomState(0).randint(
        0, 512, (num_micro, 2, seq)), jnp.int32), dsh)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, -1),
             "loss_mask": jax.device_put(jnp.ones(toks.shape, jnp.float32),
                                         dsh)}
    step = build_train_step(model, opt, pc, num_micro)
    args = (params, opt.init(params), batch, jax.random.PRNGKey(1), 1e-4, 0.0)
    return mesh, step, args


def test_no_dp_reduction_inside_a_loop_and_fp32_after():
    mesh, step, args = _cell_step()
    rows = hlo_collectives.collectives(
        step.lower(*args).compile().as_text())
    dp = hlo_collectives.mesh_groups(dict(mesh.shape), ["dp"])
    tp = hlo_collectives.mesh_groups(dict(mesh.shape), ["tp"])
    assert dp != tp
    # the reading reads: the layer scans' tensor-parallel collectives are
    # found two loops deep, with the scans' trip counts
    assert any(r["groups"] == tp and r["loops"] == (4, 2) for r in rows)
    over_dp = hlo_collectives.reductions_over(rows, dp)
    in_loops = [r for r in over_dp if r["loops"]]
    assert not in_loops, in_loops
    assert over_dp, "the step's one reduction over dp is missing"
    assert all(r["dtypes"] == ["f32"] for r in over_dp), over_dp
    n_leaves = len(jax.tree_util.tree_leaves(args[0]))
    assert sum(r["calls"] for r in over_dp) <= n_leaves


@pytest.mark.parametrize("activation", [
    "liglu", "geglu", "reglu", "swiglu",
    # a non-gated MLP has no pair: its first projection stays flat
    None,
])
def test_a_gated_mlp_trades_nothing_over_tp(activation):
    """The step's tensor-parallel edge (``parallel/glu_pairs.py``, PR 50):
    held flat, ``[gate | up]`` in contiguous shards of ``2F``, a gated
    MLP's halves met through 24 all-to-alls (op_name ``.../mlp/
    concatenate``) and 32 permutes (``.../mlp/split``) a step here; held
    paired, every collective left over tp is sequence parallelism's or the
    loss's, at the counts they had."""
    mesh, step, args = _cell_step(glu_activation=activation)
    first = args[0]["transformer"]["layers"]["mlp"]["dense_h_to_4h"]
    assert glu_pairs.count(args[0]) == ((1, 0) if activation else (0, 0))
    assert first["kernel"].ndim == (4 if activation else 3)
    rows = hlo_collectives.collectives(
        step.lower(*args).compile().as_text())
    tp = hlo_collectives.mesh_groups(dict(mesh.shape), ["tp"])
    over_tp = [r for r in rows if r["groups"] == tp]
    moved = [r for r in over_tp
             if r["family"] in ("all-to-all", "collective-permute")]
    assert not [r for r in moved if "/mlp/" in r["op_name"]], moved
    assert not moved, moved
    calls = {}
    for r in over_tp:
        key = (r["family"], r["loops"])
        calls[key] = calls.get(key, 0) + r["calls"]
    assert calls == {
        ("all-gather", (4, 2)): 64, ("all-gather", (4,)): 8,
        ("all-reduce", (4, 2)): 40, ("all-reduce", (4,)): 20,
        ("all-reduce", ()): 2}, calls


def test_the_step_logs_what_its_compiled_text_counts(tmp_path):
    import json

    from megatron_llm_tpu import telemetry

    mesh, step, args = _cell_step(num_micro=2, seq=64)
    stream = telemetry.TelemetryStream(str(tmp_path))
    telemetry.install_stream(stream)
    try:
        _, _, metrics = step(*args)
    finally:
        telemetry.install_stream(None)
        stream.close()
    assert np.isfinite(float(metrics["lm loss"]))
    with open(tmp_path / telemetry.STREAM_FILENAME) as f:
        recs = [json.loads(line) for line in f]
    (rec,) = [r for r in recs if r["kind"] == "train_step_program"]
    assert rec["num_microbatches"] == 2 and rec["dp"] == 2
    assert rec["dp_grad_reductions_in_loops"] == 0
    assert 1 <= rec["dp_grad_reductions_per_step"] <= len(
        jax.tree_util.tree_leaves(args[0]))
    assert rec["dp_grad_reduction_dtypes"] == ["f32"]
    # every collective under the mesh axes its groups run over: the dp
    # edge's all-reduces are the gradient's, and the step's table is
    # registered where a reader with no trainer in hand finds it
    by_edge = rec["collectives_by_edge"]
    assert by_edge["dp"]["all-reduce"]["calls"] >= rec[
        "dp_grad_reductions_per_step"]
    assert by_edge["dp"]["all-reduce"]["bytes"] >= rec[
        "dp_grad_reduction_bytes_per_step"]
    assert "tp" in by_edge and set(by_edge) <= {"dp", "tp", "dp+tp", ""}
    from megatron_llm_tpu.serving.loop_profiler import live_programs
    table = live_programs()["train_step"]
    assert table.collectives_by_edge() == by_edge
    assert {r["edge"] for r in table.rows if "family" in r} == set(by_edge)
    # an event, not a step: the stream's means leave it out
    assert stream.summary()["log_boundaries"] == 0


def test_reading_a_compiled_text():
    """Replica groups in both notations, a trip count from the compiler's
    own statement and from the loop's condition, bytes of a tuple."""
    text = """
%cond (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %c = s32[] constant(5)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%inner (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=1
  %ar = (bf16[2,16]{1,0}, bf16[4]{0}) all-reduce(%a, %b), replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add
  ROOT %t = (s32[], f32[8]) tuple(%i, %x)
}

%outer (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %w = (s32[], f32[8]) while(%p), condition=%cond, body=%inner
  ROOT %t = (s32[], f32[8]) tuple(%i, %x)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %w = (s32[], f32[8]) while(%t), condition=%c2, body=%outer, backend_config={"known_trip_count":{"n":"4"}}
  %ars = f32[16,4]{1,0} all-reduce-start(%a), replica_groups={{0,2},{1,3}}, to_apply=%add
  ROOT %g = f32[8] get-tuple-element(%w), index=1
}
"""
    rows = hlo_collectives.collectives(text)
    by_comp = {r["computation"]: r for r in rows}
    inner, entry = by_comp["inner"], by_comp["main"]
    assert inner["loops"] == (4, 5) and inner["calls"] == 20
    assert inner["bytes"] == 2 * 32 + 2 * 4 and inner["dtypes"] == ["bf16"]
    assert entry["loops"] == () and entry["bytes"] == 256
    dp = hlo_collectives.mesh_groups({"dp": 2, "tp": 2}, ["dp"])
    assert inner["groups"] == entry["groups"] == dp == {(0, 2), (1, 3)}
    assert hlo_collectives.mesh_groups({"dp": 2, "tp": 2}, ["tp"]) == {
        (0, 1), (2, 3)}
    assert hlo_collectives.reductions_over(rows, dp, min_bytes=100) == [entry]
    assert "4x5" in hlo_collectives.table(rows)


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------

SEQ, VOCAB = 32, 128


def _model(case):
    if case == "moe_aux":
        from megatron_llm_tpu.config import TransformerConfig
        from megatron_llm_tpu.models.gpt import GPTModel

        # 3 experts never divide over dp 2: the experts are replicated and
        # the routing losses are each rank's own, averaged
        return GPTModel(TransformerConfig(
            num_layers=2, hidden_size=32, num_attention_heads=4,
            ffn_hidden_size=64, seq_length=SEQ, max_position_embeddings=SEQ,
            padded_vocab_size=VOCAB, tie_embed_logits=True,
            glu_activation="swiglu", add_bias_linear=False, num_experts=3,
            moe_top_k=2, moe_capacity_factor=8.0, moe_aux_loss_coeff=0.1,
            moe_z_loss_coeff=0.01, hidden_dropout=0.0,
            attention_dropout=0.0, use_flash_attn=False))
    from megatron_llm_tpu.models.llama import LlamaModel, llama_config

    return LlamaModel(llama_config(
        "tiny", seq_length=SEQ, max_position_embeddings=SEQ,
        padded_vocab_size=VOCAB))


def _sequences(case, n=8):
    rng = np.random.RandomState(3)
    toks = rng.randint(0, VOCAB, (n, SEQ)).astype(np.int32)
    mask = np.ones((n, SEQ), np.float32)
    if case == "uneven_mask":
        # even rows (dp rank 0) count 5 tokens, odd rows (rank 1) 29
        mask[0::2, 5:] = 0.0
        mask[1::2, :3] = 0.0
    return toks, np.roll(toks, -1, -1), mask


def _one_step(case, dp, num_micro, tp=1):
    """One SGD step of lr 1 from fixed weights on the 8 sequences, laid out
    as ``num_micro`` microbatches over ``dp`` ranks: the logged loss, the
    gradient's norm, and the gradient (old - new parameters)."""
    mesh = _mesh(tp=tp, dp=dp)
    model = _model(case)
    params = sh.init_params(model, jax.random.PRNGKey(0))
    before = jax.device_get(params)
    tc = TrainConfig(micro_batch_size=8 // (dp * num_micro),
                     global_batch_size=8, lr=1.0, optimizer="sgd",
                     sgd_momentum=0.0, clip_grad=0.0, weight_decay=0.0)
    pc = ParallelConfig(tensor_model_parallel_size=tp,
                        data_parallel_size=dp, sequence_parallel=tp > 1)
    opt = MegatronOptimizer(tc)
    dsh = NamedSharding(mesh, P(None, "dp", None))
    batch = {k: jax.device_put(
        jnp.asarray(v).reshape(num_micro, 8 // num_micro, SEQ), dsh)
        for k, v in zip(("tokens", "labels", "loss_mask"), _sequences(case))}
    step = build_train_step(model, opt, pc, num_micro)
    after, _, m = step(params, opt.init(params), batch,
                       jax.random.PRNGKey(1), 1.0, 0.0)
    grads = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b),
                                   before, jax.device_get(after))
    return {k: float(v) for k, v in m.items()}, grads, model, before


def _close(a, b, rel=1e-5):
    """To 1e-5 of the leaf's largest entry, and to the two ulps of a
    weight near 1 that ``old - new`` cannot tell apart."""
    scale = float(np.max(np.abs(b)))
    np.testing.assert_allclose(a, b, rtol=0, atol=rel * scale + 2.5e-7)


@pytest.mark.parametrize("case,ref_layout", [
    # the same 8 sequences, one a microbatch, on one rank
    ("dense", (1, 8)),
    ("moe_aux", (1, 8)),
    # the same 4 microbatches of 2 on one rank: a microbatch's masked mean
    # divides by BOTH ranks' token count (5 + 29), which a mean of the two
    # ranks' own means (dp 1 x 8 would be that) gets wrong
    ("uneven_mask", (1, 4)),
    # tensor parallel with sequence parallelism under the manual dp region
    ("dense_tp2", (1, 8)),
    # and with the flash kernel (interpreted), as on the chip: its own
    # shard_map nests in the dp region, and its backward must not mix the
    # ranks' dq / dk / dv
    ("dense_tp2_flash", (1, 8)),
])
def test_dp2_x_4_microbatches_is_the_same_step(case, ref_layout,
                                               monkeypatch):
    if case.endswith("_flash"):
        from megatron_llm_tpu.ops.pallas import flash_attention as F

        monkeypatch.setattr(F, "_INTERPRET", True)
        case = case.replace("_flash", "")
    tp = 2 if case.endswith("tp2") else 1
    case = case.replace("_tp2", "")
    m2, g2, _, _ = _one_step(case, dp=2, num_micro=4, tp=tp)
    m1, g1, _, _ = _one_step(case, *ref_layout)
    assert set(m2) == set(m1)
    if case == "moe_aux":
        assert "moe aux loss" in m2 and "moe z loss" in m2
    for k in m1:
        assert m2[k] == pytest.approx(m1[k], rel=1e-5, abs=1e-7), k
    for a, b in zip(jax.tree_util.tree_leaves(g2),
                    jax.tree_util.tree_leaves(g1)):
        _close(a, b)


@pytest.mark.parametrize("case,dp,num_micro", [
    ("dense", 1, 1),
    ("uneven_mask", 1, 1),
    ("uneven_mask", 2, 1),
    ("uneven_mask", 2, 4),
])
def test_the_step_is_the_plain_gradient(case, dp, num_micro):
    m, g, model, before = _one_step(case, dp=dp, num_micro=num_micro)
    topology.destroy_model_parallel()
    toks, labels, mask = (jnp.asarray(x).reshape(num_micro, -1, SEQ)
                          for x in _sequences(case))

    def whole(p):
        return sum(default_loss_func(
            model(p, toks[i], labels=labels[i], train=True), mask[i])
            for i in range(num_micro)) / num_micro

    loss, want = jax.value_and_grad(whole)(
        jax.tree_util.tree_map(jnp.asarray, before))
    assert m["lm loss"] == pytest.approx(float(loss), rel=1e-5)
    assert m["grad_norm"] == pytest.approx(
        float(global_grad_norm(want)), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(want)):
        _close(a, np.asarray(b))


def test_expert_parallel_parameters_keep_the_global_program():
    """Experts folded into dp hold shards of parameters over dp: no rank
    owns a whole copy, so the step stays one program for GSPMD."""
    from megatron_llm_tpu.training import _DataRanks

    _mesh(tp=1, dp=2)
    replicated = _model("moe_aux")
    sharded = type(replicated)(dataclasses.replace(
        replicated.cfg, num_experts=4, moe_expert_axis="expert"))
    batch = {"tokens": jnp.zeros((4, 2, SEQ), jnp.int32)}
    for model, axes in ((replicated, ("dp",)), (sharded, ())):
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        assert _DataRanks.of(model, params, batch).axes == axes
    odd = {"tokens": jnp.zeros((4, 3, SEQ), jnp.int32)}
    params = jax.eval_shape(replicated.init, jax.random.PRNGKey(0))
    assert _DataRanks.of(replicated, params, odd).axes == ()
