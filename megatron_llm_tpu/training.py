"""Training runtime: train_step + pretrain driver.

Reference: ``megatron/training.py`` — ``pretrain`` (:55-169), ``train_step``
(:393-459), ``_train`` loop (:654-770), ``training_log`` (:462-641).

TPU re-design: the reference's train_step is imperative — a Python
microbatch loop (schedules.py) each issuing fwd/bwd, then three grad-sync
phases, then the optimizer.  Here the *entire* step — microbatch
accumulation loop, loss scaling, grad clip, inf check, Adam, master->param
cast — is one jitted function: ``lax.scan`` over the microbatch axis, then
the functional optimizer.

Data parallelism is the reference's own shape (``allreduce_gradients`` once
a step, distributed.py:202), written as a manual region: the scan runs
under ``shard_map`` over the data axes (``_DataRanks``; tp / cp stay with
GSPMD inside it).  Every dp rank accumulates, in fp32, the gradient of ITS
rows of each microbatch; nothing parameter-shaped crosses dp inside the
microbatch scan nor inside the layer scans under it, and after the scan
every leaf is summed over dp exactly once, in fp32 (``_DataRanks.run``),
before the optimizer.  Left to GSPMD, the sum sits where the gradient is
produced — in the backward layer scan, once a layer a microbatch, in the
parameters' dtype (PERF.md section 6, PR 31).  ``loss_func`` still sees the
global microbatch: the ranks exchange the per-token losses (a few KB), not
gradients.  ``_ReadStep`` counts the reductions in the compiled program.
"""

from __future__ import annotations

import logging
import math
import sys
import time
from functools import partial
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu.config import TrainConfig, TransformerConfig, ParallelConfig
from megatron_llm_tpu.optimizer import MegatronOptimizer, OptimizerParamScheduler
from megatron_llm_tpu.optimizer.optimizer import global_grad_norm
from megatron_llm_tpu import health
from megatron_llm_tpu import hlo_collectives
from megatron_llm_tpu import random as mrandom
from megatron_llm_tpu import telemetry
from megatron_llm_tpu import topology
from megatron_llm_tpu import tracing
from megatron_llm_tpu.global_vars import get_counters
from megatron_llm_tpu.parallel.sharding import logical_to_mesh
from megatron_llm_tpu.topology import SLICE_AXIS

logger = logging.getLogger("megatron_llm_tpu")

# --log_params_norm without layer stats re-reduces the whole param tree at
# every log boundary; jit once so it compiles a single cached program
# instead of retracing op-by-op eagerly each time
_params_norm_jit = jax.jit(global_grad_norm)


def average_losses_across_data_parallel_group(losses):
    """Reference: megatron/utils.py:100-107 — with a single-controller mesh
    the loss pytree is already global; the mean is the DP-averaged value."""
    return jax.tree_util.tree_map(jnp.mean, losses)


def default_loss_func(loss_tok: jax.Array, loss_mask: jax.Array):
    """Masked token-mean loss (reference: finetune.py:201-218)."""
    loss_mask = loss_mask.astype(jnp.float32)
    return jnp.sum(loss_tok * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)


def _live_data_axes():
    """(mesh, the data axes more than one device wide); (None, ()) with no
    mesh."""
    mesh = topology._MESH
    if mesh is None:
        return None, ()
    return mesh, tuple(a for a in topology.data_axes() if mesh.shape[a] > 1)


class _DataRanks:
    """The data-parallel ranks one train step runs on, and what crosses them.

    With ``axes`` the step's microbatch scan is manual over those mesh axes
    (``shard_map``; tp / cp / pp stay with GSPMD): a rank holds its rows of
    every microbatch and a whole copy of the parameters.  Without (no mesh,
    one rank, rows that do not divide, or parameters that are themselves
    sharded over the data axes: MoE experts folded into dp, which need every
    rank's tokens) every method is the identity and the step is one global
    program for GSPMD to partition."""

    def __init__(self, mesh=None, axes=()):
        self.mesh, self.axes = mesh, tuple(axes)
        self.n = math.prod(mesh.shape[a] for a in self.axes)

    @classmethod
    def of(cls, model, params, batch):
        ranks = cls(*_live_data_axes())
        # every batch entry is [num_micro, rows, ...]; an entry with no rows
        # axis, or rows that do not divide, leaves the split to GSPMD
        rows = {x.shape[1] if x.ndim > 1 else 1
                for x in jax.tree_util.tree_leaves(batch)}
        if (ranks.n == 1 or any(r % ranks.n for r in rows)
                or ranks._hold_shards_of(model, params)):
            return cls()
        return ranks

    def _hold_shards_of(self, model, params) -> bool:
        specs = getattr(model, "param_specs", None)
        if specs is None:
            return False
        return any(
            a in self.axes
            for spec in jax.tree_util.tree_leaves(
                specs(params), is_leaf=lambda v: isinstance(v, tuple))
            for entry in logical_to_mesh(spec)
            for a in (entry if isinstance(entry, tuple) else (entry,)))

    def run(self, fn, staged: bool = False):
        """``fn(params, batch, rng_key, scale, ranks)`` -> (fp32 gradients,
        losses, aux losses) on every rank, and then the step's ONE
        reduction: every gradient leaf summed over the ranks.

        The ranks' gradients leave the manual region side by side on a new
        leading axis and are summed outside it, so the reduction is the
        partitioner's own ``all-reduce`` (one a leaf, f32, after every
        loop), under the name a trace's reader knows.  ``staged`` (a
        multi-slice mesh) sums inside the region instead, in-slice axes
        first and then across slices, as two collectives
        (``multislice.hierarchical_psum``): left to the partitioner the
        two hops fold into one."""
        fn = partial(fn, ranks=self)
        if not self.axes:
            return fn
        staged = staged and SLICE_AXIS in self.axes

        def leave(g):
            if not staged:
                return g[None]
            from megatron_llm_tpu.multislice import hierarchical_psum
            return hierarchical_psum(
                g, tuple(a for a in self.axes if a != SLICE_AXIS))

        def on_a_rank(*args):
            grads, losses, auxes = fn(*args)
            return jax.tree_util.tree_map(leave, grads), losses, auxes

        region = jax.shard_map(
            on_a_rank, mesh=self.mesh,
            in_specs=(P(), P(None, self.axes), P(), P()),
            out_specs=(P() if staged else P(self.axes), P(), P()),
            axis_names=set(self.axes), check_vma=False)
        if staged:
            return region

        def whole(*args):
            grads, losses, auxes = region(*args)
            return (jax.tree_util.tree_map(lambda g: g.sum(axis=0), grads),
                    losses, auxes)

        return whole

    def fold_in(self, key):
        if not self.axes:
            return key
        return jax.random.fold_in(key, jax.lax.axis_index(self.axes))

    def rows_of_all(self, tree):
        """Every rank's rows (leading axis), on every rank."""
        if not self.axes:
            return tree
        return jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, self.axes, axis=0, tiled=True),
            tree)

    def own_rows(self, tree):
        """Of a cotangent for ``rows_of_all``'s result, this rank's part."""
        if not self.axes:
            return tree
        i = jax.lax.axis_index(self.axes)

        def own(c):
            rows = c.shape[0] // self.n
            return jax.lax.dynamic_slice_in_dim(c, i * rows, rows, axis=0)

        return jax.tree_util.tree_map(own, tree)

    def mean(self, x):
        """A per-rank statistic (the MoE routing losses), averaged."""
        if not self.axes or x is None:
            return x
        return jax.lax.pmean(x, self.axes)

    def share(self, ct):
        """Of a cotangent for ``mean``'s result, this rank's part."""
        return ct if not self.axes or ct is None else ct / self.n


class _ReadStep:
    """The jitted train step, compiled ahead of its first call (once: the
    calls run that executable) so that the compiled program's own text can
    say where its data-parallel gradient reductions sit.  One
    ``train_step_program`` record goes to the structured log
    (``--structured_log_dir``): ``dp_grad_reductions_per_step`` is counted
    from the text (``hlo_collectives``), not asserted from this file, and
    ``collectives_by_edge`` gives every collective's calls and bytes a step
    under the mesh axes its replica groups run over.  The instruction
    table they are read from is registered beside the serve loop's
    (``loop_profiler.live_programs()['train_step']``)."""

    def __init__(self, jitted, num_microbatches: int):
        self.jitted, self.num_microbatches = jitted, num_microbatches
        self.compiled = None
        self.program: Dict = {}

    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)

    def __call__(self, *args):
        if self.compiled is None:
            self.compiled = self.jitted.lower(*args).compile()
            self._read()
        try:
            return self.compiled(*args)
        except (TypeError, ValueError):
            # other shapes, dtypes or placements than the first call's:
            # jit compiles for them, and the trainer counts a recompile
            return self.jitted(*args)

    def _read(self):
        mesh, axes = _live_data_axes()
        self.program = {"kind": "train_step_program",
                        "num_microbatches": self.num_microbatches,
                        "dp": math.prod(mesh.shape[a] for a in axes)}
        try:
            # every instruction of the step under the name a trace prints
            # for it, a collective with the mesh axes its groups run over
            # (its edge): ``live_programs()['train_step']``
            table = hlo_collectives.ProgramTable(
                "train_step",
                hlo_collectives.instructions(self.compiled.as_text()),
                mesh_shape=dict(mesh.shape) if mesh is not None else None)
            from megatron_llm_tpu.serving import loop_profiler
            # (the table alone: not this object and its executable)
            loop_profiler.register_program("train_step", lambda: table)
            rows = [r for r in table.rows if "family" in r]
            # over dp, over slice, or (the flat multi-slice sum) over both
            over = [axes[k:j + 1] for k in range(len(axes))
                    for j in range(k, len(axes))]
            found = [r for sub in over
                     for r in hlo_collectives.reductions_over(
                         rows, hlo_collectives.mesh_groups(
                             dict(mesh.shape), sub))]
            self.program.update(
                dp_grad_reductions_per_step=sum(r["calls"] for r in found),
                dp_grad_reductions_in_loops=sum(
                    r["calls"] for r in found if r["loops"]),
                dp_grad_reduction_bytes_per_step=sum(
                    r["bytes"] * r["calls"] for r in found),
                dp_grad_reduction_dtypes=sorted(
                    {d for r in found for d in r["dtypes"]}),
                collectives_by_edge=table.collectives_by_edge())
        except Exception as e:  # noqa: BLE001 - a reading, never a failure
            logger.warning("train step's collectives not read: %s", e)
        if jax.process_index() == 0:
            print(f" train step program: {self.program}", flush=True)
        stream = telemetry.get_stream()
        if stream is not None:
            stream.emit(self.program)


def build_train_step(
    model,
    optimizer: MegatronOptimizer,
    parallel_cfg: ParallelConfig,
    num_microbatches: int,
    loss_func: Callable = default_loss_func,
    forward_only: bool = False,
    log_num_zeros_in_grad: bool = False,
    log_layer_stats: bool = False,
):
    """Compile one global training step.

    Batch layout: dict of arrays with leading axes [num_micro, batch, seq]
    where ``batch`` is the *global* batch per microbatch (dp-sharded).
    Expected keys: tokens, labels, loss_mask; optional position_ids,
    attention_mask.
    """
    sp = parallel_cfg.sequence_parallel
    # MoE models return (per-token loss, [lb, z] routing aux) — static on
    # the model config, so BERT/T5's own tuple returns are unaffected
    moe_on = getattr(getattr(model, "cfg", None), "num_experts", 0) > 1
    # multi-slice hierarchical (ICI-then-DCN) gradient staging: the one
    # reduction after the microbatch scan sums in-slice first, then across
    # slices, as two collectives (multislice.hierarchical_psum)
    hierarchical = ((getattr(parallel_cfg, "num_slices", 1) or 1) > 1
                    and getattr(parallel_cfg, "multislice_hierarchical",
                                False))

    def forward(params, micro, rng_key):
        """The model on one microbatch: (outputs, MoE routing aux or None)."""
        # every batch key beyond the canonical trio is forwarded as a model
        # kwarg (tokentype_ids / sentence_order for BERT, encoder inputs for
        # T5 — mirroring the per-arch get_batch of the reference entry points)
        extra = {
            k: v for k, v in micro.items()
            if k not in ("tokens", "labels", "loss_mask")
        }
        out = model(
            params,
            micro["tokens"],
            labels=micro["labels"],
            rng_key=rng_key,
            train=not forward_only,
            sequence_parallel=sp,
            **extra,
        )
        return out if moe_on else (out, None)

    def objective(out, moe_aux, loss_mask, scale):
        """What a microbatch adds to the step's loss, from the model's
        outputs for the GLOBAL microbatch."""
        res = loss_func(out, loss_mask)
        # loss_func may return (total, {metric: scalar}) to log components
        # separately (reference logs a loss dict per arch, e.g. BERT's
        # {'lm loss', 'sop loss'} — pretrain_bert.py loss_func)
        loss, aux = res if isinstance(res, tuple) else (res, {})
        total = loss
        if moe_aux is not None:
            # the routing losses enter the optimized objective; the logged
            # 'lm loss' stays the pure LM component, with the balance loss
            # (and the z-loss, when enabled) reported under their own names
            # (reference's per-key loss dict)
            cfg = model.cfg
            aux = {**aux, "moe aux loss": moe_aux[0]}
            if cfg.moe_z_loss_coeff > 0.0:
                aux["moe z loss"] = moe_aux[1]
            total = (loss + cfg.moe_aux_loss_coeff * moe_aux[0]
                     + cfg.moe_z_loss_coeff * moe_aux[1])
        # scaled loss for fp16 (reference: optimizer.scale_loss,
        # schedules.py:142-202); scale==1 for bf16/fp32
        return total * scale / num_microbatches, (loss, aux)

    if forward_only:

        def eval_step(params, batch, rng_key):
            def body(carry, micro):
                out, moe_aux = forward(params, micro, None)
                _, (loss, _aux) = objective(out, moe_aux,
                                            micro["loss_mask"], 1.0)
                return carry, loss

            _, losses = jax.lax.scan(body, 0, batch)
            return jnp.mean(losses)

        return jax.jit(eval_step)

    def accumulate(params, batch, rng_key, scale, ranks):
        """fp32 gradients of the whole step, its losses and aux losses.

        With ``ranks`` this runs once on every data-parallel rank, on that
        rank's rows of each microbatch: the rank accumulates the gradient
        of ITS rows and nothing parameter-shaped crosses ``ranks.axes``
        in here (``ranks.run`` sums the ranks' results, once).  Without,
        on the global batch."""

        def body(grads_acc, scanned):
            micro, idx = scanned
            mkey = ranks.fold_in(jax.random.fold_in(rng_key, idx))
            (out, moe_aux), pullback = jax.vjp(
                lambda p: forward(p, micro, mkey), params)
            # loss_func sees the global microbatch (a masked mean's
            # denominator, in-batch negatives); every rank differentiates it
            # with respect to all rows and pulls back its own rows'
            # cotangent, so no collective carries a gradient in here
            (_, (loss, aux)), ct = jax.value_and_grad(
                objective, argnums=(0, 1), has_aux=True)(
                ranks.rows_of_all(out), ranks.mean(moe_aux),
                ranks.rows_of_all(micro["loss_mask"]), scale)
            (g,) = pullback((ranks.own_rows(ct[0]), ranks.share(ct[1])))
            grads_acc = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), grads_acc, g
            )
            return grads_acc, (loss, aux)

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        grads, (losses, auxes) = jax.lax.scan(
            body, zeros, (batch, jnp.arange(num_microbatches))
        )
        return grads, losses, auxes

    def train_step(params, opt_state, batch, rng_key, lr, wd):
        scale = opt_state.grad_scaler.scale
        ranks = _DataRanks.of(model, params, batch)
        grads, losses, auxes = ranks.run(accumulate, staged=hierarchical)(
            params, batch, rng_key, scale)
        new_params, new_opt_state, stats = optimizer.step(
            params, grads, opt_state, lr, wd, layer_stats=log_layer_stats
        )
        metrics = {
            "lm loss": jnp.mean(losses),
            "grad_norm": stats["grad_norm"],
            "loss_scale": stats["loss_scale"],
            "skipped_iter": stats["found_inf"].astype(jnp.int32),
        }
        if log_layer_stats:
            # fixed-shape [G] arrays — one extra fused output, no shape
            # dependence on anything but the param tree, so steady state
            # stays zero-recompile
            metrics["layer_stats"] = stats["layer_stats"]
        if log_num_zeros_in_grad:   # reference --log_num_zeros_in_grad
            metrics["num zeros"] = sum(
                jnp.sum(g == 0.0)
                for g in jax.tree_util.tree_leaves(grads)
            ).astype(jnp.int32)
        # component losses reported by the loss_func override the total
        # under their own names ("lm loss" stays the true MLM loss for BERT)
        metrics.update({k: jnp.mean(v) for k, v in auxes.items()})
        return new_params, new_opt_state, metrics

    return _ReadStep(jax.jit(train_step, donate_argnums=(0, 1)),
                     num_microbatches)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def training_log(
    iteration: int,
    train_iters: int,
    metrics: Dict[str, float],
    elapsed_per_iter: float,
    tokens_per_iter: float,
    lr: float,
    writer=None,
    printer=print,
    throughput: Optional[Dict] = None,
    interval_time: Optional[float] = None,
):
    """One console/TB log line (reference: training.py:462-641,
    tokens/sec at :591-609).

    ``throughput`` is a ``telemetry.ThroughputCalculator.compute()``
    record; when present the line carries tokens/sec/device, achieved
    TFLOPs/device and MFU (null MFU fields — unknown peak, or the
    fabrication guard — are simply omitted, never printed as numbers).

    ``elapsed_per_iter`` is *train-only* step time (eval and
    checkpoint-save wall-clock excluded, so throughput/MFU reflect the
    step the hardware actually ran); ``interval_time`` is the raw
    log-interval wall per iteration including those sections — both are
    reported so a gap between them is visible instead of silently
    deflating MFU."""
    tps = tokens_per_iter / max(elapsed_per_iter, 1e-9)
    line = (
        f" iteration {iteration:8d}/{train_iters:8d} |"
        f" elapsed time per iteration (ms): {elapsed_per_iter * 1000.0:.1f} |"
    )
    if interval_time is not None:
        line += (f" interval time per iteration (ms):"
                 f" {interval_time * 1000.0:.1f} |")
    line += f" tokens per second: {tps:.1f} |"
    if throughput is not None:
        line += (f" tokens per second per device:"
                 f" {throughput['tokens_per_sec_per_device']:.1f} |")
        if throughput.get("tflops_per_device") is not None:
            line += (f" TFLOPs per device:"
                     f" {throughput['tflops_per_device']:.1f} |")
        if throughput.get("mfu") is not None:
            line += f" MFU: {throughput['mfu'] * 100.0:.1f}% |"
    line += (
        f" learning rate: {lr:.3E} |"
        f" lm loss: {float(metrics.get('lm loss', 0.0)):.6E} |"
        f" loss scale: {float(metrics.get('loss_scale', 1.0)):.1f} |"
        f" grad norm: {float(metrics.get('grad_norm', 0.0)):.3f} |"
        f" skipped iterations: {int(metrics.get('skipped_iter', 0))} |"
    )
    # extra loss components (e.g. BERT's 'sop loss') appear after the
    # standard fields, like the reference's per-key loss dict logging
    known = {"lm loss", "loss_scale", "grad_norm", "skipped_iter"}
    for k in sorted(set(metrics) - known):
        v = metrics[k]
        # recovery counters and other integral extras read better as ints
        line += (f" {k}: {v} |" if isinstance(v, int)
                 else f" {k}: {float(v):.6E} |")
    printer(line)
    if writer is not None:
        for k, v in metrics.items():
            writer.add_scalar(k, float(v), iteration)
        writer.add_scalar("tokens_per_sec", tps, iteration)
        writer.add_scalar("learning_rate", lr, iteration)
        if interval_time is not None:
            writer.add_scalar("interval-time-per-iteration", interval_time,
                              iteration)
        if throughput is not None:
            writer.add_scalar("tokens_per_sec_per_device",
                              throughput["tokens_per_sec_per_device"],
                              iteration)
            if throughput.get("tflops_per_device") is not None:
                writer.add_scalar("tflops_per_device",
                                  throughput["tflops_per_device"], iteration)
            if throughput.get("mfu") is not None:
                writer.add_scalar("mfu", throughput["mfu"], iteration)
    return tps


def pretrain(
    model,
    params,
    train_cfg: TrainConfig,
    parallel_cfg: ParallelConfig,
    batch_iterator,
    *,
    scheduler: Optional[OptimizerParamScheduler] = None,
    optimizer: Optional[MegatronOptimizer] = None,
    loss_func: Callable = default_loss_func,
    log_interval: int = 10,
    save_interval: Optional[int] = None,
    save_dir: Optional[str] = None,
    eval_iterator=None,
    eval_interval: Optional[int] = None,
    eval_iters: int = 10,
    exit_signal_handler=None,
    start_iteration: int = 0,
    opt_state=None,
    on_metrics=None,
    timers=None,
    skip_iters=(),
    exit_interval: Optional[int] = None,
    exit_duration_in_mins: Optional[float] = None,
    train_step=None,
    save_fn=None,
    log_params_norm: bool = False,
    log_num_zeros_in_grad: bool = False,
    log_layer_stats_interval: int = 0,
    writer=None,
    tensorboard_log_interval: int = 1,
    log_timers: bool = True,
    async_save: bool = False,
    log_memory: bool = False,
    log_batch_size: bool = False,
    log_world_size: bool = False,
    log_validation_ppl: bool = False,
    resilience=None,
    telemetry=None,
    preempt_exit_code: int = 0,
):
    """Minimal-dependency pretrain loop (the full CLI driver lives in
    ``finetune.py`` / ``pretrain_gpt.py`` at the repo root).

    ``batch_iterator`` yields batch dicts shaped
    [num_micro, global_batch, seq] (see build_train_step).

    Behavioral flags (reference ``training.py:397-399,731-767``):
      * ``skip_iters`` — iteration numbers that run forward-only (loss is
        still computed/logged, no parameter update).
      * ``exit_interval`` — save + exit when iteration %% interval == 0.
      * ``exit_duration_in_mins`` — save + exit once the loop has run
        this long.

    Timers (reference ``training.py:500-525``): phases that exist under
    the fused-jit TPU design are timed — ``batch-generator``,
    ``train-step`` (async dispatch), ``train-step-sync`` (device wait at
    the log boundary; dispatch+sync ~ the reference's forward-backward +
    optimizer total), ``save-checkpoint``, ``eval-time``.  Finer split
    timers (forward vs backward vs optimizer) do not exist because one
    XLA program runs all three fused — that is the point of the design.

    ``train_step`` overrides the compiled step (same signature as
    ``build_train_step``'s result) — how ``finetune.py`` drives the
    pipelined engine through this one loop.  With a custom step, skipped
    iterations have no forward-only program, so their loss logs as NaN,
    and ``eval_iterator`` is rejected.  ``save_fn(save_dir, it, params,
    opt_state, scheduler)`` overrides checkpoint writing (e.g. to convert
    a VPP stage-major layout back to natural order first).

    ``resilience`` (a ``resilience.ResilienceManager``) arms the
    fault-tolerance runtime: fault injection before/into each batch,
    rolling host snapshots, NaN/spike detection at check boundaries with
    rewind, and the hang watchdog around dispatch/sync.  All of it is
    host-side — the jitted step is untouched.

    ``log_layer_stats_interval`` (reference-free; see ``health.py``) arms
    the model-health observatory: the train step emits per-group
    grad/param/update norms + non-finite grad counts on-device, the host
    fetches them at log boundaries (feeding --log_params_norm and the
    resilience NaN localizer) and emits the full record into JSONL /
    TensorBoard every ``interval`` iterations.

    ``telemetry`` (a ``telemetry.Telemetry``) carries the observability
    runtime: throughput/MFU accounting at log boundaries, the structured
    JSONL stream + flight recorder, and in-loop profiler capture.  When
    None, a default throughput-only bundle is built from the model so
    tokens/sec/device + MFU appear in every run's log lines for free.
    Like resilience, everything is host-side and (for the stream/flight
    recorder) off the device-sync path except at log boundaries.
    """
    from megatron_llm_tpu import checkpointing
    from megatron_llm_tpu.telemetry import Telemetry, device_memory_stats
    from megatron_llm_tpu.timers import Timers

    if timers is None:
        timers = Timers(log_level=2)
    if telemetry is None:
        telemetry = Telemetry.default(model)
    stream = telemetry.stream
    profiler = telemetry.profiler
    trace = getattr(telemetry, "tracing", None)
    if trace is not None:
        tracing.install_tracing(trace)
        recompile = trace.recompile
    else:
        # compiles are heard whether or not --trace_dir asks for the
        # span file: ``recompiles`` is in every training log
        recompile = tracing.RecompileDetector()
        tracing.install_detector(recompile)
    straggler = trace.straggler if trace is not None else None
    skip_iters = frozenset(skip_iters or ())

    num_slices = getattr(parallel_cfg, "num_slices", 1) or 1
    num_micro = max(
        train_cfg.global_batch_size
        // (train_cfg.micro_batch_size * parallel_cfg.data_parallel_size
            * num_slices),
        1,
    )
    # per-slice attribution: map the gathered per-host timer snapshots
    # onto slices so the JSONL stream and straggler events name the slice
    # the fleet is waiting on (multi-slice runs only)
    slice_map = None
    if num_slices > 1:
        from megatron_llm_tpu import multislice
        slice_map = multislice.host_slice_map(num_slices=num_slices)
        if straggler is not None:
            straggler.host_slice_map = slice_map
    if optimizer is None:
        optimizer = MegatronOptimizer(
            train_cfg, params_dtype=jax.tree_util.tree_leaves(params)[0].dtype
        )
    if opt_state is None:
        with tracing.startup_span("build_optimizer"):
            opt_state = optimizer.init(params)
    if scheduler is None:
        # NB: `x if x is not None else y`, not `or` — an explicit 0.0
        # start/end weight decay is a legitimate ramp-from-zero config
        swd = train_cfg.start_weight_decay
        ewd = train_cfg.end_weight_decay
        scheduler = OptimizerParamScheduler(
            max_lr=train_cfg.lr,
            min_lr=train_cfg.min_lr,
            lr_warmup_steps=train_cfg.lr_warmup_iters,
            lr_decay_steps=train_cfg.lr_decay_iters or max(train_cfg.train_iters, 1),
            lr_decay_style=train_cfg.lr_decay_style,
            start_wd=swd if swd is not None else train_cfg.weight_decay,
            end_wd=ewd if ewd is not None else train_cfg.weight_decay,
            wd_incr_steps=max(train_cfg.train_iters, 1),
            wd_incr_style=train_cfg.weight_decay_incr_style,
        )
        scheduler.num_steps = start_iteration

    custom_step = train_step is not None
    if custom_step and eval_iterator is not None:
        raise ValueError(
            "eval_iterator is not supported with a custom train_step "
            "(no forward-only program exists for it)")
    with tracing.startup_span("build_train_step"):
        if not custom_step:
            train_step = build_train_step(
                model, optimizer, parallel_cfg, num_micro, loss_func,
                log_num_zeros_in_grad=log_num_zeros_in_grad,
                log_layer_stats=log_layer_stats_interval > 0,
            )
        eval_step = (
            build_train_step(model, optimizer, parallel_cfg, num_micro,
                             loss_func, forward_only=True)
            if eval_iterator is not None
            else None
        )

    base_key = mrandom.base_key(train_cfg.seed)
    counters = get_counters()
    iteration = start_iteration
    last_time = time.perf_counter()
    train_start = time.perf_counter()
    # eval + checkpoint-save wall-clock inside the current log interval:
    # subtracted from the interval so elapsed-per-iteration (and thus
    # tokens/sec + MFU) measures the training step, not the pauses
    # (mutable cell because _save below also accumulates into it)
    non_train = [0.0]
    skip_step = None  # forward-only step, compiled lazily on first skip
    # the start-up timeline (tracing.startup_span): the first call of the
    # step until its loss is home is ``first_step``, and the program is
    # "ready" at the second's entry
    steps_called = 0
    ls_names = None   # health group names, resolved on first stats fetch

    def _layer_stats_record(ls_dev):
        """device stats dict -> host JSONL record ({groups, grad_norm,
        param_norm, update_norm, update_ratio, nonfinite_grads})."""
        nonlocal ls_names
        if ls_names is None:
            ls_names = health.layer_group_names(params)
        return health.to_record(ls_names, jax.device_get(ls_dev))

    injector = resilience.injector if resilience is not None else None
    watchdog = resilience.watchdog if resilience is not None else None
    if resilience is not None:
        resilience.bind_rescue(
            save_dir,
            checkpointing.config_to_args(getattr(model, "cfg", None)))
    if watchdog is not None:
        # armed only after the first step completes: iteration 1 includes
        # XLA compilation, which can dwarf any sane hang timeout
        watchdog.start()
        watchdog.pause()

    def _signals(consensus: bool) -> bool:
        # older handlers (tests, user code) may lack the consensus kwarg
        try:
            return exit_signal_handler.signals_received(consensus=consensus)
        except TypeError:
            return exit_signal_handler.signals_received()

    def _save(it):
        if watchdog is not None:
            watchdog.pause()        # storage latency is not a hang
        t0 = time.perf_counter()
        with tracing.span("checkpoint_save", "checkpoint", iteration=it):
            timers("save-checkpoint", log_level=0).start()
            if save_fn is not None:
                save_fn(save_dir, it, params, opt_state, scheduler)
            else:
                checkpointing.save_checkpoint(
                    save_dir, it, params, opt_state, scheduler,
                    consumed_samples=counters.get("samples", 0),
                    args=checkpointing.config_to_args(
                        getattr(model, "cfg", None)),
                    async_save=async_save,
                )
            timers("save-checkpoint").stop()
        non_train[0] += time.perf_counter() - t0
        if watchdog is not None:
            watchdog.resume()

    # one root span spans the whole loop (category "run" is trace-only,
    # so goodput never counts it) — every second of the run nests under
    # it, which is what makes the exported trace's coverage ~100%.
    # Entered by hand so the loop body keeps its indentation; the
    # finally below closes it on every exit path (SystemExit included).
    root_span = tracing.span("train", "run", start_iteration=start_iteration)
    root_span.__enter__()
    try:
        while iteration < train_cfg.train_iters:
            if resilience is not None and resilience.snapshot_due(iteration):
                # host-copy the last known-good state BEFORE this step runs
                # (donation invalidates the old buffers once dispatched)
                resilience.take_snapshot(iteration, params, opt_state,
                                         scheduler)
            if injector is not None:
                injector.before_iteration(iteration + 1)
            if profiler is not None:
                profiler.maybe_start(iteration + 1)
            timers("batch-generator", log_level=1).start()
            with tracing.span("data_next", "data"):
                batch = next(batch_iterator)
            timers("batch-generator").stop()
            if injector is not None:
                batch = injector.poison_batch(iteration + 1, batch)
            lr, wd = scheduler.step(1)
            if resilience is not None:
                lr = lr * resilience.lr_scale
            step_key = jax.random.fold_in(base_key, iteration)
            if (iteration + 1) in skip_iters:
                # reference training.py:397-399: forward-only, no update
                print(" IMPORTANT! skipping backprop for this iteration!",
                      flush=True)
                if custom_step:
                    # a custom (e.g. pipelined) step has no forward-only
                    # program; skip means "consume data, update nothing"
                    metrics = {"lm loss": jnp.float32(float("nan")),
                               "skipped_iter": 1}
                else:
                    # the forward-only program's first compile is
                    # expected — it must not count as a recompile
                    recompile.pause()
                    if skip_step is None:
                        # eval_step is the same forward-only program; reuse
                        # its compilation when available
                        skip_step = eval_step or build_train_step(
                            model, optimizer, parallel_cfg, num_micro,
                            loss_func, forward_only=True)
                    # fresh metrics: grad_norm/loss_scale/aux losses from the
                    # previous step must not masquerade as this iteration's
                    metrics = {"lm loss": skip_step(params, batch, step_key),
                               "skipped_iter": 1}
                    recompile.resume()
            else:
                timers("train-step", log_level=1).start()
                if steps_called == 1:
                    tracing.startup_ready()
                t_step0 = time.perf_counter()
                with tracing.span("step", "step", iteration=iteration + 1):
                    params, opt_state, metrics = train_step(
                        params, opt_state, batch, step_key, lr, wd
                    )
                    if steps_called == 0:
                        jax.block_until_ready(metrics["lm loss"])
                        tracing.startup_completed(
                            "first_step", t_step0, time.perf_counter())
                steps_called += 1
                timers("train-step").stop()
                # a compile that ran inside the dispatch span is not
                # productive step time — reattribute it to 'compile'
                _, csecs = recompile.drain()
                if csecs > 0.0 and trace is not None:
                    trace.tracer.goodput.move("step", "compile", csecs)
            if watchdog is not None:
                watchdog.resume()   # (re)arms; first arm is post-compile
            iteration += 1
            if iteration == start_iteration + 1:
                # the train-step program exists now; any later backend
                # compile is a recompile (shape/layout leak in the loop)
                recompile.mark_steady()
            if profiler is not None:
                # sync so the traced window contains the device work of
                # its last step, not just that step's dispatch
                profiler.maybe_stop(
                    iteration,
                    sync=lambda: jax.block_until_ready(metrics["lm loss"]))
            tokens = batch["tokens"].size
            counters["tokens"] += tokens
            # one sample == one sequence: every leading axis but seq
            # (reference tracks consumed_train_samples, training.py:700;
            # this feeds the checkpoint's consumed_samples field)
            counters["samples"] += tokens // batch["tokens"].shape[-1]
            if stream is not None:
                # host-side fields only — the per-iteration flight-recorder
                # entry must never force a device sync
                stream.record_dispatch({
                    "iteration": iteration,
                    "lr": float(lr),
                    "tokens": int(tokens),
                })

            at_log_boundary = bool(log_interval
                                   and iteration % log_interval == 0)
            if (resilience is not None
                    and resilience.check_due(iteration, at_log_boundary)):
                loss_val = float(metrics["lm loss"])    # device sync
                if watchdog is not None:
                    watchdog.progress()
                gn = metrics.get("grad_norm")
                bad = resilience.record_metrics(
                    iteration, loss_val,
                    None if gn is None else float(gn))
                if bad and "layer_stats" in metrics:
                    # NaN localization: hand the sentinel this step's
                    # per-group stats so the rewind names the offenders
                    resilience.observe_layer_stats(
                        iteration,
                        _layer_stats_record(metrics["layer_stats"]),
                        announce=True)
                if bad and resilience.should_rewind():
                    if watchdog is not None:
                        watchdog.pause()
                    params, opt_state, iteration = resilience.rewind(
                        params, opt_state, scheduler, batch_iterator)
                    if watchdog is not None:
                        watchdog.resume()
                    last_time = time.perf_counter()
                    non_train[0] = 0.0
                    continue

            if at_log_boundary:
                ls_host = None
                if "layer_stats" in metrics:
                    # pop before the float() conversion below — the [G]
                    # arrays are fetched once here (a few KB, no extra
                    # device work) and fan out to params norm, resilience,
                    # TensorBoard and the JSONL record
                    metrics = dict(metrics)
                    ls_host = _layer_stats_record(metrics.pop("layer_stats"))
                    if resilience is not None:
                        resilience.observe_layer_stats(iteration, ls_host)
                at_stats_boundary = bool(
                    ls_host is not None and log_layer_stats_interval
                    and iteration % log_layer_stats_interval == 0)
                if log_params_norm:     # reference --log_params_norm
                    metrics = dict(metrics)
                    if ls_host is not None:
                        # the per-group norms partition the sum of squares
                        # — derive the global norm on host instead of
                        # re-reducing the whole tree on device
                        metrics["params norm"] = health.derived_params_norm(
                            ls_host)
                    else:
                        # first use compiles the cached standalone
                        # reduction — expected, not a recompile
                        recompile.pause()
                        metrics["params norm"] = _params_norm_jit(params)
                        recompile.resume()
                timers("train-step-sync", log_level=1).start()
                with tracing.span("step_sync", "step", iteration=iteration):
                    jax.block_until_ready(metrics["lm loss"])
                timers("train-step-sync").stop()
                now = time.perf_counter()
                # elapsed (-> tokens/sec, MFU) is train-only: eval and
                # checkpoint-save wall inside the interval is subtracted,
                # so a save-heavy interval no longer deflates MFU;
                # interval_time keeps the raw wall for goodput honesty
                interval_time = (now - last_time) / log_interval
                elapsed = max(now - last_time - non_train[0], 1e-9) \
                    / log_interval
                non_train[0] = 0.0
                last_time = now
                # --tensorboard_log_interval is an absolute iteration
                # interval (reference semantics); metrics only exist at log
                # boundaries, so the effective cadence is their intersection
                use_writer = (writer if writer is not None
                              and iteration % max(tensorboard_log_interval, 1)
                              == 0 else None)
                if use_writer is not None:
                    # reference --log_*_to_tensorboard extras
                    # (training.py:509-589)
                    if log_batch_size:
                        use_writer.add_scalar("batch-size",
                                              train_cfg.global_batch_size,
                                              iteration)
                    if log_world_size:
                        use_writer.add_scalar("world-size",
                                              jax.device_count(), iteration)
                    if log_memory:
                        stats = device_memory_stats()
                        use_writer.add_scalar(
                            "mem-bytes-in-use",
                            stats.get("bytes_in_use", 0), iteration)
                        # reference training.py:580-589 also reports the
                        # high-water mark and allocation count (backends
                        # that don't track them just omit the scalars)
                        if "peak_bytes_in_use" in stats:
                            use_writer.add_scalar(
                                "mem-peak-bytes-in-use",
                                stats["peak_bytes_in_use"], iteration)
                        if "num_allocs" in stats:
                            use_writer.add_scalar(
                                "mem-num-allocs",
                                stats["num_allocs"], iteration)
                    if at_stats_boundary:
                        # grouped scalars: layer_stats/<stat>/<group>
                        ur = ls_host.get("update_ratio")
                        for i, g in enumerate(ls_host["groups"]):
                            for key in ("grad_norm", "param_norm",
                                        "update_norm"):
                                if key in ls_host:
                                    use_writer.add_scalar(
                                        f"layer_stats/{key}/{g}",
                                        health.record_value(ls_host[key][i]),
                                        iteration)
                            if ur is not None and ur[i] is not None:
                                use_writer.add_scalar(
                                    f"layer_stats/update_ratio/{g}",
                                    ur[i], iteration)
                log_metrics = {k: float(v) for k, v in metrics.items()}
                if resilience is not None:
                    from megatron_llm_tpu.resilience import recovery_counters
                    log_metrics.update(recovery_counters())
                throughput = (telemetry.throughput.compute(tokens, elapsed)
                              if telemetry.throughput is not None else None)
                training_log(
                    iteration, train_cfg.train_iters,
                    log_metrics,
                    elapsed, tokens, lr,
                    writer=use_writer,
                    throughput=throughput,
                    interval_time=interval_time,
                )
                # one snapshot feeds writer + console; the old
                # write()-then-log() pair double-read (and could
                # double-reset) every timer.  The gathered per-host
                # snapshot doubles as the straggler detector's input and
                # the per-slice attribution source — the allgather
                # already happened at this boundary.
                # --log_timers_to_tensorboard gates the writer sink only;
                # the console line and the straggler-detector snapshot
                # are always produced
                gathered = timers.report(
                    use_writer if log_timers else None, iteration,
                    normalizer=log_interval)
                if straggler is not None and gathered:
                    straggler.check(gathered, iteration)
                if stream is not None:
                    from megatron_llm_tpu.resilience import recovery_counters
                    rec = {
                        "iteration": iteration,
                        "train_iters": train_cfg.train_iters,
                        "lm_loss": log_metrics.get("lm loss"),
                        "grad_norm": log_metrics.get("grad_norm"),
                        "loss_scale": log_metrics.get("loss_scale"),
                        "skipped_iter": int(log_metrics.get("skipped_iter",
                                                            0)),
                        "learning_rate": float(lr),
                        "step_time_secs": elapsed,
                        "interval_time_secs": interval_time,
                        "tokens_per_iter": int(tokens),
                        **(throughput or {}),
                        "memory": device_memory_stats(),
                        "recovery": recovery_counters(),
                    }
                    if trace is not None:
                        g = trace.goodput_summary()
                        rec["goodput_pct"] = g["goodput_pct"]
                        rec["goodput"] = {
                            k: round(v, 4) if isinstance(v, (int, float))
                            else v
                            for k, v in g.items()}
                        rec["straggler_events"] = int(
                            counters.get("straggler_events", 0))
                    rec["recompiles"] = int(counters.get("recompiles", 0))
                    if slice_map is not None and gathered:
                        from megatron_llm_tpu import multislice
                        per_host = gathered.get("train-step")
                        if per_host is None:
                            # elementwise max over whatever sections exist
                            per_host = [max(col) for col
                                        in zip(*gathered.values())]
                        st = multislice.slice_times(per_host, slice_map)
                        rec["slice_times"] = {str(k): round(v, 6)
                                              for k, v in sorted(st.items())}
                        ws = multislice.worst_slice(st)
                        if ws is not None:
                            rec["worst_slice"] = ws
                            if trace is not None:
                                # slice dimension of goodput: the fleet
                                # waited lag_secs/iter on this slice over
                                # the whole interval
                                trace.tracer.goodput.add_slice_stall(
                                    ws["slice"],
                                    ws["lag_secs"] * log_interval)
                    if at_stats_boundary:
                        rec["layer_stats"] = ls_host
                    stream.emit(rec)
                if use_writer is not None and hasattr(use_writer, "flush"):
                    use_writer.flush()
                if on_metrics is not None:
                    on_metrics(iteration, metrics)

            if eval_step is not None and eval_interval and iteration % eval_interval == 0:
                if watchdog is not None:
                    watchdog.pause()    # eval has its own duration budget
                # eval's forward-only program compiles on first use —
                # an expected compile, not a recompile
                recompile.pause()
                t_eval0 = time.perf_counter()
                with tracing.span("eval", "eval", iteration=iteration):
                    timers("eval-time", log_level=0).start()
                    losses = []
                    for _ in range(eval_iters):
                        eval_batch = next(eval_iterator)
                        losses.append(
                            float(eval_step(params, eval_batch, None)))
                    timers("eval-time").stop()
                non_train[0] += time.perf_counter() - t_eval0
                recompile.resume()
                if watchdog is not None:
                    watchdog.resume()
                val = sum(losses) / len(losses)
                print(f" validation loss at iteration {iteration}: {val:.6E}")
                if writer is not None:
                    writer.add_scalar("validation loss", val, iteration)
                    if log_validation_ppl:   # reference --log_validation_ppl...
                        import math
                        writer.add_scalar("validation ppl", math.exp(min(val, 20.0)),
                                          iteration)
                    if hasattr(writer, "flush"):
                        writer.flush()

            saved = False
            if save_interval and save_dir and iteration % save_interval == 0:
                _save(iteration)
                saved = True

            # deterministic consensus boundaries only: every host reaches
            # the same (log / save / final) iterations, so the multi-host
            # allgather inside signals_received always pairs up.  Off these
            # boundaries the poll is local-only and free (the reference
            # all-gathers every iteration, dist_signal_handler.py:73-81).
            at_boundary = (saved or at_log_boundary
                           or iteration >= train_cfg.train_iters)
            if exit_signal_handler is not None and _signals(at_boundary):
                print("exiting on termination signal: saving checkpoint")
                if save_dir:
                    if not saved:
                        _save(iteration)
                    counters["signal_saves"] += 1
                # preemption-aware rescue: the consensus above means every
                # host (every slice) saw the SIGTERM and reaches this save
                # + exit together; a non-zero code (17, shared with the
                # hang watchdog) tells the fleet supervisor to restart —
                # possibly at a different dp x slice shape (elastic resume)
                code = int(preempt_exit_code or 0)
                if code and stream is not None:
                    stream.emit({"kind": "preempt_rescue",
                                 "iteration": iteration,
                                 "exit_code": code,
                                 "saved": bool(save_dir)})
                sys.exit(code)

            # exit based on duration (reference training.py:746-758)
            if exit_duration_in_mins:
                train_mins = (time.perf_counter() - train_start) / 60.0
                if train_mins > exit_duration_in_mins:
                    if save_dir and not saved:
                        _save(iteration)
                    print(f" exiting program after {train_mins:.1f} minutes",
                          flush=True)
                    sys.exit(0)

            # exit based on iterations (reference training.py:761-767)
            if exit_interval and iteration % exit_interval == 0:
                if save_dir and not saved:
                    _save(iteration)
                print(f" exiting program at iteration {iteration}", flush=True)
                sys.exit(0)

    finally:
        # every exit path — normal completion, sys.exit (raises
        # SystemExit), or an exception — flushes in-flight async
        # saves so a durable checkpoint always gets its tracker
        root_span.__exit__(None, None, None)
        if steps_called == 1:
            tracing.startup_ready()     # a run of one step is ready too
        if trace is None:
            tracing.install_detector(None)
        if watchdog is not None:
            watchdog.stop()
        if profiler is not None:
            # a window truncated by exit/exception still yields a usable
            # xplane (close() is a no-op when no trace is active)
            profiler.close()
        checkpointing.finalize_async_saves()
    return params, opt_state, iteration
