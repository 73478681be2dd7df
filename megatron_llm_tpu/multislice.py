"""Multi-slice elastic training runtime (MegaScale tier).

The single-job mesh (``topology.py``) scales tp/pp/cp/dp inside one pod
slice over ICI.  This module is the runtime layer above it, per MegaScale
(arXiv 2402.15627, PAPERS.md): data parallelism *across* pod slices over
DCN, restart at a different ``dp x slice`` product from the same
checkpoint, preemption-aware rescue of the whole fleet, and per-slice
attribution so a slow slice is named the way a NaN layer is
(``health.py`` precedent).

Four pieces:

1. **Hierarchical gradient all-reduce** — ICI first, DCN second.  The
   train step (``training.build_train_step``) runs its microbatch scan
   manual over the data axes ``('slice', 'dp')``: every replica
   accumulates the fp32 gradient of its own rows, and the one reduction
   after the scan is ``hierarchical_psum``: a psum over the in-slice
   ``dp`` axis (ICI), then a second psum over ``slice`` (DCN) — two
   staged collectives a step where a flat psum over both axes would fold
   the hops into one.  The CPU integration tests check the staged
   reduction is checksum-identical to a flat all-reduce.

2. **Elastic resume** — ``run_shape.json`` written next to checkpoints
   records the shape that produced them; on load the resume path detects
   a ``dp x slice`` change, logs it into the JSONL stream
   (``kind: 'elastic_resume'``), and the consumed-samples counter from
   the checkpoint meta drives the data sampler's deterministic skip, so
   the new fleet shape continues the same sample order.  The cross-mesh
   restore itself is ``checkpointing.py``'s resharding-on-load.

3. **Preemption rescue** — a SIGTERM on any one slice reaches the whole
   fleet through ``DistributedSignalHandler``'s boundary consensus; the
   train loop then makes a rescue save and the entire fleet exits with
   ``PREEMPT_EXIT_CODE`` (17, shared with the hang watchdog) so the
   scheduler restarts it — possibly at a different shape (see 2).

4. **Per-slice attribution** — ``host_slice_map`` + ``slice_times`` turn
   the cross-host timer gathers (``timers.report``) into per-slice step
   times; ``tracing.StragglerDetector`` names the slice on every event
   and the JSONL stream carries ``slice_times`` / ``worst_slice`` fields
   (telemetry schema 4), aggregated offline by
   ``tools/telemetry_report.py`` / ``tools/trace_report.py``.

Env contract (docs/guide/multislice.md): processes are launched with
contiguous rank blocks per slice (ranks [0, P/S) are slice 0, ...);
``MEGASCALE_SLICE_ID``, when set by the launcher, is validated against
the derived id at mesh build.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu import topology

SLICE_AXIS = topology.SLICE_AXIS

# Whole-fleet exit code after a consensus preemption rescue — shared with
# resilience.HangWatchdog.EXIT_CODE so "restart me" means one thing to
# the supervisor regardless of which subsystem asked for it.
from megatron_llm_tpu.resilience import PREEMPT_EXIT_CODE  # noqa: E402

RUN_SHAPE_FILENAME = "run_shape.json"


# ---------------------------------------------------------------------------
# Hierarchical (ICI-then-DCN) reduction
# ---------------------------------------------------------------------------

def hierarchical_psum(x, ici_axes: Sequence[str], dcn_axis: str = SLICE_AXIS):
    """Two-stage all-reduce for use INSIDE a manual (shard_map) region:
    psum over the in-slice ICI axes first, then a second psum over the
    DCN ``slice`` axis.  Mathematically identical to one flat psum over
    all the axes (addition is associative); structurally it keeps the
    cross-DCN collective a separate, later hop."""
    if ici_axes:
        x = jax.lax.psum(x, tuple(ici_axes))
    return jax.lax.psum(x, dcn_axis)


def hierarchical_allreduce(x: jax.Array, mesh=None) -> jax.Array:
    """Sum per-replica values with the staged ICI-then-DCN reduction.

    ``x`` has leading dim ``slice * dp`` spanning ``('slice', 'dp')`` —
    one partial value per data-parallel replica (a gradient shard, a
    checksum).  Returns the total, replicated.  The flat counterpart for
    parity checks is ``flat_allreduce``."""
    mesh = mesh or topology.get_mesh()
    ici = tuple(a for a in (topology.DP_AXIS,) if mesh.shape[a] >= 1)
    fn = jax.shard_map(
        lambda xs: hierarchical_psum(xs.sum(axis=0), ici),
        mesh=mesh,
        in_specs=P((SLICE_AXIS, topology.DP_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)(x)


def flat_allreduce(x: jax.Array, mesh=None) -> jax.Array:
    """Single flat psum over ``('slice', 'dp')`` — the reduction the
    hierarchical path must be checksum-identical to."""
    mesh = mesh or topology.get_mesh()
    fn = jax.shard_map(
        lambda xs: jax.lax.psum(xs.sum(axis=0),
                                (SLICE_AXIS, topology.DP_AXIS)),
        mesh=mesh,
        in_specs=P((SLICE_AXIS, topology.DP_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)(x)


# ---------------------------------------------------------------------------
# Elastic resume: run-shape persistence + consumed-samples reconciliation
# ---------------------------------------------------------------------------

def run_shape_from_mesh() -> Dict[str, Any]:
    """The live mesh's fleet shape (the source of truth at save time);
    empty when no mesh is initialized (unit tests saving ad hoc)."""
    m = topology._MESH
    if m is None:
        return {}
    return {
        "world_size": int(m.size),
        "processes": int(jax.process_count()),
        "num_slices": int(m.shape[SLICE_AXIS]),
        "data_parallel_size": int(m.shape[topology.DP_AXIS]),
        "tensor_model_parallel_size": int(m.shape[topology.TP_AXIS]),
        "pipeline_model_parallel_size": int(m.shape[topology.PP_AXIS]),
        "context_parallel_size": int(m.shape[topology.CP_AXIS]),
    }


def run_shape_from_args(args) -> Dict[str, Any]:
    return {
        "world_size": int(getattr(args, "world_size", 0) or 0),
        "processes": int(jax.process_count()),
        "num_slices": int(getattr(args, "num_slices", 1) or 1),
        "data_parallel_size": int(args.data_parallel_size),
        "tensor_model_parallel_size": int(args.tensor_model_parallel_size),
        "pipeline_model_parallel_size": int(
            args.pipeline_model_parallel_size),
        "context_parallel_size": int(args.context_parallel_size),
        "global_batch_size": int(args.global_batch_size),
        "micro_batch_size": int(args.micro_batch_size),
    }


def write_run_shape(save_dir: str, shape: Dict[str, Any]) -> Optional[str]:
    """Record the fleet shape next to the checkpoints (process 0; best
    effort — a shape file must never fail a save)."""
    if not save_dir or jax.process_index() != 0:
        return None
    try:
        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir, RUN_SHAPE_FILENAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(shape, f, indent=1)
        os.replace(tmp, path)
        return path
    except OSError:
        return None


def read_run_shape(load_dir: str) -> Optional[Dict[str, Any]]:
    if not load_dir:
        return None
    try:
        with open(os.path.join(load_dir, RUN_SHAPE_FILENAME)) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def detect_elastic_resume(load_dir: str, args) -> Optional[Dict[str, Any]]:
    """Compare the checkpoint's recorded run shape against the current
    one.  Returns an ``elastic_resume`` event dict when the ``dp x
    slice`` product (or any parallel size) changed, else None.  No
    recorded shape (pre-multislice checkpoints) is not a change."""
    old = read_run_shape(load_dir)
    if old is None:
        return None
    new = run_shape_from_args(args)
    keys = ("num_slices", "data_parallel_size",
            "tensor_model_parallel_size", "pipeline_model_parallel_size",
            "context_parallel_size", "world_size")
    changed = {k: (old.get(k), new[k]) for k in keys
               if old.get(k) is not None and old.get(k) != new[k]}
    if not changed:
        return None
    return {
        "kind": "elastic_resume",
        "changed": {k: {"from": o, "to": n} for k, (o, n) in changed.items()},
        "old_shape": old,
        "new_shape": new,
    }


def announce_elastic_resume(load_dir: str, args, iteration: int,
                            consumed_samples: int,
                            stream=None) -> Optional[Dict[str, Any]]:
    """Detect + log a shape change on resume.  Prints on process 0 and
    emits the event into the structured JSONL stream when one is
    installed.  Returns the event (or None)."""
    ev = detect_elastic_resume(load_dir, args)
    if ev is None:
        return None
    ev = {**ev, "iteration": int(iteration),
          "consumed_samples": int(consumed_samples)}
    if jax.process_index() == 0:
        deltas = ", ".join(
            f"{k} {v['from']} -> {v['to']}" for k, v in ev["changed"].items())
        print(f" > ELASTIC RESUME at iteration {iteration}: {deltas}; "
              f"data order reconciled by skipping "
              f"{consumed_samples} consumed samples", flush=True)
    if stream is None:
        try:
            from megatron_llm_tpu import telemetry
            stream = telemetry.get_stream()
        except Exception:
            stream = None
    if stream is not None:
        rec = dict(ev)
        rec_kind = rec.pop("kind")
        stream.emit({**rec, "kind": rec_kind})
    return ev


# ---------------------------------------------------------------------------
# Per-slice attribution
# ---------------------------------------------------------------------------

def host_slice_map(process_count: Optional[int] = None,
                   num_slices: Optional[int] = None) -> List[int]:
    """Process index -> slice id, under the contiguous-rank-block launch
    contract (slice outermost in the device order).  Degenerates to all
    zeros when one process hosts every slice (virtual-device runs)."""
    procs = process_count if process_count is not None else jax.process_count()
    sl = num_slices if num_slices is not None else topology.num_slices_or_default()
    if sl <= 1 or procs < sl:
        return [0] * procs
    return [p * sl // procs for p in range(procs)]


def slice_times(per_host_secs: Sequence[float],
                host_map: Sequence[int]) -> Dict[int, float]:
    """Per-host section times -> per-slice times.  A slice is as slow as
    its slowest host (everyone inside the slice waits on the ICI
    collective; the fleet waits on the DCN one)."""
    out: Dict[int, float] = {}
    for host, secs in enumerate(per_host_secs):
        s = host_map[host] if host < len(host_map) else 0
        out[s] = max(out.get(s, 0.0), float(secs))
    return out


def worst_slice(times: Dict[int, float]) -> Optional[Dict[str, float]]:
    """The slice the fleet is waiting on, with its lag over the median
    of the others.  None when there is nothing to compare."""
    if len(times) < 2:
        return None
    from statistics import median
    worst = max(times, key=lambda s: times[s])
    others = [v for s, v in times.items() if s != worst]
    med = median(others)
    return {
        "slice": int(worst),
        "secs": float(times[worst]),
        "median_other_secs": float(med),
        "lag_secs": float(times[worst] - med),
        "ratio": float(times[worst] / med) if med > 0 else float("inf"),
    }
