"""Device-mesh topology — the TPU-native replacement for
``megatron/core/parallel_state.py``.

The reference builds ~7 families of NCCL process groups from the
(tp, pp, vpp) sizes with rank arithmetic (``parallel_state.py:51-205``) and
exposes ~40 getters.  On TPU the entire fabric is one
``jax.sharding.Mesh`` with axes ``('pp', 'dp', 'tp')`` — the same rank
order as the reference (pp outer, dp middle, tp inner; TP groups are
contiguous device blocks, ``parallel_state.py:146-151``) so TP collectives
ride nearest-neighbour ICI links.

"Groups" become mesh axes; "group getters" become axis-size/axis-index
queries.  Rank predicates used inside sharded code (e.g.
``is_pipeline_last_stage`` inside the 1F1B loop) use ``jax.lax.axis_index``
under ``shard_map`` instead of global rank math.

Multi-host bootstrap: ``jax.distributed.initialize`` over DCN replaces the
torchrun/NCCL rendezvous (reference: ``megatron/initialize.py:124-151``).
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical mesh-axis names.  The reference has no context-parallel groups
# (SURVEY §5.7: no ring attention / Ulysses); cp is this framework's
# first-class long-context axis — sequence-sharded activations with ring
# attention over ICI neighbours (parallel/ring_attention.py).
# ``slice`` is the outermost, DCN-connected axis: one entry per pod slice
# in a MegaScale-style multi-slice job (multislice.py).  It is size 1 in
# ordinary single-slice runs, so every spec/getter below is unchanged
# semantically unless --num_slices > 1.
SLICE_AXIS = "slice"
PP_AXIS = "pp"
DP_AXIS = "dp"
CP_AXIS = "cp"
TP_AXIS = "tp"
MESH_AXES = (SLICE_AXIS, PP_AXIS, DP_AXIS, CP_AXIS, TP_AXIS)

# env contract for slice identity (the MEGASCALE_SLICE_ID convention used
# by multi-slice TPU launchers); validated against the process-derived id
SLICE_ID_ENV = "MEGASCALE_SLICE_ID"

_MESH: Optional[Mesh] = None
_VIRTUAL_PIPELINE_MODEL_PARALLEL_SIZE: Optional[int] = None


def initialize_model_parallel(
    tensor_model_parallel_size: int = 1,
    pipeline_model_parallel_size: int = 1,
    virtual_pipeline_model_parallel_size: Optional[int] = None,
    context_parallel_size: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
    num_slices: int = 1,
) -> Mesh:
    """Build the global device mesh.

    Mirrors ``initialize_model_parallel`` (parallel_state.py:51-205) but
    returns a Mesh; dp size is derived as world // (slice*tp*pp*cp) exactly
    like the reference derives dp in arguments.py:76.

    ``num_slices`` partitions the fleet into that many DCN-connected pod
    slices (outermost mesh axis).  Device order from ``jax.devices()`` is
    process-major, so slices are contiguous process blocks: process p
    belongs to slice ``p * num_slices // process_count`` — the contract
    ``multislice.py`` documents and ``MEGASCALE_SLICE_ID`` is checked
    against.
    """
    global _MESH, _VIRTUAL_PIPELINE_MODEL_PARALLEL_SIZE
    if devices is None:
        devices = jax.devices()
    world = len(devices)
    tp, pp = tensor_model_parallel_size, pipeline_model_parallel_size
    cp, sl = context_parallel_size, num_slices
    if sl < 1 or world % sl != 0:
        raise RuntimeError(
            f"world size ({world}) is not divisible by num_slices ({sl})")
    if world % (sl * tp * pp * cp) != 0:
        raise RuntimeError(
            f"world size ({world}) is not divisible by num_slices ({sl}) "
            f"x tensor parallel size ({tp}) x pipeline parallel size "
            f"({pp}) x context parallel size ({cp})"
        )
    dp = world // (sl * tp * pp * cp)
    # Rank order (slice outermost — DCN boundaries between contiguous
    # device blocks; then pp, dp, cp, tp inner) — tp innermost keeps TP
    # collectives on nearest-neighbour ICI (parallel_state.py:116-171), cp
    # next so the ring permute is also neighbour-local.
    dev_array = np.asarray(devices).reshape(sl, pp, dp, cp, tp)
    _MESH = Mesh(dev_array, MESH_AXES)
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_SIZE = virtual_pipeline_model_parallel_size
    if sl > 1:
        declared = os.environ.get(SLICE_ID_ENV)
        if declared is not None:
            derived = slice_id()
            if derived is not None and int(declared) != derived:
                print(f" > WARNING: {SLICE_ID_ENV}={declared} but process "
                      f"{jax.process_index()} maps to slice {derived} by "
                      f"device order; check the launch rank ordering",
                      flush=True)
    return _MESH


def model_parallel_is_initialized() -> bool:
    return _MESH is not None


def get_mesh() -> Mesh:
    if _MESH is None:
        raise RuntimeError("model parallel mesh is not initialized")
    return _MESH


def set_mesh(mesh: Mesh) -> None:
    global _MESH
    _MESH = mesh


def destroy_model_parallel() -> None:
    # reference: parallel_state.py:497
    global _MESH, _VIRTUAL_PIPELINE_MODEL_PARALLEL_SIZE
    _MESH = None
    _VIRTUAL_PIPELINE_MODEL_PARALLEL_SIZE = None


# ---------------------------------------------------------------------------
# Size getters (reference: parallel_state.py:217-320).
# ---------------------------------------------------------------------------

def get_tensor_model_parallel_world_size() -> int:
    return get_mesh().shape[TP_AXIS]


def get_pipeline_model_parallel_world_size() -> int:
    return get_mesh().shape[PP_AXIS]


def get_data_parallel_world_size() -> int:
    return get_mesh().shape[DP_AXIS]


def get_context_parallel_world_size() -> int:
    return get_mesh().shape[CP_AXIS]


def get_virtual_pipeline_model_parallel_world_size() -> Optional[int]:
    return _VIRTUAL_PIPELINE_MODEL_PARALLEL_SIZE


def get_num_slices() -> int:
    """Size of the outer DCN ``slice`` axis (1 unless --num_slices > 1)."""
    return get_mesh().shape[SLICE_AXIS]


def num_slices_or_default(default: int = 1) -> int:
    """``get_num_slices()`` that tolerates an uninitialized mesh (pure
    single-device paths and numpy-golden tests)."""
    return _MESH.shape[SLICE_AXIS] if _MESH is not None else default


def slice_id() -> Optional[int]:
    """Which slice THIS process's devices belong to (host-side query).

    With ``slice`` outermost and jax's process-major device order, slices
    are contiguous process blocks.  Returns None when one process hosts
    more than one slice (single-process virtual-device runs) and the
    membership is therefore ambiguous — except slice 0 when there is only
    one slice.
    """
    sl = get_num_slices()
    if sl == 1:
        return 0
    procs = jax.process_count()
    if procs % sl != 0:
        return None if procs < sl else jax.process_index() * sl // procs
    return jax.process_index() // (procs // sl)


def get_world_size() -> int:
    m = get_mesh()
    return (m.shape[SLICE_AXIS] * m.shape[PP_AXIS] * m.shape[DP_AXIS]
            * m.shape[CP_AXIS] * m.shape[TP_AXIS])


# ---------------------------------------------------------------------------
# In-shard rank queries — valid *inside* shard_map over the mesh.
# (reference rank getters parallel_state.py:322-481 are process-global;
# under SPMD the analogue is the per-shard axis index.)
# ---------------------------------------------------------------------------

def get_tensor_model_parallel_rank():
    return jax.lax.axis_index(TP_AXIS)


def get_pipeline_model_parallel_rank():
    return jax.lax.axis_index(PP_AXIS)


def get_data_parallel_rank():
    return jax.lax.axis_index(DP_AXIS)


def get_slice_rank():
    return jax.lax.axis_index(SLICE_AXIS)


def is_pipeline_first_stage():
    # reference: parallel_state.py:322-341
    return jax.lax.axis_index(PP_AXIS) == 0


def is_pipeline_last_stage():
    return jax.lax.axis_index(PP_AXIS) == get_pipeline_model_parallel_world_size() - 1


# ---------------------------------------------------------------------------
# Host-side process queries (multi-host data loading).
# ---------------------------------------------------------------------------

def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bootstrap over DCN (reference: initialize.py:124-151 uses
    torchrun env vars + NCCL TCP rendezvous; here it is
    ``jax.distributed.initialize``, driven by the same env conventions)."""
    if num_processes is None:
        num_processes = int(os.environ.get("WORLD_SIZE", "1"))
    if num_processes <= 1:
        return
    if process_id is None:
        process_id = int(os.environ.get("RANK", "0"))
    if coordinator_address is None:
        addr = os.environ.get("MASTER_ADDR", "127.0.0.1")
        port = os.environ.get("MASTER_PORT", "8476")
        coordinator_address = f"{addr}:{port}"
    # Multi-process *CPU* runs (the 2-process integration tests) need the
    # gloo cross-host collectives backend selected before the CPU client
    # is created; without it every cross-process computation fails with
    # "Multiprocess computations aren't implemented on the CPU backend".
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


# ---------------------------------------------------------------------------
# Sharding constructors.
# ---------------------------------------------------------------------------

def current_mesh_and_manual():
    """(governing mesh, already-Manual axis names) for building a
    shard_map that may nest inside another manual region — the abstract
    context mesh when one is active (inside jit/manual regions jax
    requires it), else the concrete global mesh.  ``(None, set())`` when
    no mesh governs.  A nested shard_map names only the axes it makes
    manual ITSELF: an already-Manual axis named again with no spec entry
    reads as "replicated over it", and the transpose then averages the
    inputs' cotangents over ranks whose data differ."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        # not inside any mesh context: the concrete global mesh governs
        mesh = _MESH
    if mesh is None:
        return None, set()
    manual = {
        name for name, t in zip(mesh.axis_names, mesh.axis_types)
        if t == jax.sharding.AxisType.Manual
    }
    return mesh, manual


def sharded_auto_mesh_active() -> bool:
    """True when the governing mesh has a size>1 axis still under GSPMD
    auto-sharding — i.e. auto partitioning is in play and a bare Mosaic
    custom call is a lowering error.  Axes already Manual don't count:
    inside a fully-manual region the arrays are device-local and pallas
    is legal."""
    mesh, manual = current_mesh_and_manual()
    return mesh is not None and any(
        mesh.shape[a] > 1 for a in mesh.axis_names if a not in manual)


def nesting_mesh(required_axis: str):
    """Mesh + already-manual axes for a shard_map that may nest inside
    another manual region (the pipeline engines).

    Returns ``(mesh, manual_axes)``, or ``(None, None)`` when
    ``required_axis`` is absent or size 1 in the governing mesh — the
    caller should fall back to its unsharded path.  The nested shard_map
    names ``required_axis`` alone (see ``current_mesh_and_manual``); the
    manual axes are there for a caller that sizes its specs by what is
    still automatic.  NOTE: when an
    abstract mesh is active but lacks the axis we must NOT silently
    switch to the global mesh (a nested shard_map over a different mesh
    than the enclosing context fails with an opaque jax error —
    round-3 advisor finding).  Shared by ``vocab_parallel_lookup_manual``
    and ``context_parallel_attention``."""
    mesh, manual = current_mesh_and_manual()
    if (mesh is None or required_axis not in mesh.axis_names
            or mesh.shape[required_axis] == 1):
        return None, None
    return mesh, manual


def data_axes():
    """The mesh axes the global batch dimension spans: ``('slice', 'dp')``
    in a multi-slice run (data parallelism crosses the DCN axis too),
    plain ``('dp',)`` otherwise.  Usable directly as one PartitionSpec
    entry — ``P(None, data_axes(), None)``."""
    if _MESH is not None and _MESH.shape[SLICE_AXIS] > 1:
        return (SLICE_AXIS, DP_AXIS)
    return (DP_AXIS,)


def named_sharding(*spec) -> NamedSharding:
    return NamedSharding(get_mesh(), P(*spec))


def replicated_sharding() -> NamedSharding:
    return NamedSharding(get_mesh(), P())
