"""Logical-axis sharding rules and constraint helpers (GSPMD path).

The reference encodes parallel placement *imperatively*: each TP layer calls
the right collective by hand (``megatron/core/tensor_parallel/layers.py``).
The TPU-native equivalent is *declarative*: params and activations carry
logical axis names, a rules table maps logical axes to mesh axes, and
``with_sharding_constraint`` pins the placement; XLA/GSPMD inserts the
collectives (the same allreduce/allgather/reduce-scatter pattern — see the
module docstring of ``parallel/mappings.py`` for the explicit versions).

Logical axes used across the framework:

| logical    | meaning                           | mesh axis |
|------------|-----------------------------------|-----------|
| 'batch'    | microbatch dim of activations     | dp        |
| 'seq'      | sequence dim (activations)        | None (tp when sequence-parallel region) |
| 'hidden'   | model hidden dim                  | None      |
| 'vocab'    | vocabulary dim (embedding, head)  | tp        |
| 'ffn'      | MLP intermediate dim              | tp        |
| 'heads'    | attention-head dim (q/k/v/o)      | tp        |
| 'kv_heads' | KV-head dim under GQA             | tp        |
| 'stage'    | stacked pipeline-stage dim        | pp        |
| 'expert'   | MoE expert dim                    | dp (EP folded into dp) |
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_llm_tpu import topology

DEFAULT_RULES = {
    # 'batch' under the default rules is resolved dynamically by
    # _batch_axes() — ('slice', 'dp') in a multi-slice mesh, 'dp'
    # otherwise; this entry is the custom-rules fallback.
    "batch": topology.DP_AXIS,
    # 'seq' rides the cp axis: a no-op at cp=1, contiguous context-parallel
    # sequence sharding when cp>1 (ring attention handles the cross-chunk
    # attention; everything else is position-wise)
    "seq": topology.CP_AXIS,
    # sequence-parallel (Megatron SP) regions; composes with cp
    "seq_tp": (topology.CP_AXIS, topology.TP_AXIS),
    "seq_cp": topology.CP_AXIS,
    "hidden": None,
    "vocab": topology.TP_AXIS,
    "ffn": topology.TP_AXIS,
    "heads": topology.TP_AXIS,
    "kv_heads": topology.TP_AXIS,
    "stage": topology.PP_AXIS,
    "expert": topology.DP_AXIS,
    "dp_shard": topology.DP_AXIS,  # ZeRO-1 optimizer-state sharding
    None: None,
}


def _batch_axes():
    """Mesh axes for the logical 'batch' dim, resolved at trace time:
    ('slice', 'dp') in a multi-slice mesh, plain 'dp' otherwise."""
    axes = topology.data_axes()
    return axes if len(axes) > 1 else axes[0]


def logical_to_mesh(
    logical_spec: Sequence[Optional[str]], rules=None
) -> P:
    rules = rules or DEFAULT_RULES
    def resolve(a):
        if a == "batch" and rules is DEFAULT_RULES:
            return _batch_axes()
        return rules.get(a)
    return P(*(resolve(a) for a in logical_spec))


def _mesh() -> Optional[Mesh]:
    return topology._MESH


def axis_size(logical: str, rules=None) -> int:
    """Over how many devices of the live mesh a logical axis is sharded
    (1 with no mesh)."""
    mesh = _mesh()
    if mesh is None:
        return 1
    entry = logical_to_mesh((logical,), rules)[0]
    axes = entry if isinstance(entry, tuple) else (entry,)
    return math.prod(mesh.shape[a] for a in axes if a is not None)


def _auto_axes_of(spec: P) -> P:
    """``spec`` without the mesh axes that are Manual where this is traced:
    inside a manual region (the train step's data-parallel ranks, a
    pipeline stage) a dimension is already this device's own part along
    those axes, and a constraint may name only what GSPMD still places."""
    _, manual = topology.current_mesh_and_manual()
    if not manual:
        return spec

    def auto(entry):
        if isinstance(entry, tuple):
            return tuple(a for a in entry if a not in manual) or None
        return None if entry in manual else entry

    return P(*(auto(e) for e in spec))


def constrain(x: jax.Array, *logical_axes: Optional[str], rules=None) -> jax.Array:
    """``with_sharding_constraint`` by logical axis names; no-op when no mesh
    is initialized (pure single-device runs and numpy-golden tests)."""
    mesh = _mesh()
    if mesh is None or all(a is None for a in logical_axes):
        return x
    spec = _auto_axes_of(logical_to_mesh(logical_axes, rules))
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def with_logical_constraint(tree, specs, rules=None):
    """Tree-map constrain: ``specs`` is a pytree of logical-axis tuples
    matching ``tree``."""
    mesh = _mesh()
    if mesh is None:
        return tree
    def one(x, s):
        spec = _auto_axes_of(logical_to_mesh(s, rules))
        if all(a is None for a in spec):
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(
        one, tree, specs, is_leaf=lambda v: v is None)


def make_shardings(specs, rules=None, mesh: Optional[Mesh] = None):
    """Pytree of logical-axis tuples -> pytree of NamedShardings.

    ``None`` spec entries pass through as ``None`` — partial trees
    (e.g. a LoRA adapter tree whose non-target positions are structural
    placeholders) shard only where a spec exists."""
    mesh = mesh or topology.get_mesh()
    return jax.tree_util.tree_map(
        lambda s: (None if s is None
                   else NamedSharding(mesh, logical_to_mesh(s, rules))),
        specs,
        is_leaf=lambda v: isinstance(v, tuple) or v is None,
    )


def init_params(model, key, form=None):
    """``model.init(key)`` with every leaf born on its own shards.

    An eager init materializes the whole model on the default device
    (layer by layer, then once more for the stack) before
    ``shard_params`` spreads it; under ``jit`` with the output shardings
    of ``model.param_specs`` no device ever holds more than its share.
    ``form`` (a tree -> tree function, the trainer's
    ``glu_pairs.for_trainer``) is applied inside the same program, and
    the leaves are born on the shards of the form it gives."""
    init = model.init
    if form is not None:
        def init(k):    # (the compile ledger's name for it, as model.init's)
            return form(model.init(k))
    abstract = jax.eval_shape(init, key)
    shardings = make_shardings(model.param_specs(abstract))
    return jax.jit(init, out_shardings=shardings)(key)


def shard_params(params, specs, rules=None, mesh: Optional[Mesh] = None):
    """device_put a host-side param pytree onto the mesh per its specs.

    ``None`` placeholders (both sides) pass through untouched, so
    partial trees (LoRA adapters) shard without a fully-populated spec
    tree."""
    shardings = make_shardings(specs, rules, mesh)
    return jax.tree_util.tree_map(
        lambda x, s: x if s is None else jax.device_put(x, s),
        params, shardings,
        is_leaf=lambda v: v is None,
    )
