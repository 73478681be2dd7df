"""The two forms of a gated MLP's fused first projection.

FLAT, the public form: ``dense_h_to_4h.kernel [.., h, 2F]`` stored
``[gate | up]`` (bias ``[.., 2F]``).  ``model.init``, checkpoints,
``weights_conversion/*``, the serving engine and the benchmark's references
read and write it, and ``models/transformer.py::mlp`` splits its product in
half.  Sharded over tp, a contiguous shard of the ``2F`` columns is a run of
gate columns OR of up columns, so ``act(gate) * up`` has to fetch its
partner's columns from another shard: permutes under the ``split`` forward
and again in the rematerialised forward, all-to-alls under its transpose
(a ``concatenate``) backward, on every layer call.

PAIRED, the trainer's resident form: ``kernel [.., 2, h, F]`` (bias
``[.., 2, F]``), ``[.., 0, :, :]`` the gate and ``[.., 1, :, :]`` the up,
with ``'ffn'`` on ``F``: a shard holds both halves of its own columns.  It
is to the flat form what Megatron's tp-rank checkpoints (``[gate; up]``
chunks a rank, ``weights_conversion/megatron_ckpt.py::_split_tp``) are to
its merged ones: the same numbers, moved.  The pair sits BEFORE ``h`` so
that the two minor dimensions are a matmul operand's own: ``[.., h, 2, F]``
is born in a layout of two-row tiles that the TPU's compiler copies into
``[.., 2, h, F]`` order and back, the whole leaf, every step (PERF.md
section 6, PR 50).

The trainer converts at its two doors and nowhere else: in, after the init
or the checkpoint load (``finetune.py``, ``for_trainer``); out, in
``checkpointing.save_checkpoint`` (``flat``).  What is paired is visible in
a leaf's rank (one more than the second projection's), so nothing carries a
flag: ``layers.column_parallel_linear``, ``mlp`` and
``language_model._linear_spec`` follow the rank.  A projection that carries
LoRA leaves or int8 scales stays flat, and so does a non-gated MLP's, which
has no pair.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_llm_tpu.parallel import sharding as sh

FIRST, SECOND = "dense_h_to_4h", "dense_4h_to_h"


def _kernels(name, node):
    """(first weight, second weight, whether the first may change form) of
    a dense MLP's node (``layers['mlp']``; the experts' shared MLP keeps
    its flat form: ``models/moe.py``); None for any other node."""
    if not (name == "mlp" and isinstance(node, dict) and FIRST in node
            and SECOND in node):
        return None
    first, second = node[FIRST], node[SECOND]
    if not (isinstance(first, dict) and isinstance(second, dict)):
        return None                     # an adapter tree's placeholders
    k1 = first.get("kernel", first.get("kernel_q"))
    k2 = second.get("kernel", second.get("kernel_q"))
    if k1 is None or k2 is None:
        return None
    # int8 scales (``kernel_q``) or LoRA leaves: the projection stays flat
    return k1, k2, "kernel" in first and "lora_A" not in first


def _walk(tree, on_mlp: Callable, name=None):
    if isinstance(tree, dict):
        found = _kernels(name, tree)
        if found is not None:
            return on_mlp(tree, *found)
        return {k: _walk(v, on_mlp, k) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_walk(v, on_mlp, name) for v in tree)
    return tree


# leaf -> (the leaf in the other form, the logical or mesh spec it lies on
# there, from the spec it lay on): ``[.., h, 2F]`` <-> ``[.., 2, h, F]`` and
# ``[.., 2F]`` <-> ``[.., 2, F]``, in methods numpy has too (a rescue save
# flattens a host snapshot and must not touch the device)
_PAIR = {
    "kernel": (lambda a: a.reshape(a.shape[:-1] + (2, -1)).swapaxes(-2, -3),
               lambda s: s[:-2] + (None,) + s[-2:]),
    "bias": (lambda a: a.reshape(a.shape[:-1] + (2, -1)),
             lambda s: s[:-1] + (None,) + s[-1:]),
}
_FLAT = {
    "kernel": (lambda a: a.swapaxes(-2, -3).reshape(
        a.shape[:-3] + (a.shape[-2], -1)), lambda s: s[:-3] + s[-2:]),
    "bias": (lambda a: a.reshape(a.shape[:-2] + (-1,)),
             lambda s: s[:-2] + s[-1:]),
}


def _reformed(node, forms):
    """The node with its first projection's kernel and bias in the other
    form.  A leaf that lies on a mesh lands on the shards of its new form's
    own spec: left to the partitioner, a reshape that splits or merges the
    sharded axis leaves its result whole on every device."""
    def other(name, a):
        if name not in forms:
            return a
        form, respec = forms[name]
        placed = getattr(a, "sharding", None)
        if isinstance(a, jax.core.Tracer) or not isinstance(
                placed, NamedSharding):
            return form(a)
        spec = tuple(placed.spec) + (None,) * (a.ndim - len(placed.spec))
        return jax.jit(form, out_shardings=NamedSharding(
            placed.mesh, P(*respec(spec))))(a)

    return {**node,
            FIRST: {name: other(name, a) for name, a in node[FIRST].items()}}


def pair(tree):
    """Every flat gated first projection of a params-shaped tree (or an
    optimizer's tree of moments) in the paired form; the rest as it is."""
    def on_mlp(node, k1, k2, free):
        if (not free or k1.ndim != k2.ndim
                or k1.shape[-1] != 2 * k2.shape[-2]):
            return node                 # held flat, paired, or not gated
        return _reformed(node, _PAIR)
    return _walk(tree, on_mlp)


def flat(tree):
    """``pair``'s inverse: the public form, ``[gate | up]``."""
    def on_mlp(node, k1, k2, free):
        return _reformed(node, _FLAT) if k1.ndim == k2.ndim + 1 else node
    return _walk(tree, on_mlp)


def count(tree) -> Tuple[int, int]:
    """(first projections held paired, gated ones left flat) of a tree."""
    n = [0, 0]

    def on_mlp(node, k1, k2, free):
        if k1.ndim == k2.ndim + 1:
            n[0] += 1
        elif k1.shape[-1] == 2 * k2.shape[-2]:
            n[1] += 1
        return node

    _walk(tree, on_mlp)
    return n[0], n[1]


def for_trainer(tree):
    """The tree as a train step should hold it: paired wherever the
    ``'ffn'`` axis is sharded over more than one device, else as it is."""
    return pair(tree) if sh.axis_size("ffn") > 1 else tree
