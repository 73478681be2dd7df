"""Ulysses-style all-to-all sequence parallelism (context-parallel
algorithm #2, alongside ``ring_attention``).

The reference has no sequence/context parallelism at all (SURVEY §5.7);
this module implements the DeepSpeed-Ulysses formulation on the TPU
``cp`` mesh axis: activations arrive sequence-sharded
``[b, s/P, heads, d]``; one ``lax.all_to_all`` re-shards **heads** and
gathers the **full sequence** per device, attention runs locally over
the whole sequence with ``heads/P`` heads (so the tuned Pallas flash
kernel applies unchanged — no online-softmax carry across devices), and
a second all-to-all restores the sequence sharding.

Trade-off vs ring attention (``parallel/ring_attention.py``): Ulysses
moves 2x the activation bytes per layer through ICI but keeps the
attention arithmetic completely local and dense (no per-hop masking
waste for causal chunks and no cp-1 ppermute latency chain); ring
shards heads nowhere, so it supports head counts < cp.  Requirements
here: ``num_heads % cp == 0`` and ``kv_heads % cp == 0`` — callers
(``models/transformer.attention``) route to ring when the head counts
don't divide.

Reference for the algorithm: DeepSpeed-Ulysses (arXiv 2309.14509);
public TPU precedent for all-to-all head/sequence re-sharding is the
GSPMD all-to-all pattern used by the t5x/MaxText MoE stacks.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from megatron_llm_tpu import topology


def ulysses_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
):
    """Inside shard_map: q [b, s/P, nh, d]; k, v [b, s/P, ng, d] with the
    sequence contiguously sharded over ``axis_name`` (chunk r = global
    positions [r*s_local, (r+1)*s_local)).  Returns [b, s/P, nh, d]."""
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    P_sz = lax.psum(1, axis_name)
    nh, ng = q.shape[2], k.shape[2]
    assert nh % P_sz == 0 and ng % P_sz == 0, (
        f"ulysses needs heads divisible by cp: nh={nh} ng={ng} cp={P_sz}")

    # a2a #1: scatter heads, gather sequence -> [b, s, nh/P, d].  Parts
    # from rank r' are its contiguous seq chunk, concatenated in rank
    # order, so the gathered axis is the global sequence in order.
    def scatter_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = scatter_heads(q), scatter_heads(k), scatter_heads(v)

    # local attention over the FULL sequence with nh/P heads: the exact
    # same kernel path as single-device attention (pallas flash on TPU,
    # reference math elsewhere), so all flash tuning carries over.
    # sharded_ variant: tp/dp are still GSPMD-auto inside this cp-manual
    # region, and a Mosaic call can't be auto-partitioned over them
    from megatron_llm_tpu.ops.pallas.flash_attention import (
        sharded_flash_attention,
    )

    ctx = sharded_flash_attention(
        qg, kg, vg, causal=causal, sliding_window=sliding_window,
        softmax_scale=softmax_scale)

    # a2a #2: scatter sequence, gather heads -> [b, s/P, nh, d]
    return lax.all_to_all(ctx, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_context_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    softmax_scale: Optional[float] = None,
):
    """shard_map wrapper mirroring ``ring_attention.context_parallel_attention``:
    global arrays with the sequence axis sharded over cp; nests under the
    pipeline engines' manual regions via ``topology.nesting_mesh``."""
    mesh, _ = topology.nesting_mesh(topology.CP_AXIS)
    if mesh is None:
        raise RuntimeError(
            "ulysses_context_attention called with no usable 'cp' axis in "
            "scope (callers gate on get_context_parallel_world_size() > 1)")
    fn = partial(
        ulysses_self_attention,
        axis_name=topology.CP_AXIS,
        causal=causal,
        sliding_window=sliding_window,
        softmax_scale=softmax_scale,
    )
    spec = P(None, topology.CP_AXIS, None, None)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names={topology.CP_AXIS},
        check_vma=False,
    )(q, k, v)


def ulysses_supported(num_heads: int, num_kv_heads: int, cp: int) -> bool:
    return num_heads % cp == 0 and num_kv_heads % cp == 0
