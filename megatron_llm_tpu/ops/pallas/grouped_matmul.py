"""Grouped matrix multiplication (Pallas TPU): the expert matrices of the
dropless MoE layer (``models/moe.py``).

``rows`` [m, k] are the step's (token, choice) assignments sorted by
expert, ``weights`` [G, k, n] one matrix a group, ``group_sizes`` [G] how
many consecutive rows each group owns (in order; rows past their sum
belong to nobody).  Group g's rows are multiplied by ``weights[g]``.

Why a kernel, and not ``jax.lax.ragged_dot``: measured on a TPU v5e at
OLMoE's widths (512 sorted rows, 64 experts of [2048, 2048] + [1024,
2048], bf16; my chip run, PR 26) XLA's own ragged dot takes 2.55 ms a
layer with every expert touched — 313 GB/s of the experts' 805 MB,
where the capacity einsum it replaces takes 1.24 — because it tiles all
512 rows into one block and so multiplies every expert's matrix by 512
rows of which a handful are its own.  A tile of 128 rows (the idiom of
JAX's ``megablox`` grouped matmul, which this follows) reads the same
bytes with a quarter of the products: 1.17 ms, and 0.32 ms when two
tokens are live.

How: the work is a list of VISITS, one per (group, row tile) pair in
which the group owns a row — at most ``tiles + groups - 1`` of them,
built with a cumulative sum and a search by XLA before the call and
prefetched as scalars.  The grid is ``(n tiles, visits, k tiles)`` with
the number of visits a run-time value, so an empty group is never
visited and its weights are never read: time follows the experts that
tokens chose, not the experts there are (a model's every layer can go
in stacked as ``[L * E, k, n]`` with one layer's groups non-empty).  A
visit multiplies its row tile by its group's ``[tk, tn]`` block into an
fp32 accumulator and, after the last k tile, stores the rows the group
owns and leaves the tile's other rows as the tile's other visits wrote
them (visits of one tile are consecutive, so its output block stays in
VMEM between them).  bf16 products, fp32 accumulation, one rounding to
the output's dtype: the precision of the einsum it replaces.

Rows that belong to no group come back undefined; the caller masks them.
Interpret-mode tests run the kernel on the CPU via ``_INTERPRET``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.pallas import pallas_backend_available

_INTERPRET = False
# rows a visit multiplies: the MXU's height; more wastes products on rows
# of other groups, fewer leaves the weights' stream waiting on grid steps
_TILE_ROWS = 128


def kernel_available() -> bool:
    """True when ``grouped_matmul`` would run the Pallas kernel (TPU
    backend, or interpret mode in tests)."""
    return _INTERPRET or pallas_backend_available()


def _largest_tile(size: int, limit: int) -> int:
    """The largest of ``limit``, ``limit / 2`` ... 128 that divides
    ``size``; the whole of it when none does (a test's tiny width)."""
    t = limit
    while t >= 128:
        if size % t == 0:
            return t
        t //= 2
    return size


def _visits(group_sizes: jax.Array, tiles: int, tm: int, rows: int):
    """(offsets [G + 1], group of each visit [V], row tile of each visit
    [V], number of visits): the (group, row tile) pairs in which the
    group owns at least one row, in row order."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    per = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(per)
    V = tiles + min(G, rows) - 1
    v = jnp.arange(V, dtype=jnp.int32)
    # every visit against every group: a few hundred thousand compares in
    # one fused operation, where a binary search is a loop of launches
    group = jnp.minimum(
        jnp.searchsorted(visit_end, v, side="right", method="compare_all"),
        G - 1).astype(jnp.int32)
    tile = first[group] + v - (visit_end[group] - per[group])
    tile = jnp.clip(tile, 0, tiles - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])
    return (offsets.astype(jnp.int32), group, tile,
            jnp.maximum(visit_end[-1], 1).astype(jnp.int32))


def _body(offsets_ref, group_ref, tile_ref, rows_ref, w_ref, out_ref,
          acc_ref, *, tm: int, k_tiles: int):
    v, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(rows_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == k_tiles - 1)
    def _():
        g = group_ref[v]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc_ref.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        out_ref[...] = jnp.where(
            mine, acc_ref[...], out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


@jax.jit
def grouped_matmul(rows: jax.Array, weights: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """rows [m, k] x weights [G, k, n] by ``group_sizes`` [G] -> [m, n]
    in ``rows``' dtype (module docstring)."""
    m, k = rows.shape
    G, _, n = weights.shape
    tm = _TILE_ROWS if m >= _TILE_ROWS else -(-m // 16) * 16
    padded = -(-m // tm) * tm
    if padded != m:
        rows = jnp.pad(rows, ((0, padded - m), (0, 0)))
    tk, tn = _largest_tile(k, 1024), _largest_tile(n, 2048)
    tiles = padded // tm
    offsets, group, tile, visits = _visits(
        group_sizes.astype(jnp.int32), tiles, tm, padded)
    out = pl.pallas_call(
        functools.partial(_body, tm=tm, k_tiles=k // tk),
        name="moe_experts",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda ni, v, ki, o, g, t:
                             (t[v], ki)),
                pl.BlockSpec((None, tk, tn), lambda ni, v, ki, o, g, t:
                             (g[v], ki, ni)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda ni, v, ki, o, g, t:
                                   (t[v], ni)),
            grid=(n // tn, visits, k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((padded, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=_INTERPRET,
    )(offsets, group, tile, rows, weights)
    return out[:m]
