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

Which ``[tk, tn]``: ``tiles`` takes, of the multiples of 128 that divide
each width, the pair that makes the fewest grid steps a visit and fits
``_VMEM_BUDGET`` by ``vmem_bytes``' count of what the call holds.  A
grid step has a fixed cost of about 0.3 us, and a block under some
half a MiB pays that and not its DMA: 8 layers' chunk at Mellum's 2304
/ 1792 / 896 takes 38.3 ms at blocks of 256 x 256 and 128 x 256 (126
steps a visit, what halving from 1024 / 2048 gave widths that are no
multiples of 512) and 12.4-12.7 ms at any blocks of 0.7 to 3.9 MiB (6
to 2 steps a visit; my chip run, PR 33).  So a block is as large as
fits, and the choice follows the operands' shapes and nothing else.

Rows that belong to no group come back undefined; the caller masks them.
Interpret-mode tests run the kernel on the CPU via ``_INTERPRET``.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.pallas import pallas_backend_available

_INTERPRET = False
# rows a visit multiplies.  128 stay: a visit reads its group's whole
# matrix whatever rows it owns, so taller tiles buy nothing for a group
# of a few rows (a decode step's every group, most of a chunk's) and
# multiply every group's matrix by more rows of other groups, while
# shorter ones make more visits, each a read of the matrix again
_TILE_ROWS = 128
# what a call may hold in VMEM by ``vmem_bytes``' count.  The chip's
# compiler grants a kernel 16 MiB by default (a v5e's scoped limit, not
# raised here); a quarter of it stays for what Mosaic itself needs (the
# epilogue's fp32 temporaries)
_VMEM_BUDGET = 12 * 2 ** 20


def kernel_available() -> bool:
    """True when ``grouped_matmul`` would run the Pallas kernel (TPU
    backend, or interpret mode in tests)."""
    return _INTERPRET or pallas_backend_available()


def vmem_bytes(tm: int, tk: int, tn: int, dtype) -> int:
    """What a call holds in VMEM at these tiles: the pipeline's two
    buffers each of the ``[tk, tn]`` weight block, the ``[tm, tk]`` rows
    block and the ``[tm, tn]`` output block, and the fp32 accumulator."""
    item = jnp.dtype(dtype).itemsize
    return 2 * item * (tk * tn + tm * tk + tm * tn) + 4 * tm * tn


def _divisors(size: int) -> list[int]:
    """The multiples of 128 that divide ``size``; the whole of it when
    none does (a test's tiny width)."""
    return [t for t in range(128, size + 1, 128) if size % t == 0] or [size]


def tiles(k: int, n: int, rows: int, dtype) -> tuple[int, int, int]:
    """(tm, tk, tn) of ``grouped_matmul`` for ``rows`` x [k, n] matrices.

    ``tm`` is ``_TILE_ROWS``, or fewer rows rounded up to a sublane tile.
    ``(tk, tn)`` is the pair of divisors that makes the fewest grid steps
    a visit, ``(k / tk) x (n / tn)``, under ``_VMEM_BUDGET``; of pairs
    that tie (their blocks are as large) the wider ``tn``: longer runs
    of the matrix's rows in a block, and the visit list walked fewer
    times.  (Where a tie's other pair is ONE k tile the chip reads it
    up to 8% faster in a chunk, a straddling group's block not being
    fetched twice: ROADMAP S15 says why that waits.)  The budget is
    counted at the full row tile whatever ``rows`` is, so a model's
    every program (a decode step of few rows, a chunk) takes one
    tiling."""
    tm = _TILE_ROWS if rows >= _TILE_ROWS else -(-rows // 16) * 16
    ks, ns = _divisors(k), _divisors(n)
    fits = [(tk, tn) for tk in ks for tn in ns
            if vmem_bytes(_TILE_ROWS, tk, tn, dtype) <= _VMEM_BUDGET]
    # nothing fits only where a width has no divisor and is huge
    tk, tn = min(fits or [(ks[0], ns[0])],
                 key=lambda t: ((k // t[0]) * (n // t[1]), -t[1]))
    return tm, tk, tn


def describe(k: int, n: int, dtype) -> dict:
    """``tiles``' choice for [k, n] matrices as the serving engine's
    ``stats()['moe_expert_tiles']`` report it."""
    _, tk, tn = tiles(k, n, _TILE_ROWS, dtype)
    return {"k": k, "n": n, "tk": tk, "tn": tn,
            "steps_per_visit": (k // tk) * (n // tn),
            "vmem_bytes": vmem_bytes(_TILE_ROWS, tk, tn, dtype)}


def moe_expert_tiles(mcfg) -> Optional[Dict[str, Dict[str, int]]]:
    """The blocks the experts' grouped matmul takes at a sparse model's
    widths (``tiles``, a function of the operands' shapes): for ``w_in``
    [H, (2x)F] and ``w_out`` [F, H] the widths, the block and the grid
    steps a visit.  None for a dense model.  ``w_in``'s ``n`` is the
    width it is LAID OUT at (``models/moe.py::laid_width``: 1920 for an
    ungated width of 1856), ``w_out``'s ``k`` the width itself."""
    if mcfg.num_experts <= 1:
        return None
    from megatron_llm_tpu.models.moe import laid_width

    H, F = mcfg.hidden_size, mcfg.expert_hidden_size
    wide = 2 * F if mcfg.glu_activation else laid_width(mcfg)
    dtype = mcfg.compute_jnp_dtype
    return {"w_in": describe(H, wide, dtype),
            "w_out": describe(F, H, dtype)}


def _visits(group_sizes: jax.Array, row_tiles: int, tm: int, rows: int):
    """(offsets [G + 1], group of each visit [V], row tile of each visit
    [V], number of visits): the (group, row tile) pairs in which the
    group owns at least one row, in row order."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    per = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_end = jnp.cumsum(per)
    V = row_tiles + min(G, rows) - 1
    v = jnp.arange(V, dtype=jnp.int32)
    # every visit against every group: a few hundred thousand compares in
    # one fused operation, where a binary search is a loop of launches
    group = jnp.minimum(
        jnp.searchsorted(visit_end, v, side="right", method="compare_all"),
        G - 1).astype(jnp.int32)
    tile = first[group] + v - (visit_end[group] - per[group])
    tile = jnp.clip(tile, 0, row_tiles - 1).astype(jnp.int32)
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
    tm, tk, tn = tiles(k, n, m, rows.dtype)
    padded = -(-m // tm) * tm
    if padded != m:
        rows = jnp.pad(rows, ((0, padded - m), (0, 0)))
    offsets, group, tile, visits = _visits(
        group_sizes.astype(jnp.int32), padded // tm, tm, padded)
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
