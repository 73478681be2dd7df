"""Learned sparse attention over the paged pool (Pallas Mosaic TPU): the
indexer's scores, the exact choice of each query's keys, and paged
attention under that choice, for the decode step and the prefill chunk.

``ops/dsa.py`` says what is computed (and is the dense path these
kernels are tested against); ``ops/paged_kv.py::PagedKVCache.attend``
and ``attend_latent`` are the two callers.  Two kernels of this module's
and the shared paged walk under a mask, six names on a profile's ``XLA
Ops`` line (eight with a latent pool's, below):

* ``dsa_index_scores_decode`` / ``dsa_index_scores_prefill``: a walk
  over each row's LIVE pages of the indexer's pool (``index_pages``
  ``[P, bs, di]``), in blocks of ``TB`` keys, block j+1 on its way from
  HBM while block j is scored: ``sum_h w[h] * relu(qI[h] . kI)`` for all
  of the row's queries against the block, written out as
  ``[row, block, query, TB]`` fp32.  Blocks past the row's last live
  page are never written; nobody reads them unmasked.
* ``dsa_select_decode`` / ``dsa_select_prefill``: for 32 queries of a
  chunk at a time (the decode step's batch: 8 rows), the ``topk``
  largest scores over the positions the query may see (``s <= t``),
  exactly and with no sort: the ``topk``-th largest value found by
  building its bit pattern from the top bit down (32 counts over the
  row), equal scores taken from the earliest position (17 more counts,
  over positions).  A step holds its queries' whole rows of the scores
  and of the mask in VMEM (the slot's table: Pallas moves them in and
  out), but COUNTS over blocks ``0 .. n_live - 1`` only, a prefetched
  scalar a step: for a chunk the blocks through its last live key, for
  the decode step through its longest row, 0 for an idle slot.  Every
  count is a loop over those blocks (``_Blocks``: ``dsa.choose`` runs
  over an array it never holds at once), after one pass that leaves the
  scores' ordered bits and each query's open positions in scratch.  Out
  comes an additive mask, 0 for a chosen key and ``NEG_INF`` for any
  other, in the scores' layout, on the counted blocks; past them the
  mask holds whatever the buffer held, and the walks below never fetch
  it.  Never ``approx_max_k``.
* ``paged_attention_sparse_decode`` / ``paged_attention_prefill_masked``:
  NOT a kernel of this module: ``paged_attention.py``'s own walk
  (``_walk_call(..., mask=)``) over the row's live pages of K and V,
  the mask applied to the scores of every block before the online
  softmax, so the query attends the chosen keys and no other.  Its
  fetches, its hand-over of a grid step's first block and its blocks
  that skip the position masks are the shared walk's as they stand (a
  copy of that walk lived here until PR 57 and had none of them: 1,043
  -> 665 us a decode call of 8 rows of 14-21 thousand keys on a v5e,
  bit for bit the same outputs).

Why the decode step walks every live page of K and V and masks, rather
than gathering the chosen tokens out of the pages: a token's keys and
values are 1 KB each at four KV heads of 128, and the walk's cost is its
DMAs, not its bytes (PR 25 read 0.1 us a live PAGE of 16 KB).  2,048
chosen tokens are 4,096 DMAs of 1 KB a row a layer; a context of 18,000
tokens is 2,250 DMAs of 16 KB.  The walk issues fewer, reads each key
once for all 32 heads, and is the kernel the other serving cells
already trust; the mathematics are the same (``PERF.md`` section 6 has
what the chip read).  Prefill attends densely under the mask for the
reason the issue gives: gathering per query token would move 4 MB a
token a layer.

OVER A LATENT POOL (``paged_selected_latent_attention``: a model whose
indexer chooses LATENT rows) the scores and the choice are the two
kernels above as they stand, over ``index_pages`` in blocks of the
latent walk's own 512 rows (``latent_block_keys``), and the attention is
latent attention's two walks under the mask, two more names:
``mla_attention_sparse_decode`` (the shared walk again: one kv group
whose key is the whole row and whose value its first ``value_width``
columns, every absorbed query head against it, a row's whole mask in
VMEM) and ``mla_attention_prefill_masked`` (the latent chunk's own walk,
``paged_attention.py::_chunk_body``: each block of latents expanded per
head in VMEM, a block's ``[C, 512]`` slice of the mask riding with its
pages).  The decode step WALKS every live page and masks for the reason
above, at other numbers: a latent page of 16 rows is one descriptor of
20 KB and costs the walk 0.036 us (the table in ``paged_attention.py``),
2,048 chosen rows are 2,048 descriptors of 1.25 KB, so the walk issues
fewer up to a context of 32k and twice as many at 64k, reads each row
once for all 64 heads, and is the kernel the latent cells already trust
(``PERF.md`` section 6, PR 61, has what the chip read; a step that
gathers is its open question 3).

Interpret-mode tests run these on the CPU via ``paged_attention``'s
module-level ``_INTERPRET`` flag.
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops import dsa as _dsa
from megatron_llm_tpu.ops.pallas import paged_attention as _pa
from megatron_llm_tpu.ops.pallas.paged_attention import (
    NEG_INF, _pages_per_block)

_TILE = 8                    # rows of a vector register
_SELECT_ROWS = 32            # queries a chunk's select step holds in VMEM
_SELECT_LOOP_BYTES = 64 * 1024   # scores one step of the choice's loops takes
_PREFILL_BLOCK_Q = 128       # query rows of a masked-prefill step
_VMEM_LIMIT = 64 * 1024 * 1024


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _params():
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


# ---------------------------------------------------------------------------
# the indexer's scores: a walk over the live pages of index_pages
# ---------------------------------------------------------------------------

def _scores_body(bt_ref, cl_ref, vl_ref, iq_ref, iw_ref, ip_hbm, out_hbm,
                 kbuf, obuf, sem_in, sem_out):
    """One row: blocks 0 .. its last live one; block j scored while block
    j+1 is fetched and block j-1's scores are on their way out."""
    s = pl.program_id(0)
    _, kp, bs, di = kbuf.shape
    _, hi, R, _ = iq_ref.shape
    TB = kp * bs
    ctx, n = cl_ref[s], vl_ref[s]
    top = jnp.minimum(ctx + jnp.maximum(n, 1), bt_ref.shape[1] * bs) - 1
    last = top // bs
    nblk = jnp.where(n > 0, last // kp + 1, 0)

    def fetch(j, slot, start):
        p0 = j * kp

        def page(i, carry):
            cp = pltpu.make_async_copy(
                ip_hbm.at[bt_ref[s, p0 + i]], kbuf.at[slot, i],
                sem_in.at[slot])
            cp.start() if start else cp.wait()
            return carry

        jax.lax.fori_loop(0, jnp.minimum(kp, last - p0 + 1), page, 0)

    def store(j, slot):
        return pltpu.make_async_copy(obuf.at[slot], out_hbm.at[s, j],
                                     sem_out.at[slot])

    @pl.when(nblk > 0)
    def _first_block():
        fetch(0, 0, True)

    w_all = iw_ref[0]                                     # [R, hi] fp32

    def block(j, carry):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nblk)
        def _next_block():
            fetch(j + 1, 1 - slot, True)

        fetch(j, slot, False)
        k = kbuf[slot].reshape(TB, di)
        acc = jnp.zeros((R, TB), jnp.float32)
        for h in range(hi):
            sc = jax.lax.dot_general(
                iq_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # [R, TB]
            acc = acc + w_all[:, h:h + 1] * jnp.maximum(sc, 0.0)

        @pl.when(j >= 2)
        def _buffer_free():
            store(j - 2, slot).wait()

        obuf[slot] = acc
        store(j, slot).start()
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)

    @pl.when(nblk >= 2)
    def _drain_one():
        store(nblk - 2, jax.lax.rem(nblk, 2)).wait()

    @pl.when(nblk >= 1)
    def _drain_last():
        store(nblk - 1, jax.lax.rem(nblk + 1, 2)).wait()


def _index_scores(iq, iw, index_pages, block_tables, context_lens,
                  valid_lens, *, kp, name):
    """iq [S, R, hi, di], iw [S, R, hi] -> scores [S, nblk, R, TB] fp32
    (blocks past a row's live pages unwritten)."""
    S, R, hi, di = iq.shape
    bs = index_pages.shape[1]
    M = block_tables.shape[1]
    nblk = -(-M // kp)
    TB = kp * bs
    iq_t = jnp.transpose(iq, (0, 2, 1, 3))                # [S, hi, R, di]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, hi, R, di), lambda s, *_: (s, 0, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, R, hi), lambda s, *_: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, kp, bs, di), index_pages.dtype),
            pltpu.VMEM((2, R, TB), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _scores_body, name=name, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nblk, R, TB), jnp.float32),
        compiler_params=_params(), interpret=_pa._INTERPRET,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      valid_lens.astype(jnp.int32), iq_t, iw.astype(jnp.float32),
      index_pages)


# ---------------------------------------------------------------------------
# the choice: the topk largest of a row, exactly, with no sort
# ---------------------------------------------------------------------------

class _Blocks:
    """A ``[nblk, rows, TB]`` array held a few blocks at a time:
    ``at(j, u)`` gives blocks ``j .. j + u - 1`` as ``[u, rows, TB]``.  A
    comparison or ``& |`` with another such, or with a per-query
    ``[rows, 1]`` (spread over the lanes once, before any loop), gives
    another, so ``dsa.choose`` runs over it as over an array, and only
    the blocks its ``count`` walks are ever computed."""

    def __init__(self, at, lanes):
        self.at, self.lanes = at, lanes

    def _blockwise(op):
        def apply(self, other):
            if isinstance(other, _Blocks):
                return _Blocks(lambda j, u: op(self.at(j, u), other.at(j, u)),
                               self.lanes)
            other = jnp.broadcast_to(other, other.shape[:-1] + (self.lanes,))
            return _Blocks(lambda j, u: op(self.at(j, u), other), self.lanes)
        return apply

    __ge__, __gt__, __eq__, __lt__, __and__, __or__ = map(
        _blockwise, (operator.ge, operator.gt, operator.eq, operator.lt,
                     operator.and_, operator.or_))


def _select_body(nl_ref, pos_ref, s_ref, o_ref, bits_ref, open_ref, *, topk,
                 pos_bits, group):
    """``rows`` queries: ``s_ref`` [1, nblk, rows, TB] scores, ``pos_ref``
    [1, rows, 1] each query's own position (-1: a dead row, which chooses
    nothing), ``nl_ref`` [G, steps] the blocks this step counts over.
    Key (block j, lane c) lies at position j * TB + c and is open to the
    query at or after it.  Blocks ``n ..`` of ``s_ref`` are never read and
    of ``o_ref`` never written."""
    _, nblk, rows, TB = s_ref.shape
    n = jnp.minimum(nl_ref[pl.program_id(0), pl.program_id(1)], nblk)

    def walk(body, carry):
        """``body(j, u, carry)`` over blocks 0 .. n - 1, ``group`` at a
        time (a loop step of a few vector registers is all branch) and
        what is left one by one."""
        whole = n // group
        carry = jax.lax.fori_loop(
            0, whole, lambda i, c: body(i * group, group, c), carry)
        if group > 1:
            carry = jax.lax.fori_loop(
                whole * group, n, lambda j, c: body(j, 1, c), carry)
        return carry

    within = {u: _iota((u, rows, TB), 0) * TB + _iota((u, rows, TB), 2)
              for u in {group, 1}}
    kpos = _Blocks(lambda j, u: j * TB + within[u], TB)
    pos_q = jnp.broadcast_to(pos_ref[0], (rows, TB))

    def stage(j, u, carry):
        # once a step: the scores' ordered bits and what each query may
        # see, so that a count loads, compares and adds and does no more
        at = pl.ds(j, u)
        bits_ref[at] = _dsa.ordered_bits(s_ref[0, at])
        open_ref[at] = (kpos.at(j, u) <= pos_q).astype(jnp.int32)
        return carry

    walk(stage, 0)
    key = _Blocks(lambda j, u: bits_ref[pl.ds(j, u)], TB)
    valid = _Blocks(lambda j, u: open_ref[pl.ds(j, u)] != 0, TB)

    def count(cond):
        # over a query's row: the live blocks and their lanes; fp32 counts
        # are exact far beyond a row's entries
        acc = walk(lambda j, u, c: c + jnp.sum(
            jnp.where(cond.at(j, u), 1.0, 0.0), axis=0),
            jnp.zeros((rows, TB), jnp.float32))
        return jnp.sum(acc, axis=-1, keepdims=True)       # [rows, 1]

    chosen = _dsa.choose(key, valid, kpos, topk, pos_bits, count)

    def write(j, u, carry):
        o_ref[0, pl.ds(j, u)] = jnp.where(chosen.at(j, u), 0.0, NEG_INF)
        return carry

    walk(write, 0)


def block_keys(block_size, groups, head_dim, dtype, table_pages):
    """Keys of one compute block of a pool of such pages: what a step of
    each walk takes, and the unit the choice counts in."""
    return block_size * _pages_per_block(block_size, groups, head_dim, dtype,
                                         table_pages)


def select_rows(n):
    """Queries a select step holds: a chunk's ``_SELECT_ROWS``, the
    decode step's batch in tiles of 8."""
    return _TILE if n == 1 else _SELECT_ROWS


def select_blocks(context_lens, valid_lens, n, block_keys, xp=jnp):
    """[G, steps] int: the blocks of ``block_keys`` keys each select step
    of a call counts over (``n`` queries a slot, ``select_rows(n)`` a
    step).  A chunk (``n`` > 1): for every step of slot s the blocks that
    hold the slot's keys through the chunk's last live one, 0 for an
    idle slot.  The decode step: its slots are the rows, and a step
    counts through the longest of its rows.  ``xp``: ``numpy`` for the
    host's own count of the same."""
    rows = select_rows(n)
    blocks = (valid_lens > 0) * (-(-(context_lens + valid_lens)
                                   // block_keys))           # [S]
    if n == 1:
        return xp.pad(blocks, (0, -len(blocks) % rows)).reshape(
            1, -1, rows).max(axis=-1)
    return xp.broadcast_to(blocks[:, None], (len(blocks), -(-n // rows)))


def _select(scores, row_pos, n_live, *, topk, name):
    """scores [G, nblk, N, TB], row_pos [G, N], n_live [G, steps] (N /
    steps queries a step) -> additive mask [G, nblk, N, TB] fp32 (0
    chosen, NEG_INF not) on each step's blocks ``0 .. n_live - 1``; what
    lies past them is whatever the buffer held."""
    _, nblk, N, TB = scores.shape
    rows = N // n_live.shape[1]
    assert N == rows * n_live.shape[1] and rows % _TILE == 0, (N, n_live.shape)
    return _select_call(
        n_live.astype(jnp.int32), row_pos.astype(jnp.int32)[..., None],
        scores, topk=topk, name=name, interpret=_pa._INTERPRET,
        group=max(1, min(nblk, _SELECT_LOOP_BYTES // (rows * TB * 4))))


# a jit of its own inside the engine's programs: the layers of a program
# call it with one set of shapes, so the kernel is traced and lowered once
# a program and not once a layer (its loops make it the family's longest
# to trace: 0.1 s a layer a program, which is set-up)
@functools.partial(jax.jit,
                   static_argnames=("topk", "name", "interpret", "group"))
def _select_call(n_live, row_pos, scores, *, topk, name, interpret, group):
    G, nblk, N, TB = scores.shape
    rows = N // n_live.shape[1]
    pos_bits = max(1, math.ceil(math.log2(nblk * TB + 1)))
    spec = pl.BlockSpec((1, nblk, rows, TB), lambda g, i, *_: (g, 0, i, 0),
                        memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, N // rows),
        in_specs=[pl.BlockSpec((1, rows, 1), lambda g, i, *_: (g, i, 0),
                               memory_space=pltpu.VMEM), spec],
        out_specs=spec,
        scratch_shapes=[pltpu.VMEM((nblk, rows, TB), jnp.int32)] * 2,
    )
    return pl.pallas_call(
        functools.partial(_select_body, topk=topk, pos_bits=pos_bits,
                          group=group),
        name=name, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.float32),
        compiler_params=_params(), interpret=interpret,
    )(n_live, row_pos, scores)


# ---------------------------------------------------------------------------
# the public entry: scores, choice, and the shared walk under the choice
# ---------------------------------------------------------------------------

def latent_block_keys(block_size, table_pages):
    """``block_keys`` of a LATENT pool: the rows of one block of the
    absorbed decode walk, which the masked chunk's walk takes too."""
    return block_size * _pa.latent_pages_per_block(block_size, table_pages)


def _choice(iq, iw, index_pages, tables, n, kp, topk):
    """The indexer's scores over each row's live pages of ``index_pages``
    and the choice, in blocks of ``kp`` pages: the additive mask the
    walks take (0 chosen, ``NEG_INF`` not).  ``n`` 1 (the decode step,
    ``iq`` [S, 1, Hi, di]): ``[S, blocks, kp * bs]``.  A chunk (``iq``
    [S, n, Hi, di] with ``n`` a multiple of ``select_rows(n)``: the
    caller pads): ``[S, blocks, n, kp * bs]``.  Blocks past a step's
    ``select_blocks`` hold whatever the buffer held."""
    block_tables, context_lens, valid_lens = tables
    S = iq.shape[0]
    bs = index_pages.shape[1]
    n_live = select_blocks(context_lens, valid_lens, n, kp * bs)
    live = jnp.arange(n)[None, :] < valid_lens[:, None]           # [S, n]
    row_pos = jnp.where(live, context_lens[:, None] + jnp.arange(n)[None, :],
                        -1)
    rows = select_rows(n)
    if n == 1:
        # the rows' one query each, padded to a tile of 8 for the scores
        # and gathered into one batch of rows for the choice
        pad = rows - 1
        scores = _index_scores(
            jnp.pad(iq, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(iw, ((0, 0), (0, pad), (0, 0))), index_pages, *tables,
            kp=kp, name="dsa_index_scores_decode")[:, :, 0, :]    # [S, nb, TB]
        Sp = -S % rows
        scores = jnp.pad(jnp.transpose(scores, (1, 0, 2)),
                         ((0, 0), (0, Sp), (0, 0)))[None]         # [1, nb, S+, TB]
        mask = _select(scores, jnp.pad(row_pos[:, 0], (0, Sp),
                                       constant_values=-1)[None], n_live,
                       topk=topk, name="dsa_select_decode")[0, :, :S]
        # blocks past a step's ``n_live`` ride along unwritten; the walk
        # stops at each row's own last live page
        return jnp.transpose(mask, (1, 0, 2))
    scores = _index_scores(iq, iw, index_pages, *tables, kp=kp,
                           name="dsa_index_scores_prefill")
    return _select(scores, row_pos, n_live, topk=topk,
                   name="dsa_select_prefill")


def _padded_chunk(n, *arrays):
    """``arrays`` [S, n, ...] with rows of zeros appended up to a multiple
    of a chunk's ``select_rows``, and how many."""
    pad = -n % select_rows(n)
    if pad:
        arrays = tuple(
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in arrays)
    return pad, arrays


def paged_selected_attention(q, iq, iw, k_pages, v_pages, index_pages,
                             block_tables, context_lens, valid_lens, *,
                             topk, softmax_scale):
    """``q`` [S, n, nh, d] at positions ``context_lens[s] ..`` over the
    pool (this call's keys, values and indexer keys already written),
    each query attending the ``topk`` keys its indexer (``iq``
    [S, n, Hi, di], ``iw`` [S, n, Hi]) scores highest over the positions
    at or before its own.  ``n`` 1 is the decode step, whose rows are the
    batch; ``n`` > 1 a chunk a row.  Returns [S, n, nh, d]."""
    S, n, nh, d = q.shape
    bs, g = k_pages.shape[1], k_pages.shape[2]
    M = block_tables.shape[1]
    kp = _pages_per_block(bs, g, d, k_pages.dtype, M)
    tables = (block_tables, context_lens, valid_lens)
    if n == 1:
        mask = _choice(iq, iw, index_pages, tables, n, kp, topk)
        return _pa._walk_call(
            q, k_pages, v_pages, *tables, None, None, scale=softmax_scale,
            window=None, block_q=1, name="paged_attention_sparse_decode",
            mask=jnp.repeat(mask, g, axis=-1))
    pad, (q, iq, iw) = _padded_chunk(n, q, iq, iw)
    mask = _choice(iq, iw, index_pages, tables, n + pad, kp, topk)
    bq = min(_PREFILL_BLOCK_Q, n + pad)
    while (n + pad) % bq:       # q-blocks tile the padded chunk exactly
        bq -= 1
    out = _pa._walk_call(
        q, k_pages, v_pages, *tables, None, None, scale=softmax_scale,
        window=None, block_q=bq, name="paged_attention_prefill_masked",
        mask=mask)
    return out[:, :n]


def paged_selected_latent_attention(q, q_rope, kv_up, iq, iw, latent_pages,
                                    index_pages, block_tables, context_lens,
                                    valid_lens, *, topk, softmax_scale,
                                    value_width):
    """``paged_selected_attention`` over a LATENT pool (this call's rows
    and indexer keys already written), in latent attention's two forms
    (``paged_attention.py`` has both walks).  The decode step (``n`` 1),
    ABSORBED: ``q`` [S, 1, nh, W] the absorbed queries at the pool's row
    width, ``kv_up`` None; the shared walk under the mask, one kv group
    whose key is the row and whose value its first ``value_width``
    columns (``mla_attention_sparse_decode``); returns [S, 1, nh,
    value_width].  A chunk, EXPANDED in its kernel: ``q`` [S, n, nh, dn]
    the no-rope queries, ``q_rope`` [S, n, nh, dr], ``kv_up`` [r, nh, dn
    + dv]; the latent chunk's walk with a block's slice of the mask
    riding with its pages (``mla_attention_prefill_masked``); returns
    [S, n, nh, dv]."""
    n = q.shape[1]
    bs, M = latent_pages.shape[1], block_tables.shape[1]
    kp = _pa.latent_pages_per_block(bs, M)
    tables = (block_tables, context_lens, valid_lens)
    if kv_up is None:
        assert n == 1, q.shape
        mask = _choice(iq, iw, index_pages, tables, 1, kp, topk)
        return _pa._walk_call(
            q, latent_pages, None, *tables, None, None, scale=softmax_scale,
            window=None, block_q=1, name="mla_attention_sparse_decode",
            value_width=value_width, mask=mask)
    pad, (q, q_rope, iq, iw) = _padded_chunk(n, q, q_rope, iq, iw)
    mask = _choice(iq, iw, index_pages, tables, n + pad, kp, topk)
    return _pa.latent_attention_prefill(
        q, q_rope, kv_up, latent_pages, block_tables, context_lens,
        valid_lens=valid_lens, softmax_scale=softmax_scale, mask=mask)[:, :n]
