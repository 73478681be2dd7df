"""Ragged paged-attention kernel for the serving engine (Pallas Mosaic
TPU): decode (one query token per slot), chunked prefill (a [C]-token
query block per slot) and the speculative verify step are one walk.

The dense path (``dense_paged_attention`` below) gathers every slot's
block table into a dense ``[b, M*bs, g, d]`` view (dequantizing every
int8 page) before masked attention.  This kernel leaves the pools in HBM
and fetches, slot by slot, only the pages that slot attends
(arXiv:2604.15464 is the blueprint; paged-KV HBM traffic is the serving
throughput ceiling, arXiv:2605.25645).

Shape contract (the serving engine's paged programs):

* ``q`` — decode ``[S, nh, d]``: ONE query token per slot; prefill
  ``[S, C, nh, d]``: a C-token chunk per slot (the engine's ``[1, C]``
  chunked-prefill call).
* ``k_pages``/``v_pages`` — ``[P, bs, g, d]`` shared page pool, already
  containing this call's scatter-on-write (the query tokens' K/V sit at
  positions ``context_lens[s] .. context_lens[s]+C-1``).  int8 pools
  ship per-(page, position, group) fp32 absmax scales ``[P, bs, g]``
  and are dequantized in-kernel, so int8 is what crosses HBM.
* ``block_tables`` — ``[S, M]`` int32, entries beyond a slot's
  allocation = 0 (the reserved garbage block).
* ``context_lens`` — ``[S]`` int32: tokens already cached BEFORE this
  call's queries.  Decode attends keys ``0..context_lens[s]``
  inclusive; prefill row ``j`` attends ``0..context_lens[s]+j`` (causal
  within the chunk on top of the full paged history).  A sliding window
  additionally drops ``key_pos <= query_pos - window``.
* ``valid_lens`` — ``[S]`` int32: this call's real tokens per slot.  A
  slot with 0 is skipped: no fetch, a row of zeros.

Kernel structure: grid ``(slot, q-block)``, sequential; NO grid axis runs
over the table.  A (slot, q-block) attends pages ``first .. last`` of its
row (``last`` from the newest query position, ``first`` from the sliding
window, 0 without one) and loops over them with a dynamic trip count in
compute blocks of ``kp`` pages (16 pages = 256 keys for bf16 pages of
``[16, 8, 128]``, worked out from the shapes so that two blocks of K and
two of V are 2 MiB of VMEM).  Each page of a block is one
``make_async_copy`` a pool from the pool into a double buffer, block j+1
in flight while block j is computed.  A block whose pages are all live
(every block but a walk's last) is ISSUED from a loop of static trip
count; only the last, partial one keeps a dynamic loop.  A whole block
is AWAITED once: a DMA semaphore counts bytes, so ONE descriptor the
size of the buffer half awaits all its pages' arrivals, whichever pages
they were; the partial block awaits its pages one by one.  While a walk's last block
is computed the NEXT grid step's first block is already on its way (the
scalars are prefetched, so step t reads step t+1's lengths and table
row); which buffer half it went to is carried in SMEM scratch.  The
grid's first step starts its own first block, a step that walks nothing
its successor's, the last step none; a slot with ``valid_lens`` 0
fetches nothing, and table entries outside ``first .. last`` of a live
slot are never read.  A decode step runs the loop by twos over STATIC
halves, the block in half 0 then the block in half 1 (a walk whose block
0 lies in half 1 skips the first turn's first): with the issue loop
unrolled, every descriptor's destination is then a constant of the
program, which halves what a descriptor costs the scalar core.  A chunk
keeps one loop over dynamic halves: the paired loop reads faster for the
kernel alone and no faster in the cells (below).  A block that no mask can
touch (wholly at or below the q-block's oldest row, wholly inside its
newest row's window: every block but a walk's last and a window walk's
first) skips the position masks and the zeroing of dead pages' values;
the others mask by position.  fp32 online softmax (m, l, acc in VMEM
scratch) carries across blocks.

What the block MULTIPLIES (PR 62).  Where the pool's dtype is the
queries' (``native_operands``; an int8 pool's is not, and its scales
make the operands fp32), both products take their operands as they lie
in the pool under an fp32 accumulator: ``q x k`` of bf16 by bf16 is
exact there, and nothing of a block of K, of V or of the queries is
widened.  What this replaced widened q, K and V to fp32 and multiplied
fp32 by fp32, which on a v5e under Mosaic's default precision is ONE pass
of bf16 operands: the old walk rounded ``p`` to bf16 once, and with one
rounded term the new operands give the old kernels' outputs bit for bit
(fifteen shapes, chip runs of PR 62).  In how many terms of the pool's
dtype the fp32 probabilities meet the values follows from what bounds
the block (``_value_terms``).  A chunk's block of 512 rows a kv group or
more at heads of 128 or less is bound by the vector units and the MXU
has room: ``p`` goes as TWO terms (``_weighted_values``: over a bf16
pool ``p`` with its lower half cleared, which is a bf16 as it stands,
and what that left, rounded), stacked ``[2R, T]`` so that V crosses the
MXU once: 16 bits of ``p``, eight more than the walk ever had, and
FASTER than one term, the more so the more rows (3% at 512, 14% at
1,024), the stacked operand being laid out for the MXU once where a
single term is converted on its way in.  Everywhere else ONE term, the
bits the walk always gave: a decode step's ``nh`` rows and a q-block of
256 rows (a 64-token chunk at 8 kv heads), which pay for every tile of V
they load whatever streams past it (two terms cost them 2-3% and 9%);
heads of 256, where the MXU bounds a chunk (137 GFLOP in 1.74 ms is 40%
of the peak; two terms +26% there); a verify step's 20 rows, which are
not whole tiles; a latent pool's walk, as its chunk.  m, l, acc, the
exponentials and the masks are fp32 everywhere.

One fetch serves every query head.  Decode multiplies all ``nh`` heads
against all ``(key, kv group)`` pairs of a block in one matmul
``[nh, d] x [d, 256 * g]`` and masks, for each head, the lanes of the
other groups: the pages are used as they lie in the pool, each key
crosses the MXU once either way, and the ``g``-fold exponentials are of
a ``[32, 2048]`` block.  A chunk's rows would make that waste real, so
with ``block_q > 1`` each group's rows ``[block_q * nh/g, d]`` meet that
group's keys ``[256, d]``.

What it costs (TPU v5e, bf16, pages of 16 tokens; PR 62's readings are
the second table; chip runs of PR 48,
the kernel alone, 20 calls chained, PR 47's tree -> this one; the
outputs bit for bit the same): time follows the live pages and not the
table, and a live page costs about the same whatever its bytes.

===================================  ======  ============  ==============  ============
rows x mean live pages (K + V bytes)  pages   us a call     us a live page  of 819 GB/s
===================================  ======  ============  ==============  ============
32 x 65, 8 kv heads (64 KiB)          2,065   276 -> 234    0.134 -> 0.113  60 -> 71%
48 x 456, 4 kv heads (32 KiB)        21,908  2,495 -> 1,570  0.114 -> 0.072  35 -> 56%
48 x 126, the same under a window     6,025   784 -> 557    0.130 -> 0.092  31 -> 43%
  of 2,048
64 x 127, 2 kv heads (16 KiB)         8,096   976 -> 708    0.121 -> 0.088  17 -> 23%
16 x 1,063, a latent pool (20 KiB)   17,011  1,038 -> 620   0.061 -> 0.036  41 -> 69%
8 x 1,151 under a mask of chosen      9,205  1,043 -> 665   0.113 -> 0.072  35 -> 55%
  keys, 4 kv heads (PR 57, below)
===================================  ======  ============  ==============  ============

Split at the second row before the change (PERF.md section 6, PR 48): the
block's arithmetic alone 0.072 us a page (0.057 with the masks skipped
where none can bite), the fetches alone 0.049 (the bytes' own time, and
the same from ONE descriptor a block out of a contiguous pool: the DMA
engine charges nothing by the descriptor), the two together 0.114: what
a descriptor costs is the SCALAR CORE'S time to build it, which does not
run under the block's matmuls.  Unrolling the issue loop or awaiting a
block once gains a twentieth each while a descriptor's destination is
worked out at run time; with both buffer halves static the same loop
costs half.  Of a 20-call chain's us a call some 25 are the timed run's
own launch; chained 200 times, 32 idle slots are 10.0 us a call before
and 12.5 after (0.08 us more a skipped step: it reads its successor's
lengths, so that a live slot's first block is on its way a step early)
and 4 rows of 500 tokens among 32 slots 26.7 and 28.0.  A chunk's
q-blocks are bound by their products: 64 tokens over 1,984 of context
64.9 us (67.7 before, chained 200 times), 512 over 8,192 at 4 kv heads
1.50 ms (1.65; 0.49 under a window of 2,048, 0.51 before), 512 over
2,048 at 2 kv heads 0.43 ms (0.47), the K + 1 verify step of 32 rows
0.67 ms (0.73).  Under the decode step's paired loop the same chunks
read 62.9 us, 1.39 ms (0.45), 0.43 and 0.64 alone, but no cell showed
the gain (``trinity-mini-serve.agent-16k`` 7,058-7,071 tokens/s with it,
6,692-7,146 over six seeds without), a chunk's kernel compiles in 10.1 s
where this one takes 7.4, and tier-1 ran into its time limit with the
interpreted kernel laid out twice more (PERF.md section 6, PR 48): a
chunk keeps the single loop.  Laid out
more than once (a decode step's block four times: two halves, each whole
or masked; a chunk's twice), a kernel costs the host more at every
start: ``paged_decode_4_kv_heads_48_slots`` of
``tests/test_tpu_aot_compile.py`` 0.1 -> 0.7 s to trace and lower and
0.7 -> 2.3 s to compile for a described v5e, the 512-token chunk at 4 kv
heads 0.2 -> 0.3 and 4.1 -> 7.4 (this sandbox's CPU, PR 48).

The same on PR 62's tree (chip runs of PR 62, the kernel alone, 20 calls
chained, the least of 5, tables drawn anew so the pages differ a little;
PR 61's tree -> this tree, whose decode step takes ONE term and whose
outputs are PR 61's to the bit in all seven rows):

===================================  ======  ================  ================
rows x mean live pages               pages   us a call         us a live page
===================================  ======  ================  ================
32 x 63, 8 kv heads                   2,010    228 ->   226    0.113 -> 0.112
48 x 420, 4 kv heads                 20,160  1,446 -> 1,426    0.072 -> 0.071
48 x 129, the same under a window     6,176    569 ->   562    0.092 -> 0.091
  of 2,048
64 x 125, 2 kv heads                  8,018    702 ->   721    0.088 -> 0.090
128 x 115, 4 kv heads                14,738  1,251 -> 1,229    0.085 -> 0.083
32 x 1,085, 2 kv heads of 256        34,728  2,535 -> 2,616    0.073 -> 0.075
8 x 1,055 under a mask of chosen      8,438    622 ->   603    0.074 -> 0.071
  keys, 4 kv heads
===================================  ======  ================  ================

A decode step's block gains a percent or two from values that are not
widened (five rows of seven; at 2 kv heads and at heads of 256 it loses
2.7% and 3.2%, the same arithmetic, bit for bit, laid out otherwise by
the compiler): its products were ONE pass of bf16 operands before, so
the width of its operands is not what bounds it (PR 48's split stands:
the block's arithmetic 0.057-0.072 us a page over fetches of 0.049).  A
second term costs it 2-3% more (1,460 -> 1,487 at 4 kv heads, an
earlier call of PR 62): its 32 rows fill a quarter of the MXU, which
loads 16 tiles of K and 16 of V a block whatever streams past them.  The chunks, PR 61's
tree -> one term -> two, the form this tree takes in capitals: 512 over
8,192 at 4 kv heads (q-blocks of 512 rows a group against 512 keys)
1,410 -> 1,352 -> 1,309 us TWO (69 GFLOP: 27% of 197 TFLOP/s; 486 ->
471 -> 452 TWO under a window of 2,048), 512 over 2,048 at 2 kv heads
(1,024 rows) 368 -> 345 -> 297 TWO, Keye's ``[1, 512]`` chunk over
17,200 keys under its mask (1,024 rows) 2,609 -> 2,511 -> 2,157 TWO (300
live rows over 9,000: 1,111 -> 1,077 -> 929 TWO); 64 tokens over 1,984
of context at 8 kv heads (256 rows against 256 keys) 91.7 -> 87.1 ONE ->
95.5; at heads of 256, where the MXU bounds the chunk (137 GFLOP in 1.74
ms is 40% of the peak), 512 over 16,384 at 2 kv heads 1,739 -> 1,511 ONE
(two terms 1,903 in an earlier call of PR 62: paid in full); the K + 1 verify step of 32 rows (20
rows a group, not whole tiles) 655 -> 667 ONE (stacked 680; as two
products of 20 rows 501, 23% under one product of the same rows, which
nobody has explained: ROADMAP S20(7)).  What bounds a ``[R, T]`` block of
a chunk at heads of 128 now is not its products and not the count of
the vector units' operations either (the scale folded into the exponent
under ``exp2`` and the second ``where`` dropped gave 1.5% and were left
out; clearing ``p``'s lower half in place of rounding it, which saves a
conversion back, 3%): what is left is the traffic of ``[R, T]`` arrays
through VMEM between those operations and the lane reductions of the
maximum and the sum.  A two-term chunk's kernel asks 20 MiB of VMEM
(``_TWO_TERMS_VMEM_LIMIT``): the compiler's stack for it is 18.92 MiB
where the fp32 walk's was 13.7 (compiled here for a described v5e), over
the 16 a kernel gets unasked.

Under a MASK of chosen keys (``_walk_call(..., mask=)``: the learned
sparse attention of ``dsa_attention.py``, which until PR 57 kept a copy
of this walk as it stood before PR 48) a query attends a key only if
the mask says so too.  A decode step holds its row's whole mask
``[blocks, T * g]`` in VMEM; a chunk's stays in HBM ``[S, blocks, C,
T]`` and a block's ``[bq, T]`` slice rides with the block's pages on a
semaphore row of its own, the next grid step's first slice with that
step's first block.  A block that no position mask can touch asks the
chosen lanes and nothing else.  The walk ends at the block of the
slot's last LIVE query (past it nobody wrote the mask), and a q-block
with no live row walks nothing.  Without a mask the traced kernel is
what it was, equation for equation.  Chip runs of PR 57, the kernel
alone at Keye's shapes (pages ``[16, 4, 128]`` bf16, 32 heads, a table
of 2,112, top-k 2,048), 20 calls chained, the copy -> this walk, the
outputs bit for bit the same: 8 rows of 14-21 thousand keys at one
query 1,043 -> 665 us a call (794 with every block taken as an edge
block: the fetches' share of the gain is two thirds), 5 live rows of 8
594 -> 387; a ``[1, 512]`` chunk over 17,200 keys 2,685 -> 2,606 (its
q-blocks of 128 rows of 32 heads were taken to be bound by their fp32
products, which PR 62 found to be one bf16 pass each; the
rows stay in the order above, ``r // qpg``, and the mask's slice is
spread over a group's heads by a sublane broadcast once a block), 300
live rows over 9,000 1,152 -> 1,110.  The decode kernel under a mask
takes 1.7 s to trace and lower in a cold process where the copy took
0.2, once a program.

A LATENT pool (``ops/paged_kv.py``: one array ``[P, bs, W]`` a layer,
a token's row its normed latent, then the one rotary key, then zeros up
to the lanes) has two reads.  A decode step takes the walk above in the
ABSORBED form (``latent_attention_decode``, launched as
``mla_attention_decode``): one kv group whose key is the whole row and
whose value is the row's first ``value_width`` columns, so a page is
fetched ONCE for scores and values, and every query head (absorbed
queries of the row's width) attends it; bytes bound it.  A chunk takes a
walk of its own in the EXPANDED form (``latent_attention_prefill``,
launched as ``mla_attention_prefill``; ``_chunk_body``): the same
prefetched table and double-buffered blocks of live pages, but each
block of latents is multiplied by each head's slice of the
up-projection IN VMEM and the head's queries ``[q_nope ; q_rope]``
attend per-head keys ``[k_nope ; the row's rotary key]`` and values that
never reach HBM.  The whole chunk is one q-block, so a context token is
expanded once a chunk a head: 2 x 32 x (192 + 128) operations a (query,
key) pair and 2 x 512 x 8,192 a context token, 36,864 a pair at a chunk
of 512 where the absorbed chunk multiplies 73,728; below about 170 live
rows a chunk the absorbed form would do fewer.  Products take the pool's
dtype with fp32 accumulation; the expanded keys and values and the
probabilities are rounded to it between them, as a flash kernel's are.

Dispatch mirrors ``flash_attention.py``: TPU backend -> kernel;
otherwise -> the dense reference.  Interpret-mode tests run the kernel
on CPU via the module-level ``_INTERPRET`` flag.  ``ops/paged_kv.py``
owns the pool these entries read and decides which of the two a
program takes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = False
NEG_INF = -1e30
# default prefill q-block rows (clipped to the chunk; kept MXU-sized so
# the fp32 scratch [block_q*nh, d] stays well inside VMEM)
_PREFILL_BLOCK_Q = 128
# and no more (q-block row, head) pairs than this: the fp32 scratch
# [block_q * nh, d] and a group's scores [block_q * qpg, T] grow with
# them, and at 128 rows of 32 heads of 128 the compiler refuses the
# kernel (21.5 MB of the 16 MB a kernel may use on a v5e).  A chunk of
# 64 rows of 32 heads, the most any chunk had until [1, 512] chunks came
# here, is exactly this many.  The scratch grows with a head's WIDTH too:
# the count is of heads of 128 lanes, and a head of two lane rows (256)
# has half as many (128 rows of 16 heads of 256 were refused at 16.8 MB:
# ``paged_prefill_chunk_512_2_kv_heads_of_256`` of
# tests/test_tpu_aot_compile.py)
_PREFILL_BLOCK_ROWS = 2048
_LANES = 128


def _use_pallas() -> bool:
    from megatron_llm_tpu.ops.pallas import pallas_backend_available

    return _INTERPRET or pallas_backend_available()


def kernel_available() -> bool:
    """True when the public entries would run the Pallas kernel (TPU
    backend, or interpret mode in tests): what ``auto`` asks
    (``ops/paged_kv.py::resolve_kernel``)."""
    return _use_pallas()


# ---------------------------------------------------------------------------
# the dense reference: what runs where the kernel cannot (the CPU, a mesh
# of several devices) and what the kernel's tests compare against
# ---------------------------------------------------------------------------

def dense_paged_attention(q, k_pages, v_pages, block_tables,
                          context_lens, valid_lens, k_scales, v_scales,
                          scale, window):
    """Gather every slot's block table into dense ``[S, M*bs, g, d]``
    keys and values (dequantizing int8 pages) and run masked attention
    in fp32: q [S, C, nh, d], row ``j`` of slot ``s`` attends key
    positions ``0..context_lens[s]+j`` (minus the sliding window).

    ``valid_lens`` [S] (None: all C rows of every slot are real) bounds
    the gather to each slot's live pages: table entries whose page
    starts at or beyond ``context_lens + valid_lens`` are read from the
    garbage block 0 instead, so the distinct pages fetched are
    ``ceil(live/bs)`` a slot and not the whole table (the shapes stay
    static, only the gathered indices collapse).  Every key position a
    real row's mask admits lies below that bound."""
    S, C, nh, d = q.shape
    bs, g = k_pages.shape[1], k_pages.shape[2]
    M = block_tables.shape[1]
    qpg = nh // g
    live = context_lens + (C if valid_lens is None else valid_lens)
    block_tables = jnp.where(
        jnp.arange(M)[None, :] * bs < live[:, None], block_tables, 0)
    k = k_pages[block_tables].reshape(S, M * bs, g, d).astype(jnp.float32)
    v = v_pages[block_tables].reshape(
        S, M * bs, g, v_pages.shape[-1]).astype(jnp.float32)
    if k_scales is not None:
        k = k * k_scales[block_tables].reshape(S, M * bs, g, 1)
        v = v * v_scales[block_tables].reshape(S, M * bs, g, 1)
    qg = q.reshape(S, C, g, qpg, d).astype(jnp.float32)
    scores = jnp.einsum("bsgpd,btgd->bgpst", qg, k) * scale
    key_pos = jnp.arange(M * bs)
    pos = context_lens[:, None] + jnp.arange(C)[None, :]        # [S, C]
    valid = key_pos[None, None, :] <= pos[:, :, None]           # [S, C, T]
    if window is not None:
        valid &= key_pos[None, None, :] > (pos[:, :, None] - window)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgpst,btgd->bsgpd", probs, v)
    return out.reshape(S, C, nh, v.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# masking and online softmax: one body for every block of the walk
# ---------------------------------------------------------------------------

def _valid_keys(key_pos, pos, window):
    """Causal and sliding-window validity of key positions against query
    positions (broadcastable int32 arrays)."""
    valid = key_pos <= pos
    if window is not None:
        valid &= key_pos > pos - window
    return valid


def _softmax_block(sq, valid, v, m_scr, l_scr, acc_scr, rows, terms=1):
    """One online-softmax update: fp32 scores ``sq`` [R, T] with their
    validity (None: every score counts) and the values ``v`` [T, d] (fp32,
    or a pool's own dtype, to which the probabilities are then rounded in
    ``terms`` terms: :func:`_weighted_values`) folded into the running
    (m, l, acc) at scratch ``rows``."""
    if valid is not None:
        sq = jnp.where(valid, sq, NEG_INF)
    m_prev = m_scr[rows]                              # [R, 1]
    m_new = jnp.maximum(m_prev, jnp.max(sq, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(sq - m_new)
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    l_scr[rows] = l_scr[rows] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[rows] = acc_scr[rows] * alpha + _weighted_values(p, v, terms)
    m_scr[rows] = m_new


def _weighted_values(p, v, terms):
    """fp32 probabilities ``p`` [R, T] times values ``v`` [T, d] in the
    values' dtype under an fp32 accumulator.  ONE term is ``p`` rounded
    to that dtype (over bf16, 8 bits of it: what an fp32 product on a
    v5e keeps of its operands anyway).  TWO terms keep 16: a bf16 IS the
    upper half of an fp32, so over a bf16 pool the first term is ``p``
    with its lower half cleared, exact as it stands, and the second what
    that left, rounded; any other dtype takes the rounded ``p`` and what
    the rounding left.  The terms go stacked ``[2R, T]`` (``R`` whole
    tiles of that dtype: ``_value_terms``), so that ``v`` crosses the MXU
    once."""
    if terms == 1 or v.dtype == jnp.float32:
        return jax.lax.dot(p.astype(v.dtype), v,
                           preferred_element_type=jnp.float32)
    if v.dtype == jnp.bfloat16:
        hi = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(p, jnp.uint32)
            & jnp.uint32(0xFFFF0000), jnp.float32)
    else:
        hi = p.astype(v.dtype).astype(jnp.float32)
    both = jax.lax.dot(
        jnp.concatenate([hi.astype(v.dtype), (p - hi).astype(v.dtype)],
                        axis=0), v, preferred_element_type=jnp.float32)
    return both[:p.shape[0]] + both[p.shape[0]:]


def native_operands(pool_dtype, query_dtype) -> bool:
    """Whether a walk's two products take their operands as they lie in
    the pool: the pool's dtype is the queries' (an int8 pool's never is,
    and its scales make the operands fp32).  What ``_walk_body`` tests,
    and what ``ops/paged_kv.py::CachePlan.account`` counts by."""
    return jnp.dtype(pool_dtype) == jnp.dtype(query_dtype)


# the fewest rows of a block that carry the probabilities in two terms
# (``_value_terms``; chip runs of PR 62, one term -> two, us a call, the
# kernel alone): 256 rows a kv group against 256 keys 87.1 -> 95.5, 512
# against 512 1,352 -> 1,309, 1,024 against 512 345 -> 297 and (under a
# mask of chosen keys) 2,511 -> 2,157
_TWO_TERM_ROWS = 512


def _value_terms(native, latent, rows, d, dtype) -> int:
    """In how many terms of the pool's dtype a block's probabilities
    ``[rows, T]`` meet its values ``[T, d]``.  TWO where the block is a
    chunk's many rows at heads of 128 or less: the vector units bound it,
    the MXU has room, and the stacked operand is laid out for the MXU
    once where a single term is converted on its way in, so two are
    FASTER than one, the more so the more rows (``_TWO_TERM_ROWS``).
    ONE, the bits the walk always gave, everywhere else: a decode step's
    ``nh`` rows and a short q-block's, which pay for every tile of V
    they load whatever streams past it; heads of 256, where the MXU
    bounds the block; rows that are not whole tiles of the dtype (a
    verify step's 20), which cannot be stacked; and a latent pool's walk,
    which rounds ``p`` once as its chunk does."""
    tile = 8 * 4 // jnp.dtype(dtype).itemsize
    return 2 if (native and not latent and d <= 128
                 and rows >= _TWO_TERM_ROWS and rows % tile == 0) else 1


def _softmax_finish(l_scr, acc_scr, rows):
    """The attention output of scratch ``rows`` (zeros where no key was
    attended)."""
    l = l_scr[rows]                                   # [R, 1]
    return acc_scr[rows] / jnp.where(l == 0.0, 1.0, l)


# ---------------------------------------------------------------------------
# the walk: grid over (slot, q-block), an in-kernel loop over live pages
# ---------------------------------------------------------------------------

# bytes of one compute block of K (or V) in VMEM.  Two of each are held
# (double buffer): 2 MiB, beside some 3 MiB of fp32 temporaries, of the
# 16 MiB a kernel may use on a v5e
_BLOCK_BYTES = 512 * 1024
_BLOCK_TOKENS = 512          # and never more tokens than this


def _pages_per_block(block_size, g, d, dtype, M):
    """Pages of one compute block, from the pool's shapes alone: 16 pages
    (256 tokens) for bf16 pages of [16, 8, 128]."""
    page_bytes = block_size * g * d * jnp.dtype(dtype).itemsize
    return max(1, min(M, _BLOCK_BYTES // page_bytes,
                      _BLOCK_TOKENS // block_size))


def latent_pages_per_block(block_size, M):
    """Pages of one compute block of a LATENT pool's absorbed walk: a
    block of ``_BLOCK_TOKENS`` keys whatever the row's width (the scores'
    lanes are what a block is sized by there).  The selection over
    latents counts, and masks, in blocks of this many pages
    (``dsa_attention.py::latent_block_keys``)."""
    return max(1, min(M, _BLOCK_TOKENS // block_size))


def _walk_body(bt_ref, cl_ref, vl_ref, q_ref, *refs,
               quantized, scale, window, qpg, value_width, masked):
    """One (slot, q-block): walk pages ``first .. last`` of the slot's
    table in blocks of ``kp`` pages, block j+1 on its way from HBM while
    block j is computed, and the NEXT grid step's first block while the
    last one is.  Nothing of the table outside that range is read.
    ``value_width`` (a decode step's only): the pool is a latent one, ONE
    array of pages ``[bs, W]`` whose rows are the keys of one kv group and
    whose first ``value_width`` columns are the values.  ``masked``: a
    mask of CHOSEN keys comes after the queries (``_walk_kernel`` has its
    two layouts), and a query attends a key only if the mask says so too;
    the walk then ends at the block of the slot's last LIVE query, past
    which nobody wrote the mask."""
    latent = value_width is not None
    n_pool = 1 if latent else 4 if quantized else 2
    _, bq, nh, _ = q_ref.shape
    mask_ref = mbuf = None
    if masked:
        mask_ref, refs = refs[0], refs[1:]
    hbm = refs[:n_pool]                   # K, V[, K scales, V scales]
    o_ref = refs[n_pool]
    bufs = refs[n_pool + 1:2 * n_pool + 1]
    scratch = refs[2 * n_pool + 1:]
    if masked and bq > 1:
        # a chunk's mask stays in HBM: a block's slice comes with its pages
        mbuf, scratch = scratch[0], scratch[1:]
    sem, half_ref, m_scr, l_scr, acc_scr = scratch
    s, qi = pl.program_id(0), pl.program_id(1)
    if latent:
        (_, kp, bs, d), g = bufs[0].shape, 1
    else:
        _, kp, bs, g, d = bufs[0].shape
    T = kp * bs                           # keys of a block
    lanes = T * g                         # (page, position, group) triples
    R = bq * qpg                          # query rows of one kv group

    def span(s, qi):
        """What q-block ``qi`` of slot ``s`` walks: its first and last
        page, in how many blocks, and the newest key any row attends."""
        # padded tail rows of a chunk may point past the table: the walk
        # ends where the table ends
        if masked:
            # and at the newest key a LIVE row attends: the mask past its
            # block holds whatever the buffer held
            top = jnp.minimum(
                cl_ref[s] + jnp.minimum((qi + 1) * bq, vl_ref[s]),
                bt_ref.shape[1] * bs) - 1
        else:
            top = jnp.minimum(cl_ref[s] + (qi + 1) * bq,
                              bt_ref.shape[1] * bs) - 1
        last = top // bs
        if window is None:
            first = 0
        else:
            first = jnp.maximum(cl_ref[s] + qi * bq - window + 1, 0) // bs
        # a slot with no token in this call walks nothing: no fetch,
        # zeros out (under a mask: a q-block with no live row)
        nblk = jnp.where(vl_ref[s] > (qi * bq if masked else 0),
                         (last - first) // kp + 1, 0)
        return first, last, nblk, top

    ctx = cl_ref[s]                       # keys cached before this call
    q0 = qi * bq                          # first chunk row of the q-block
    first, last, nblk, top = span(s, qi)
    # the grid step after this one (the slot's next q-block, else the
    # next slot's first; the grid's last step has none) and what it walks
    wrap = qi + 1 == pl.num_programs(1)
    s_next = jnp.where(wrap, s + 1, s)
    more = s_next < pl.num_programs(0)
    s_next = jnp.where(more, s_next, s)
    qi_next = jnp.where(wrap, 0, qi + 1)
    first_next, last_next, nblk_next, _ = span(s_next, qi_next)
    nblk_next = jnp.where(more, nblk_next, 0)
    # the buffer half that holds this step's block 0: the step before
    # left word of it, having put the block on its way
    opening = (s == 0) & (qi == 0)
    half0 = jnp.where(opening, 0, half_ref[0])

    def live_pages(first, last, j):
        # the last block stops at the last live page
        return jnp.minimum(kp, last - (first + j * kp) + 1)

    def page_copy(which, page, half, i):
        # a page of keys or values; of scales, one row
        src, dst = ((hbm[which].at[page], bufs[which].at[half, i])
                    if which < 2 else
                    (hbm[which].at[pl.ds(page, 1)],
                     bufs[which].at[half, pl.ds(i, 1)]))
        return pltpu.make_async_copy(src, dst, sem.at[which, half])

    def mask_copy(s, qi, j, half):
        # block j's slice of q-block qi's rows of a chunk's mask
        return pltpu.make_async_copy(
            mask_ref.at[s, j, pl.ds(qi * bq, bq)], mbuf.at[half],
            sem.at[n_pool, half])

    def start_block(s, first, last, j, half, qi=None):
        """Put block ``j`` of a walk ``first .. last`` of slot ``s`` on
        its way into buffer ``half``.  Into a STATIC half, a block whose
        pages are all live (every block but a walk's last) is issued from
        a loop of static trip count, unrolled: every descriptor's
        destination is a constant and neighbouring pages' table reads and
        address sums share bundles.  The partial block, and a block into
        a half known only at run time, keep the dynamic loop.  Under a
        chunk's mask the slice of q-block ``qi`` rides with the pages."""
        if mbuf is not None:
            mask_copy(s, qi, j, half).start()
        p0 = first + j * kp

        def page_start(i, carry=0):
            page = bt_ref[s, p0 + i]
            for which in range(n_pool):
                page_copy(which, page, half, i).start()
            return carry

        live = live_pages(first, last, j)
        if not isinstance(half, int):
            jax.lax.fori_loop(0, live, page_start, 0)
            return

        @pl.when(live == kp)
        def _whole():
            for i in range(kp):
                page_start(i)

        @pl.when(live < kp)
        def _partial():
            jax.lax.fori_loop(0, live, page_start, 0)

    def wait_block(live, half):
        """Await the ``live`` pages of the block in buffer ``half``.  A DMA
        semaphore counts bytes, so ONE descriptor the size of the buffer
        half awaits a whole block's pages whichever they were; the partial
        block awaits its pages one by one."""
        if mbuf is not None:
            mask_copy(0, 0, 0, half).wait()

        @pl.when(live == kp)
        def _whole():
            for which in range(n_pool):
                pltpu.make_async_copy(hbm[which].at[pl.ds(0, kp)],
                                      bufs[which].at[half],
                                      sem.at[which, half]).wait()

        @pl.when(live < kp)
        def _partial():
            def page_wait(i, carry):
                for which in range(n_pool):
                    page_copy(which, 0, half, i).wait()
                return carry

            jax.lax.fori_loop(0, live, page_wait, 0)

    def q_block(mine):
        # whose slice of a chunk's mask: this step's or the next's
        return None if mbuf is None else jnp.where(mine, qi, qi_next)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    # a block 0 that no step has started: this step's, on the grid's
    # first step; the next step's, where this one walks nothing
    mine = opening & (nblk > 0)

    @pl.when(mine | ((nblk == 0) & (nblk_next > 0)))
    def _unstarted_block():
        start_block(jnp.where(mine, s, s_next),
                    jnp.where(mine, first, first_next),
                    jnp.where(mine, last, last_next), 0, half0,
                    q_block(mine))

    def iota(shape, dim):
        return jax.lax.broadcasted_iota(jnp.int32, shape, dim)

    # bf16 queries on bf16 pools multiply as they are, a step's and a
    # chunk's (a product of two bf16 is exact in the fp32 accumulator);
    # anything else (int8 pools, whose scales are fp32) goes to fp32
    native = native_operands(bufs[0].dtype, q_ref.dtype)
    terms = _value_terms(native, latent, nh if bq == 1 else R, d,
                         bufs[0].dtype)
    q = q_ref[0] if native else q_ref[0].astype(jnp.float32)  # [bq, nh, d]
    # a chunk's rows by kv group, [R, d] each (flat row r is chunk row
    # r // qpg, head r % qpg): cut out once a grid step, not once a block
    q_rows = None if bq == 1 else [
        q[:, grp * qpg:(grp + 1) * qpg, :].reshape(R, d) for grp in range(g)]

    def dequantized(x, sc):
        """int8 [kp, bs, g, d] times its scales [kp, bs * g] (a page's
        in the order of its rows), as fp32 [lanes, d]."""
        x = x.astype(jnp.float32).reshape(kp, bs * g, d)
        col = sc.T                                      # [bs * g, kp]
        return jnp.concatenate(
            [x[i] * col[:, i:i + 1] for i in range(kp)], axis=0)

    if bq == 1:
        # decode: all nh heads against all (key, group) pairs of the
        # block in ONE matmul.  Lane c of the scores is key c // g of
        # the block and kv group c % g, and head h keeps the lanes of
        # its own group: the pages are used as they lie, each key
        # crosses the MXU once as it would a group at a time, and the
        # mask costs g times the exponentials of a tiny block.  A
        # constant of the shapes (with one group, every lane)
        lane = iota((nh, lanes), 1)
        own = None if g == 1 else (
            jax.lax.rem(lane, g) == jax.lax.div(iota((nh, lanes), 0), qpg))

    def attend(j, base, half, edge):
        """Fold block ``j`` in buffer ``half``, whose first key stands at
        position ``base``, into the running softmax.  ``edge``: some key
        of it is dropped for some row by where it stands (past a row's
        position, behind a row's window, or on a page past the last live
        one); a block that none of these can touch skips them all, and
        under a mask of chosen keys asks nothing else."""
        if latent:
            # keys and values are one fetch: the rows, and their first
            # columns.  Pages past the last live one are zeroed whole
            k = bufs[0][half].reshape(lanes, d)
            if edge:
                k = jnp.where(base + iota((lanes, 1), 0) <= top, k,
                              jnp.zeros_like(k))
            if not native:
                k = k.astype(jnp.float32)
            v = k[:, :value_width]
        elif quantized:
            k = dequantized(bufs[0][half], bufs[2][half])
            v = dequantized(bufs[1][half], bufs[3][half])
        else:
            k = bufs[0][half].reshape(lanes, d)
            v = bufs[1][half].reshape(lanes, d)
            if not native:
                k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        # buffer pages past the last live one hold what an earlier block
        # left there: their scores are masked below, and their values
        # zeroed here so that 0 x (whatever they are) adds nothing
        if edge and not latent:
            v = jnp.where(
                base + jax.lax.div(iota((lanes, 1), 0), g) <= top, v, 0.0)

        def both(a, b):
            return b if a is None else a if b is None else a & b

        if bq == 1:
            valid = own
            if masked:
                # the row's whole mask is here: block j's lanes, as the
                # scores' (key c // g, group c % g)
                valid = both(own,
                             mask_ref[0, pl.ds(j, 1), :] > 0.5 * NEG_INF)
            if edge:
                valid = both(
                    _valid_keys(base + jax.lax.div(lane, g), ctx, window),
                    valid)
            sq = jax.lax.dot_general(
                q[0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [nh, lanes]
            _softmax_block(sq, valid, v, m_scr, l_scr, acc_scr, slice(None),
                           terms)
            return
        # a chunk: the rows of one kv group [R, d] against that group's
        # keys [T, d]
        k, v = k.reshape(T, g, d), v.reshape(T, g, d)
        valid = None
        if masked:
            # a chunk row's choice, for each of the group's heads
            valid = jnp.broadcast_to(
                mbuf[half][:, None, :], (bq, qpg, T)).reshape(R, T) \
                > 0.5 * NEG_INF
        if edge:
            valid = both(_valid_keys(
                base + iota((R, T), 1),
                ctx + q0 + jax.lax.div(iota((R, T), 0), qpg), window), valid)
        for grp in range(g):
            sq = jax.lax.dot_general(
                q_rows[grp], k[:, grp, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # [R, T]
            _softmax_block(sq, valid, v[:, grp, :], m_scr, l_scr, acc_scr,
                           slice(grp * R, (grp + 1) * R), terms)

    def block(j, half):
        """Block ``j`` of this walk, in buffer ``half``."""
        # into the other half: this walk's next block, or after its last
        # the next grid step's first
        inner = j + 1 < nblk

        @pl.when(inner | (nblk_next > 0))
        def _next_block():
            start_block(jnp.where(inner, s, s_next),
                        jnp.where(inner, first, first_next),
                        jnp.where(inner, last, last_next),
                        jnp.where(inner, j + 1, 0), 1 - half,
                        q_block(inner))

        wait_block(live_pages(first, last, j), half)
        # every row attends every key of the block: none is newer than
        # the q-block's oldest row nor behind its newest row's window
        # (a walk's last block, and a window walk's first, are not such)
        base = (first + j * kp) * bs                    # block's first key
        whole = base + T - 1 <= jnp.minimum(ctx + q0, top)
        if window is not None:
            whole &= base > ctx + q0 + bq - 1 - window

        @pl.when(whole)
        def _whole():
            attend(j, base, half, edge=False)

        @pl.when(jnp.logical_not(whole))
        def _edge():
            attend(j, base, half, edge=True)

    if bq == 1:
        # a decode step's block is cheap enough for its fetch to show, and
        # a descriptor into a half known only at run time costs the scalar
        # core twice a constant one's (PERF.md section 6, PR 48): the loop
        # runs by twos, the block in half 0 then the block in half 1, and a
        # walk whose block 0 lies in half 1 skips the first turn's first
        def pair(i, carry):
            j = 2 * i - half0

            @pl.when(j >= 0)
            def _in_half_0():
                block(j, 0)

            @pl.when(j + 1 < nblk)
            def _in_half_1():
                block(j + 1, 1)

            return carry

        jax.lax.fori_loop(0, (nblk + half0 + 1) // 2, pair, 0)
    else:
        # a chunk keeps one loop, the half worked out as it goes: under the
        # paired loop its kernel reads 2 to 8% faster alone, the cells that
        # run it no faster, and it takes 1.4 times as long to compile (the
        # module's docstring)
        def one(j, carry):
            block(j, jax.lax.rem(half0 + j, 2))
            return carry

        jax.lax.fori_loop(0, nblk, one, 0)
    half_ref[0] = jax.lax.rem(half0 + nblk, 2)
    if bq == 1:
        out = _softmax_finish(l_scr, acc_scr, slice(None))[None]
    else:
        outs = [_softmax_finish(l_scr, acc_scr,
                                slice(grp * R, (grp + 1) * R)
                                ).reshape(bq, qpg, d) for grp in range(g)]
        out = outs[0] if g == 1 else jnp.concatenate(outs, axis=1)
    o_ref[0] = out.astype(o_ref.dtype)                  # [bq, nh, d]


def _walk_call(q, k_pages, v_pages, block_tables, context_lens,
               valid_lens, k_scales, v_scales, *, scale, window, block_q,
               name, name_suffix="", value_width=None, mask=None):
    """q [S, C, nh, d] with block_q | C (decode is C == block_q == 1);
    the pools stay in HBM and the kernel fetches pages itself.  ``name``
    is the kernel's name in a profile (``name_suffix``, the caller's
    ``_window`` for a window group's walk, and ``_quant`` for the int8
    pools appended); ``valid_lens`` None = every slot has tokens in this
    call.  ``value_width`` (with ``block_q`` 1: the absorbed decode step):
    ``k_pages`` is a latent pool ``[P, bs, W]`` (``v_pages`` None) and the
    output is ``[S, C, nh, value_width]``.  ``mask`` (over a bf16 pool of
    K and V with no window): fp32, above ``NEG_INF / 2`` where the query
    may attend the key, in blocks of the walk's own ``kp * bs`` keys; a
    decode step's ``[S, blocks, kp * bs * g]`` (lane c of a block is key
    c // g, group c % g, as the scores'), a chunk's ``[S, blocks, C,
    kp * bs]``.  Blocks past a slot's last live query's may hold
    anything: ``valid_lens`` must then be given.  A LATENT pool's decode
    step takes a mask too (the selection over latents,
    ``mla_attention_sparse_decode``: one group, so a block's lanes are
    its keys); a latent chunk's mask is its own walk's
    (``latent_attention_prefill``)."""
    if valid_lens is None:
        valid_lens = jnp.ones_like(context_lens)
    bs, M = k_pages.shape[1], block_tables.shape[1]
    if value_width is not None:
        kp = latent_pages_per_block(bs, M)
    else:
        kp = _pages_per_block(bs, k_pages.shape[2], q.shape[-1],
                              k_pages.dtype, M)
    return _walk_kernel(
        q, k_pages, v_pages, block_tables, context_lens, valid_lens,
        k_scales, v_scales, mask, scale=scale, window=window,
        block_q=block_q, name=name + name_suffix, value_width=value_width,
        kp=kp, interpret=_INTERPRET)


# a jit of its own inside the engine's programs, as the latent chunk's
# below: the layers of a program that call it with one set of shapes share
# ONE traced and lowered kernel.  A decode step's kernel, its issue loop
# unrolled over two buffer halves, takes 0.7 s to trace and lower where the
# old one took 0.1 (the module's docstring), and an engine warms a dozen
# programs of eight such layers: 22 s of set-up with every program in the
# compile cache
@functools.partial(jax.jit, static_argnames=(
    "scale", "window", "block_q", "name", "value_width", "kp", "interpret"))
def _walk_kernel(q, k_pages, v_pages, block_tables, context_lens,
                 valid_lens, k_scales, v_scales, mask=None, *, scale, window,
                 block_q, name, value_width, kp, interpret):
    """``kp`` pages a compute block."""
    S, C, nh, d = q.shape
    latent = value_width is not None
    bs, g = k_pages.shape[1], 1 if latent else k_pages.shape[2]
    dv = value_width if latent else d
    bq = block_q
    assert C % bq == 0, (C, bq)
    # a whole block is awaited by one descriptor of kp pages of the pool
    assert k_pages.shape[0] >= kp, (k_pages.shape, kp)
    quantized = k_scales is not None
    if latent:
        pools = [k_pages]
        bufs = [pltpu.VMEM((2, kp, bs, d), k_pages.dtype)]
    else:
        pools = [k_pages, v_pages]
        bufs = [pltpu.VMEM((2, kp, bs, g, d), k_pages.dtype)] * 2
    if quantized:
        # scales as [P, bs * g] rows, the order of a page's rows
        pools += [x.astype(jnp.float32).reshape(-1, bs * g)
                  for x in (k_scales, v_scales)]
        bufs += [pltpu.VMEM((2, kp, bs * g), jnp.float32)] * 2

    # a mask of chosen keys: the row's whole mask in VMEM for a decode
    # step, a chunk's left in HBM and fetched a block's slice at a time on
    # a semaphore row of its own.  Its q-block is as large as the caller
    # says (the selection's chunk: 128 rows of every head, 4 MiB of fp32
    # scratch and as much again of a group's scores), so the kernel asks
    # for the VMEM the latent chunk's asks for
    masks, mask_specs, extra = [], [], {}
    if _value_terms(native_operands(k_pages.dtype, q.dtype), latent,
                    nh if bq == 1 else bq * (nh // g), d,
                    k_pages.dtype) == 2:
        # the probabilities' two terms stand beside a block's scores
        extra = dict(compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_TWO_TERMS_VMEM_LIMIT))
    if mask is not None:
        assert not quantized and window is None, name
        assert bq == 1 or not latent, name
        blocks = -(-block_tables.shape[1] // kp)
        masks, extra = [mask], dict(compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT))
        if bq == 1:
            assert mask.shape == (S, blocks, kp * bs * g), mask.shape
            mask_specs = [pl.BlockSpec((1, blocks, kp * bs * g),
                                       lambda s, qi, *_: (s, 0, 0),
                                       memory_space=pltpu.VMEM)]
        else:
            assert mask.shape == (S, blocks, C, kp * bs), mask.shape
            mask_specs = [pl.BlockSpec(memory_space=pl.ANY)]
            bufs = bufs + [pltpu.VMEM((2, bq, kp * bs), jnp.float32)]

    def q_map(s, qi, bt_ref, cl_ref, vl_ref):
        return (s, qi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, C // bq),
        in_specs=[pl.BlockSpec((1, bq, nh, d), q_map,
                               memory_space=pltpu.VMEM)] + mask_specs
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
        out_specs=pl.BlockSpec((1, bq, nh, dv), q_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=bufs + [
            pltpu.SemaphoreType.DMA((len(bufs), 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((bq * nh, 1), jnp.float32),
            pltpu.VMEM((bq * nh, 1), jnp.float32),
            pltpu.VMEM((bq * nh, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_walk_body, quantized=quantized, scale=scale,
                          window=window, qpg=nh // g,
                          value_width=value_width, masked=bool(masks)),
        name=name + ("_quant" if quantized else ""),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, C, nh, dv), q.dtype),
        interpret=interpret, **extra,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      valid_lens.astype(jnp.int32), q, *masks, *pools)


# ---------------------------------------------------------------------------
# public entries
# ---------------------------------------------------------------------------

def paged_attention_decode(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    valid_lens: Optional[jax.Array] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    name_suffix: str = "",
) -> jax.Array:
    """Ragged paged attention for one decode token per slot.

    ``q``: [S, nh, d]; pools: [P, bs, g, d] (GQA when g < nh; pass the
    int8 pools plus ``k_scales``/``v_scales`` [P, bs, g] for in-kernel
    dequant); ``block_tables``: [S, M]; ``context_lens``: [S] query
    positions; ``valid_lens``: [S], 0 for a slot that is not decoding
    (its pages are not touched and its output row is unspecified; None
    = every slot decodes).  ``name_suffix`` is appended to the kernel's
    name in a profile (one walk, told apart by who launches it).
    Returns [S, nh, d] in ``q.dtype``."""
    assert q.ndim == 3 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    assert q.shape[0] == block_tables.shape[0] == context_lens.shape[0]
    assert (k_scales is None) == (v_scales is None)
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    if not _use_pallas():
        return dense_paged_attention(
            q[:, None], k_pages, v_pages, block_tables, context_lens,
            valid_lens, k_scales, v_scales, softmax_scale,
            sliding_window)[:, 0]
    return _walk_call(
        q[:, None], k_pages, v_pages, block_tables, context_lens, valid_lens,
        k_scales, v_scales, scale=softmax_scale, window=sliding_window,
        block_q=1, name="paged_attention_decode",
        name_suffix=name_suffix)[:, 0]


def paged_attention_prefill(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    *,
    valid_lens: Optional[jax.Array] = None,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
    sliding_window: Optional[int] = None,
    block_q: Optional[int] = None,
    name_suffix: str = "",
) -> jax.Array:
    """Ragged paged attention for one prefill chunk per slot.

    ``q``: [S, C, nh, d] — C query tokens per slot sitting at absolute
    positions ``context_lens[s] .. context_lens[s]+C-1`` (their K/V must
    already be scattered into the pools, as ``PagedKVCache.attend``
    does before the read).  Row ``j`` attends the full paged
    history plus its own causal prefix of the chunk; padded tail rows of
    a short final chunk compute garbage-in-garbage-out exactly like the
    dense path (the engine only reads the last valid row's logits).
    ``valid_lens`` [S]: real tokens of each slot's chunk; a slot with 0
    (an idle row of the speculative verify step) is skipped as in
    :func:`paged_attention_decode`; ``name_suffix`` as there.  Returns
    [S, C, nh, d] in ``q.dtype``."""
    assert q.ndim == 4 and k_pages.ndim == 4, (q.shape, k_pages.shape)
    assert q.shape[0] == block_tables.shape[0] == context_lens.shape[0]
    assert (k_scales is None) == (v_scales is None)
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    if not _use_pallas():
        return dense_paged_attention(
            q, k_pages, v_pages, block_tables, context_lens, valid_lens,
            k_scales, v_scales, softmax_scale, sliding_window)
    C = q.shape[1]
    lane_rows = max(1, -(-q.shape[3] // _LANES))
    bq = min(block_q or max(1, min(
        _PREFILL_BLOCK_Q,
        _PREFILL_BLOCK_ROWS // (q.shape[2] * lane_rows))), C)
    while C % bq:       # q-blocks must tile the chunk exactly; static
        bq -= 1         # (power-of-two chunks keep the full block size)
    return _walk_call(
        q, k_pages, v_pages, block_tables, context_lens, valid_lens,
        k_scales, v_scales, scale=softmax_scale, window=sliding_window,
        block_q=bq, name="paged_attention_prefill", name_suffix=name_suffix)


def dense_latent_attention(q, pages, block_tables, context_lens, valid_lens,
                           scale, value_width):
    """The dense reference of the ABSORBED form, a step's or a chunk's
    (``q`` [S, C, nh, W]): every slot's table gathered, one kv group
    whose values are the keys' first ``value_width`` columns."""
    keys = pages[:, :, None, :]
    return dense_paged_attention(
        q, keys, keys[..., :value_width], block_tables, context_lens,
        valid_lens, None, None, scale, None)


def latent_attention_decode(q, pages, block_tables, context_lens, *,
                            valid_lens=None, value_width: int,
                            softmax_scale: float):
    """Ragged attention over a latent pool for one decode token a slot.

    ``q`` [S, nh, W]: absorbed queries at the pool's row width (a row is
    ``[latent ; rotary key ; zeros]``, the query ``[W_UK^T q_nope ;
    q_rope ; anything]``); ``pages`` [P, bs, W]; tables and lengths as
    :func:`paged_attention_decode`.  Returns ``[S, nh, value_width]``:
    each head's probabilities over the rows' first ``value_width``
    columns, every live page read once."""
    assert q.ndim == 3 and pages.ndim == 3, (q.shape, pages.shape)
    if not _use_pallas():
        return dense_latent_attention(
            q[:, None], pages, block_tables, context_lens, valid_lens,
            softmax_scale, value_width)[:, 0]
    return _walk_call(
        q[:, None], pages, None, block_tables, context_lens, valid_lens,
        None, None, scale=softmax_scale, window=None, block_q=1,
        name="mla_attention_decode", value_width=value_width)[:, 0]


# ---------------------------------------------------------------------------
# a latent pool's chunk: the EXPANDED form, in a walk of its own
# ---------------------------------------------------------------------------

# keys of one block of latents (64 pages of 16), and heads of one step of
# the loop over a group's heads: two heads' chains (expansion, scores,
# softmax, values) in one body, so that one's products run under the
# other's exponentials.  What a (slot, head group) may hold in VMEM, and
# the limit the compiler is given for it (a v5e has 128 MiB; a kernel
# gets 16 unless it asks).  Measured on a v5e at the served widths, a
# [1, 512] chunk over 10,240 tokens of context (the absorbed walk this
# replaces: 2.82 ms): blocks of 512 keys 1.97 ms, 1,024 1.59, 2,048 1.75
# (half a block of dead keys a chunk); two heads a step 1.49, four 1.49,
# eight 1.59; groups of 4 / 8 / 16 / 32 heads 1.60 / 1.49 / 1.44 / 1.42
_CHUNK_BLOCK_TOKENS = 1024
_CHUNK_HEADS_A_STEP = 2
_CHUNK_VMEM_BYTES = 48 * 1024 * 1024
_CHUNK_VMEM_LIMIT = 64 * 1024 * 1024
# what a paged chunk whose probabilities go in two terms is given, and so
# HELD TO: tests/test_tpu_aot_compile.py compiles every such case under
# it, and a stack that grows past it fails there.  The compiler's stack
# for a q-block of 512 rows a kv group against 512 keys at 4 kv heads,
# the largest of the unmasked served shapes (compiled for a described
# v5e): 13.7 MB with fp32 operands (PR 61), 18.92 MiB with the pool's and
# two terms (PR 62; 16.92 at 8 kv heads of 64), over the 16 a kernel gets
# unasked.  A chunk under a mask asks ``_CHUNK_VMEM_LIMIT``, as it did
_TWO_TERMS_VMEM_LIMIT = 20 * 1024 * 1024


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def _chunk_heads(nh, C, T, W, dq, dkv, dv, r, itemsize,
                 mask_bytes: int = 0) -> int:
    """Heads of one grid step of the chunk's walk: the most (a divisor of
    ``nh``) whose queries, up-projection and output (each double-buffered
    by the pipeline), fp32 accumulator and running max and sum (a lane
    each, a whole tile wide in VMEM) fit ``_CHUNK_VMEM_BYTES`` beside the
    two blocks of latents and the temporaries of the heads in flight
    (expanded keys and values, scores and probabilities, in fp32 and
    rounded).  At the served widths 2 MB a head beside 15.6 MB: 16 heads,
    and the latents are fetched twice a layer (27 MB at a context of
    10k, 0.03 ms)."""
    a_head = (2 * C * _lanes(dq) * itemsize + 2 * r * _lanes(dkv) * itemsize
              + 2 * C * _lanes(dv) * itemsize + C * _lanes(dv) * 4
              + 2 * C * 128 * 4)
    shared = 2 * T * _lanes(W) * itemsize + _CHUNK_HEADS_A_STEP * (
        T * _lanes(dkv) * (4 + itemsize) + C * _lanes(T) * (4 + 4 + itemsize))
    fit = max(1, (_CHUNK_VMEM_BYTES - shared - mask_bytes) // a_head)
    return max(h for h in range(1, nh + 1) if nh % h == 0 and h <= fit)


def _chunk_body(bt_ref, cl_ref, vl_ref, q_ref, w_ref, *refs, scale, nope,
                chosen=False):
    """One (slot, head group) of a latent pool's chunk: walk the slot's
    live pages in blocks of ``kp`` pages (block j+1 on its way from HBM
    while block j is computed) and, for each block of latents and each
    head of the group, expand the head's no-rope keys and its values
    through its slice of the up-projection IN VMEM, score the head's
    queries against ``[k_nope ; the block's one rotary key]`` and fold
    probabilities times values into the head's running (m, l, acc).
    ``chosen``: a mask of CHOSEN keys ``[S, blocks, C, T]`` (fp32 in HBM,
    above ``NEG_INF / 2`` where the query may attend the key; written
    through the block of the slot's last live query, where this walk
    ends too) comes after the up-projection, and a block's ``[C, T]``
    slice rides with the block's pages on a semaphore of its own: a
    query attends a key only if the mask says so too, and one that no
    key of a block is chosen for (its first block's too: key 0 need not
    be chosen) adds nothing there."""
    if chosen:
        (mask_ref, pages_ref, o_ref, buf, mbuf, sem, msem, m_scr, l_scr,
         acc_scr) = refs
    else:
        pages_ref, o_ref, buf, sem, m_scr, l_scr, acc_scr = refs
    s = pl.program_id(0)
    _, hg, C, dq = q_ref.shape
    rank = w_ref.shape[1]
    _, kp, bs, W = buf.shape
    T = kp * bs                               # keys of a block
    step = _CHUNK_HEADS_A_STEP if hg % _CHUNK_HEADS_A_STEP == 0 else 1
    ctx, live = cl_ref[s], vl_ref[s]
    # newest key a live row attends; a slot with no token in this call
    # walks nothing: no fetch, zeros out
    top = jnp.minimum(ctx + live, bt_ref.shape[1] * bs) - 1
    last = jnp.maximum(top, 0) // bs
    nblk = jnp.where(live > 0, last // kp + 1, 0)
    # blocks whose every key every row attends (keys 0 .. ctx): all their
    # pages are live and nothing of them is masked
    whole = jnp.minimum((ctx + 1) // T, nblk)

    def block_dma(j, slot, start):
        p0 = j * kp
        if chosen:
            cp = pltpu.make_async_copy(mask_ref.at[s, j], mbuf.at[slot],
                                       msem.at[slot])
            cp.start() if start else cp.wait()

        def page_dma(i, carry):
            cp = pltpu.make_async_copy(pages_ref.at[bt_ref[s, p0 + i]],
                                       buf.at[slot, i], sem.at[slot])
            cp.start() if start else cp.wait()
            return carry

        # the last block stops at the last live page
        jax.lax.fori_loop(0, jnp.minimum(kp, last - p0 + 1), page_dma, 0)

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(nblk > 0)
    def _first_block():
        block_dma(0, 0, True)

    def iota(shape, dim):
        return jax.lax.broadcasted_iota(jnp.int32, shape, dim)

    nt = (((1,), (1,)), ((), ()))             # [R, d] x [T, d] -> [R, T]

    def block(j, carry, masked):
        slot = jax.lax.rem(j, 2)

        @pl.when(j + 1 < nblk)
        def _next_block():
            block_dma(j + 1, 1 - slot, True)

        block_dma(j, slot, False)
        rows = buf[slot].reshape(T, W)
        if masked:
            # pages past the last live one hold what an earlier block
            # left there: zeroed whole, so that 0 x (whatever) adds nothing
            rows = jnp.where(j * T + iota((T, 1), 0) <= top, rows,
                             jnp.zeros_like(rows))
            valid = _valid_keys(j * T + iota((C, T), 1),
                                ctx + iota((C, T), 0), None)
        if chosen:
            # the choice is among the keys a query may see, so it is the
            # one mask of an edge block too
            valid = mbuf[slot] > 0.5 * NEG_INF
        rows = rows.astype(q_ref.dtype)
        latents, k_rope = rows[:, :rank], rows[:, rank:rank + dq - nope]

        def heads(i, carry):
            hs = [i * step + u for u in range(step)]
            # the expansion, rounded to the operands' dtype as the
            # cache-less forward's is
            kvs = [jax.lax.dot(latents, w_ref[h],
                               preferred_element_type=jnp.float32
                               ).astype(q_ref.dtype) for h in hs]  # [T, dkv]
            sqs = []
            for h, kv in zip(hs, kvs):
                q = q_ref[0, h]                                   # [C, dq]
                sq = (jax.lax.dot_general(
                    q[:, :nope], kv[:, :nope], nt,
                    preferred_element_type=jnp.float32)
                    + jax.lax.dot_general(
                        q[:, nope:], k_rope, nt,
                        preferred_element_type=jnp.float32)) * scale
                if masked or chosen:
                    sq = jnp.where(valid, sq, NEG_INF)
                sqs.append(sq)
            # key 0 is in block 0 and every row attends it, so a row's
            # running max is finite from the first block on and a masked
            # score's exponential is exactly 0 (under a choice key 0 may
            # be left out: what is not chosen is zeroed by name)
            for h, kv, sq in zip(hs, kvs, sqs):
                m_prev = m_scr[h]                                 # [C, 1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(sq, axis=-1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(sq - m_new)
                if chosen:
                    p = jnp.where(valid, p, 0.0)
                l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
                acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(
                    p.astype(kv.dtype), kv[:, nope:],
                    preferred_element_type=jnp.float32)
                m_scr[h] = m_new
            return carry

        return jax.lax.fori_loop(0, hg // step, heads, carry)

    jax.lax.fori_loop(0, whole, functools.partial(block, masked=False), 0)
    jax.lax.fori_loop(whole, nblk, functools.partial(block, masked=True), 0)

    def finish(h, carry):
        o_ref[0, h] = _softmax_finish(l_scr, acc_scr, h).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, hg, finish, 0)


# a jit of its own inside the engine's programs: the layers of a program
# call it with one set of shapes, so the kernel is traced and lowered once
# a program and not once a layer
@functools.partial(jax.jit,
                   static_argnames=("scale", "kp", "hg", "interpret"))
def _chunk_call(q_nope, q_rope, kv_up, pages, block_tables, context_lens,
                valid_lens, mask=None, *, scale, kp, hg, interpret):
    """``kp`` pages a block of latents, ``hg`` heads a grid step; ``mask``
    [S, blocks, C, kp * bs]: the walk under a choice of keys, launched
    as ``mla_attention_prefill_masked``."""
    S, C, nh, nope = q_nope.shape
    r, _, dkv = kv_up.shape
    dq, dv = nope + q_rope.shape[-1], dkv - nope
    bs, W = pages.shape[1:]
    # head-major operands: a head's queries [C, dq] and its slice of the
    # up-projection [r, dkv] are whole tiles of the kernel's blocks
    q = jnp.transpose(jnp.concatenate([q_nope, q_rope], axis=-1),
                      (0, 2, 1, 3))
    w = jnp.transpose(kv_up, (1, 0, 2)).astype(q.dtype)

    def head_map(s, g, bt_ref, cl_ref, vl_ref):
        return (s, g, 0, 0)

    chosen = mask is not None
    masks, mask_bufs, mask_sems = [], [], []
    if chosen:
        assert mask.shape == (S, -(-block_tables.shape[1] // kp), C,
                              kp * bs), (mask.shape, C, kp, bs)
        masks = [mask]
        mask_bufs = [pltpu.VMEM((2, C, kp * bs), jnp.float32)]
        mask_sems = [pltpu.SemaphoreType.DMA((2,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, nh // hg),
        in_specs=[
            pl.BlockSpec((1, hg, C, dq), head_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((hg, r, dkv), lambda s, g, *_: (g, 0, 0),
                         memory_space=pltpu.VMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(masks)),
        out_specs=pl.BlockSpec((1, hg, C, dv), head_map,
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((2, kp, bs, W), pages.dtype)] + mask_bufs
        + [pltpu.SemaphoreType.DMA((2,))] + mask_sems + [
            pltpu.VMEM((hg, C, 1), jnp.float32),
            pltpu.VMEM((hg, C, 1), jnp.float32),
            pltpu.VMEM((hg, C, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_chunk_body, scale=scale, nope=nope, chosen=chosen),
        name="mla_attention_prefill" if not chosen else (
            "mla_attention_prefill_masked"),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, C, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      valid_lens.astype(jnp.int32), q, w, *masks, pages)
    return jnp.transpose(out, (0, 2, 1, 3))


def latent_attention_prefill(q_nope, q_rope, kv_up, pages, block_tables,
                             context_lens, *, valid_lens=None,
                             softmax_scale: float, mask=None):
    """Ragged attention over a latent pool for one chunk a slot, in the
    EXPANDED form: ``q_nope`` [S, C, nh, dn] and ``q_rope`` [S, C, nh, dr]
    at positions ``context_lens[s] ..`` (the chunk's own rows already
    written), ``kv_up`` [r, nh, dn + dv] the up-projection, ``pages``
    [P, bs, W] rows of ``[latent r ; rotary key dr ; zeros]``.  Every live
    page is fetched once a head group and each head's keys ``[k_nope ;
    k_rope]`` and values exist in VMEM only; causal within the chunk on
    top of the paged history.  The kernel alone: there is no dense form
    of it (``PagedKVCache.attend_latent`` takes it where
    :func:`kernel_available`, and the absorbed reference elsewhere).
    ``mask`` (the selection over latents): fp32 ``[S, blocks, C,
    latent_pages_per_block * bs]``, above ``NEG_INF / 2`` where the
    query may attend the key; the walk then takes the mask's blocks
    (``valid_lens`` must be given: past the block of a slot's last live
    query the mask holds anything) and launches as
    ``mla_attention_prefill_masked``.
    Returns ``[S, C, nh, dv]`` in the queries' dtype."""
    assert q_nope.ndim == 4 and pages.ndim == 3, (q_nope.shape, pages.shape)
    assert _use_pallas()
    S, C, nh, nope = q_nope.shape
    if valid_lens is None:
        assert mask is None
        valid_lens = jnp.full_like(context_lens, C)
    r, _, dkv = kv_up.shape
    bs, W = pages.shape[1:]
    M = block_tables.shape[1]
    # under a mask the blocks are the mask's: the absorbed walk's
    kp = (latent_pages_per_block(bs, M) if mask is not None
          else max(1, min(M, _CHUNK_BLOCK_TOKENS // bs)))
    # and its two buffers stand beside the heads' in VMEM
    room = {} if mask is None else dict(
        mask_bytes=2 * C * _lanes(kp * bs) * 4)
    hg = _chunk_heads(nh, C, kp * bs, W, nope + q_rope.shape[-1], dkv,
                      dkv - nope, r, jnp.dtype(q_nope.dtype).itemsize,
                      **room)
    return _chunk_call(q_nope, q_rope, kv_up, pages, block_tables,
                       context_lens, valid_lens, mask, scale=softmax_scale,
                       kp=kp, hg=hg, interpret=_INTERPRET)
