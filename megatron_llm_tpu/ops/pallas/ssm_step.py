"""The decode step's state-space recurrence (step 4 of
``models/mamba.py``) as ONE kernel that updates the state pool in place
and moves live rows only (Pallas Mosaic TPU).

A decode step advances every live slot's state by one token: ``S = decay
S + (delta x) outer B``, ``y = S . C``, a head.  It does under one
operation a byte, so what it costs is the bytes of ``S``, and the least a
program can move is a live row's state read once and written once.  The
XLA step (:func:`dense_ssm_step` inside ``PagedKVCache.step_state``)
moves every slot's state, live or not, five times: a fusion reads every
row's (zeros selected for a fresh one) and writes the new state, which
two consumers want, beside ``y``; and the ``concatenate`` of a ``where``
that puts it back becomes a read of the new state, a read of the old
pool and a write of all ``slots + 1`` rows.

Shape contract (``ops/paged_kv.py``'s state group; row s is slot s):

* ``pool`` — ``[slots + 1, heads, d_head, d_state]`` float32, WHOLE: it
  goes in and comes out as the same buffer (``input_output_aliases``;
  the decode program owns its pools);
* ``decay`` — ``[b, heads]`` float32, each row's ``exp(delta A)``;
  ``dx`` — ``[b, heads, d_head]`` float32, ``delta x``;
* ``B``, ``C`` — ``[b, groups, d_state]`` float32 BY GROUP (a group's
  ``heads / groups`` heads share them; never repeated to heads in HBM);
* ``live``, ``fresh`` — ``[b]`` bool: the row has a token this step; its
  request starts here, so it starts from zeros whatever the slot held.

Returns ``y`` ``[b, heads, d_head]`` float32 (zeros at a row that is not
live) and the pool.

Kernel structure: ONE program instance that walks the LIVE rows (their
indices compacted in XLA and prefetched with their count), a row in
blocks of ``hb`` heads, ``hb`` the most whose state is
:data:`_BLOCK_BYTES` (2 MiB: a whole row of 64 heads, half a row of
128).  The pool stays in HBM; each (live row, head block) is one
``make_async_copy`` into one of three VMEM buffers, advanced where it
lies and copied back to where it came from, block k + 1 arriving and
block k - 1 leaving while block k is worked on.  **A row that is not live
moves no bytes**: it is in no copy's source or destination, so its slot,
a slot no row has and the garbage row come back bit for bit, and the time
follows the live rows wherever they sit among the idle ones.  (A grid
over every row whose idle steps stay on the block already resident moves
no bytes for them either, but loses the overlap at every live row that
follows an idle one: on a v5e, 64 rows of ``[64, 64, 128]`` took 477 us
all live, 261 with the first half live and 465 with every other row
live, where this walk takes 480 / 258 / 256; PERF.md section 6, PR 45.)

Inside a block the heads are unrolled: a head's state is ``[d_head,
d_state]`` (8 vregs at 64 x 128), ``B`` and ``C`` rows broadcast over
sublanes, ``decay`` a scalar from SMEM, and ``delta x`` a COLUMN, which
is why it is handed over transposed, ``[b, blocks, d_head, hb]`` (a head
a lane), and ``y`` comes back so: the sum over ``d_state`` runs along
lanes and leaves a column.  Both transposes are a few KB a row in XLA,
and those small operands and ``y`` are whole in VMEM (2-5 MB at 64
rows).  The arithmetic of an element of ``S`` is the XLA step's, in its
order (``decay * S + dx * B`` in float32: on the chip the two pools are
equal to the bit).  ``y`` sums the same float32 products ``S C`` on the
MXU, against a matrix of ones at ``highest`` precision (float32 in three
bf16 parts, accumulated in float32: within 2e-7 of the XLA step's
relative to the largest ``y``): the same sum by a lane reduction keeps
the vector units busy for longer than the block's copies take (on a v5e
477 us against 443 for 64 rows of ``[64, 64, 128]``, and 440 with no sum
at all).  The heads are unrolled WHOLE: a loop over groups of eight,
their columns rotated to fixed lanes, is an eighth of the code to trace,
lower and hold on the chip (a server's start is 1-2 s longer for the 64
unrolled, the program some 0.1-0.2 MB a layer larger) but takes 521 us
where this takes 443: the scheduler has no loop's edge to stop at
(PERF.md section 6, PR 45, has every reading).

Dispatch is ``ops/paged_kv.py``'s (``PagedKVCache.kernel``); interpret
mode in tests rides ``paged_attention._INTERPRET``, as every kernel of
the cache does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.pallas import paged_attention as _pa

# a block of heads' state, and the VMEM buffers a block goes through
_BLOCK_BYTES = 2 << 20
_BUFFERS = 3
_LANES = 128
# the buffers, the small operands and y whole, and room for Mosaic
_VMEM_LIMIT = 24 << 20


def dense_ssm_step(state, decay, dx, B, C):
    """The recurrence on rows: ``state`` [b, heads, d_head, d_state]
    float32 as each row finds it (``PagedKVCache.step_state`` reads it
    and puts the new one back), the other operands as the module
    docstring has them.  Returns ``y`` [b, heads, d_head] and the new
    state: the XLA path, and what the kernel's tests compare against."""
    per_group = decay.shape[1] // B.shape[1]
    Bh = jnp.repeat(B, per_group, axis=1)
    Ch = jnp.repeat(C, per_group, axis=1)
    new = decay[..., None, None] * state + dx[..., None] * Bh[:, :, None, :]
    return jnp.einsum("bhdn,bhn->bhd", new, Ch), new


def head_block(heads: int, d_head: int, d_state: int) -> int:
    """Heads a block: the most that divide ``heads`` and whose float32
    state is at most :data:`_BLOCK_BYTES`."""
    most = max(1, _BLOCK_BYTES // (d_head * d_state * 4))
    return max(hb for hb in range(1, heads + 1)
               if heads % hb == 0 and hb <= most)


def _body(rows_ref, fresh_ref, n_ref, decay_ref, dxt_ref, b_ref, c_ref,
          pool_ref, yt_ref, out_ref, buf, sem_in, sem_out, *, hb, nb,
          per_group):
    """``rows_ref`` [b]: the live rows' indices first; ``n_ref`` [1]:
    how many.  ``pool_ref`` and ``out_ref`` are the one pool in HBM."""
    n = n_ref[0] * nb                   # blocks: (live row, head block)
    # y's sum over d_state on the MXU, every lane of the result the sum
    ones = jnp.ones((buf.shape[-1], _LANES), jnp.float32)
    # a row that is not live has no block: its y is zeros
    yt_ref[...] = jnp.zeros_like(yt_ref)

    def where(k):
        return rows_ref[k // nb], k % nb

    def fetch(k):
        row, j = where(k)
        return pltpu.make_async_copy(
            pool_ref.at[row, pl.ds(j * hb, hb)], buf.at[k % _BUFFERS],
            sem_in.at[k % _BUFFERS])

    def put_back(k):
        row, j = where(k)
        return pltpu.make_async_copy(
            buf.at[k % _BUFFERS], out_ref.at[row, pl.ds(j * hb, hb)],
            sem_out.at[k % _BUFFERS])

    @pl.when(n > 0)
    def _first():
        fetch(0).start()

    def block(k, carry):
        # block k + 1 arrives in the buffer block k - 2 has left
        @pl.when(k >= 2)
        def _left():
            put_back(k - 2).wait()

        @pl.when(k + 1 < n)
        def _next():
            fetch(k + 1).start()

        fetch(k).wait()
        row, j = where(k)
        fresh = fresh_ref[row] > 0
        state = buf.at[k % _BUFFERS]
        for h in range(hb):
            head = j * hb + h
            grp = head // per_group
            lane = h % _LANES
            S = jnp.where(fresh, 0.0, state[h])             # [dh, ds]
            S = (decay_ref[row, head] * S
                 + dxt_ref[row, j, :, h:h + 1] * b_ref[row, pl.ds(grp, 1), :])
            state[h] = S
            yt_ref[row, j, :, h:h + 1] = jnp.dot(
                S * c_ref[row, pl.ds(grp, 1), :], ones,
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)[:, lane:lane + 1]
        put_back(k).start()
        return carry

    jax.lax.fori_loop(0, n, block, 0)
    for last in (2, 1):
        @pl.when(n >= last)
        def _drain():
            put_back(n - last).wait()


def ssm_state_step(pool, decay, dx, B, C, live, fresh):
    """One token of every live row's recurrence, the pool updated in
    place (module docstring).  Returns ``y`` and the pool."""
    return _step(pool, decay, dx, B, C, live, fresh,
                 interpret=_pa._INTERPRET)


# jitted so that a program's state-space layers, which call it at one
# set of shapes, trace and lower the unrolled heads ONCE between them (a
# server's start traces and lowers before it can ask the compile cache)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(pool, decay, dx, B, C, live, fresh, *, interpret):
    b, nh, dh = dx.shape
    g, ds = B.shape[1], B.shape[2]
    hb = head_block(nh, dh, ds)
    nb = nh // hb
    f32 = jnp.float32
    # the live rows' indices first, in their order
    rows = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    # a head a lane: [b, blocks, d_head, hb]
    dxt = dx.astype(f32).reshape(b, nb, hb, dh).swapaxes(2, 3)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    yt, pool = pl.pallas_call(
        functools.partial(_body, hb=hb, nb=nb, per_group=nh // g),
        name="ssm_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[whole, whole, whole, in_hbm],
            out_specs=[whole, in_hbm],
            scratch_shapes=[pltpu.VMEM((_BUFFERS, hb, dh, ds), pool.dtype),
                            pltpu.SemaphoreType.DMA((_BUFFERS,)),
                            pltpu.SemaphoreType.DMA((_BUFFERS,))]),
        out_shape=[jax.ShapeDtypeStruct(dxt.shape, f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 7 (after the four prefetched scalars and three small
        # operands) is the pool; output 1 is the pool
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(rows, fresh.astype(jnp.int32),
      live.sum(dtype=jnp.int32).reshape(1), decay.astype(f32), dxt,
      B.astype(f32), C.astype(f32), pool)
    return yt.swapaxes(2, 3).reshape(b, nh, dh), pool
