"""The decode step's gated delta-rule recurrence
(``models/gated_delta.py``) as ONE kernel that updates the state pool in
place and moves live rows only (Pallas Mosaic TPU); a sibling of
``ssm_step.py`` and ``retention_step.py``.

A decode step advances every live slot's state by one token, a value
head: with ``S`` ``[keys, values]`` float32, the head's key ``k`` and
query ``q`` (its key head's), its value ``v``, its decay ``exp(g)`` and
its write strength ``beta``,

    S' = exp(g) S          d = beta (v - S'^T k)
    S  = S' + k d^T        o = S^T q

``d`` READS the state before it is written: what the state already
answers for ``k`` is taken off.  Some six operations an element of ``S``,
so what it costs is the bytes of ``S`` (64 KiB a head at 128 x 128, 2 MiB
a layer a row at 32 heads), and the least a program can move is a live
row's state read once and written once.  The XLA step
(:func:`dense_gated_delta_step` inside ``PagedKVCache.step_delta``) moves
every slot's state, live or not, and holds it twice.

THE WALKER (:func:`walk_live_rows`) is what ``ssm_step.py`` and
``retention_step.py`` each wrote for themselves, written once as a
function of the per-block update (ROADMAP D22; those two keep their own
until a PR that means to change their programs moves them): ONE program
instance that walks the LIVE rows (their indices compacted in XLA and
prefetched with their count), a (row, block of heads) at a time.  The
pool stays in HBM and goes in and comes out as the same buffer
(``input_output_aliases``; the decode program owns its pools); each
block is one ``make_async_copy`` into one of three VMEM buffers,
advanced where it lies by ``update`` and copied back to where it came
from, block k + 1 arriving and block k - 1 leaving while block k is
worked on.  **A row that is not live moves no bytes.**  The small
operands and the outputs are whole in VMEM (an output starts as zeros: a
row that is not live has no block), per-(row, head) scalars are
prefetched to SMEM.

Shape contract of :func:`delta_state_step` (``ops/paged_kv.py``'s state
group; row s is slot s):

* ``pool`` -- ``[slots + 1, value_heads, d_key, d_value]`` float32, WHOLE;
* ``q``, ``k`` -- ``[b, key_heads, d_key]`` (key head j serves value
  heads ``j r .. j r + r - 1``), ``v`` -- ``[b, value_heads, d_value]``;
* ``g`` (log decay, <= 0), ``beta`` -- ``[b, value_heads]`` float32;
* ``live``, ``fresh`` -- ``[b]`` bool: the row has a token this step;
  its request starts here, so it starts from zeros whatever the slot
  held.

Returns ``o`` ``[b, value_heads, d_value]`` float32 (zeros at a row that
is not live) and the pool.  Everything is float32 on the vector units:
the state is never rounded, and the two reductions over the keys are
sublane sums (no MXU pass rounds them).  ``q`` and ``k`` go in with a
value head's copy of its key head's vector a LANE of its block (``[b,
blocks, d_key, heads a block]``) so that a head's vector is a column,
broadcast along the state's lanes.

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
# the buffers, the small operands and the outputs whole, and room for
# Mosaic
_VMEM_LIMIT = 32 << 20


def for_value_heads(x, r: int, axis: int = 1):
    """A key head's ``x`` for each of the ``r`` value heads it serves,
    along ``axis``: key head j serves value heads ``j r .. j r + r - 1``.
    The one place the step, its kernel and the chunk ask."""
    return jnp.repeat(x, r, axis=axis)


def dense_gated_delta_step(S, q, k, v, g, beta):
    """The recurrence on rows: ``S`` [b, value_heads, d_key, d_value]
    float32 as each row finds it (``PagedKVCache.step_delta`` reads it
    and puts the new one back), the other operands as the module
    docstring has them.  Returns ``o`` [b, value_heads, d_value] and the
    new state: the XLA path, and what the kernel's tests compare
    against."""
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    r = S.shape[1] // q.shape[1]
    q = for_value_heads(q.astype(f32), r)
    k = for_value_heads(k.astype(f32), r)
    S = jnp.exp(g)[..., None, None] * S
    d = beta[..., None] * (v.astype(f32) - jnp.einsum(
        "bhkv,bhk->bhv", S, k, precision=hi))
    S = S + k[..., :, None] * d[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", S, q, precision=hi), S


def head_block(heads: int, head_bytes: int) -> int:
    """Heads a block: the most that divide ``heads`` and whose state is
    at most :data:`_BLOCK_BYTES`."""
    most = max(1, _BLOCK_BYTES // head_bytes)
    return max(hb for hb in range(1, heads + 1)
               if heads % hb == 0 and hb <= most)


def walk_live_rows(update, pool, live, fresh, *, name: str, scalars=(),
                   operands=(), outs=(), interpret: bool = False):
    """``pool`` [rows, heads, ...] advanced in place at its LIVE rows by
    ``update``, a (row, block of heads) at a time (module docstring).

    ``update(row, j, is_fresh, state, scalar_refs, operand_refs,
    out_refs)`` advances ``state``, a VMEM ref ``[hb, ...]`` holding heads
    ``j * hb ..`` of row ``row`` (whatever the slot held where
    ``is_fresh``: the update starts such a row from zeros), where it
    lies, and writes the row's part of each output.  ``scalars``: arrays
    prefetched to SMEM; ``operands``: arrays whole in VMEM; ``outs``:
    ``jax.ShapeDtypeStruct``s of outputs whole in VMEM, zeros where no
    block wrote.  Returns (the outputs, the pool)."""
    heads = pool.shape[1]
    hb = head_block(heads, pool[0, 0].size * pool.dtype.itemsize)
    nb = heads // hb
    n_sc, n_op, n_out = len(scalars), len(operands), len(outs)

    def body(*refs):
        rows_ref, fresh_ref, n_ref = refs[:3]
        scalar_refs = refs[3:3 + n_sc]
        operand_refs = refs[3 + n_sc:3 + n_sc + n_op]
        pool_ref = refs[3 + n_sc + n_op]
        out_refs = refs[4 + n_sc + n_op:4 + n_sc + n_op + n_out]
        out_pool = refs[4 + n_sc + n_op + n_out]
        buf, sem_in, sem_out = refs[5 + n_sc + n_op + n_out:]
        n = n_ref[0] * nb               # blocks: (live row, head block)
        for o in out_refs:
            o[...] = jnp.zeros_like(o)

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
                buf.at[k % _BUFFERS], out_pool.at[row, pl.ds(j * hb, hb)],
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
            update(row, j, fresh_ref[row] > 0, buf.at[k % _BUFFERS],
                   scalar_refs, operand_refs, out_refs)
            put_back(k).start()
            return carry

        jax.lax.fori_loop(0, n, block, 0)
        for last in (2, 1):
            @pl.when(n >= last)
            def _drain():
                put_back(n - last).wait()

    # the live rows' indices first, in their order
    rows = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    *results, pool = pl.pallas_call(
        body, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + n_sc,
            grid=(1,),
            in_specs=[whole] * n_op + [in_hbm],
            out_specs=[whole] * n_out + [in_hbm],
            scratch_shapes=[pltpu.VMEM((_BUFFERS, hb) + pool.shape[2:],
                                       pool.dtype),
                            pltpu.SemaphoreType.DMA((_BUFFERS,)),
                            pltpu.SemaphoreType.DMA((_BUFFERS,))]),
        out_shape=list(outs) + [jax.ShapeDtypeStruct(pool.shape,
                                                     pool.dtype)],
        # the pool is the last operand and the last output
        input_output_aliases={3 + n_sc + n_op: n_out},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(rows, fresh.astype(jnp.int32), live.sum(dtype=jnp.int32).reshape(1),
      *scalars, *operands, pool)
    return results, pool


def _update(row, j, is_fresh, state, scalar_refs, operand_refs, out_refs):
    """The delta rule on one block of a row's value heads (module
    docstring), each head's ``[d_key, d_value]`` tile where it lies.  A
    head's place in its block is static, so every slice but the row's
    and the block's is."""
    decay_ref, beta_ref = scalar_refs
    qt_ref, kt_ref, v_ref = operand_refs
    o_ref, = out_refs
    hb = state.shape[0]
    for h in range(hb):
        head = j * hb + h
        k = kt_ref[row, j, :, h:h + 1]                      # [d_key, 1]
        q = qt_ref[row, j, :, h:h + 1]
        S = decay_ref[row, head] * jnp.where(
            is_fresh, 0.0, state[h].astype(jnp.float32))
        d = beta_ref[row, head] * (
            v_ref[row, j, h:h + 1, :]
            - jnp.sum(S * k, axis=0, keepdims=True))        # [1, d_value]
        S = S + k * d
        state[h] = S.astype(state.dtype)
        o_ref[row, j, h:h + 1, :] = jnp.sum(S * q, axis=0, keepdims=True)


def delta_state_step(pool, q, k, v, g, beta, live, fresh):
    """One token of every live row's recurrence, the pool updated in
    place (module docstring).  Returns ``o`` and the pool."""
    return _step(pool, q, k, v, g, beta, live, fresh,
                 interpret=_pa._INTERPRET)


# jitted so that a program's delta-rule layers, which call it at one set
# of shapes, trace and lower the unrolled heads ONCE between them
@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(pool, q, k, v, g, beta, live, fresh, *, interpret):
    f32 = jnp.float32
    b, hv, dv = v.shape
    r = hv // q.shape[1]
    hb = head_block(hv, pool[0, 0].size * pool.dtype.itemsize)
    nb = hv // hb

    def a_lane(x):
        """A value head's copy of its key head's vector a LANE of its
        block: [b, blocks, d_key, hb]."""
        x = for_value_heads(x.astype(f32), r)
        return x.reshape(b, nb, hb, x.shape[-1]).swapaxes(2, 3)

    (o,), pool = walk_live_rows(
        _update, pool, live, fresh, name="delta_state_step",
        scalars=(jnp.exp(g.astype(f32)), beta.astype(f32)),
        operands=(a_lane(q), a_lane(k),
                  v.astype(f32).reshape(b, nb, hb, dv)),
        outs=(jax.ShapeDtypeStruct((b, nb, hb, dv), f32),),
        interpret=interpret)
    return o.reshape(b, hv, dv), pool
