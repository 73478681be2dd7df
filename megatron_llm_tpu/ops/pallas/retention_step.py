"""The decode step's power-retention recurrence (``models/retention.py``)
as ONE kernel that updates the state pool in place and moves live rows
only (Pallas Mosaic TPU); a sibling of ``ssm_step.py``.

A decode step advances every live slot's state by one token, a key-value
head: ``S = exp(a) S + phi(k) v^T``, and reads it for the head's ``r``
queries, ``num_h = phi(q_h)^T S``.  It does some two operations an
element of ``S`` a query head, so what it costs is the bytes of ``S``
(4.26 MB a key-value head at a head size of 128, 34 MB a layer a row),
and the least a program can move is a live row's state read once and
written once.  The XLA step (:func:`dense_retention_step` inside
``PagedKVCache.step_retention``) moves every slot's state, live or not,
several times, and holds it twice.

``phi`` and its layout (``models/retention.py`` has the algebra):
``phi(x)[o, a] = c_o x[a] x[(a + o) mod d]`` over ``d / 2 + 1``
ROTATIONS ``o``, so a rotation's row of ``phi`` is the vector times a
lane-rotation of itself, formed in VMEM from the ``d`` values of ``k``
(or of a query) and never fetched.  The state of a key-value head is
``[rotations, d (value), d (a)]``: a ``[value, a]`` tile a rotation.

Shape contract (``ops/paged_kv.py``'s state group; row s is slot s):

* ``pool`` — ``[slots + 1, kv_heads, rotations, d, d]`` float32, WHOLE:
  it goes in and comes out as the same buffer (``input_output_aliases``;
  the decode program owns its pools);
* ``q`` — ``[b, kv_heads, r, d]``, ``k``, ``v`` — ``[b, kv_heads, d]``,
  ``a`` — ``[b, kv_heads]`` float32 log-gates;
* ``live``, ``fresh`` — ``[b]`` bool: the row has a token this step; its
  request starts here, so it starts from zeros whatever the slot held.

Returns the numerators ``[b, kv_heads, r, d]`` float32 (zeros at a row
that is not live) and the pool.  The normaliser ``z`` (a 128th of the
state's bytes) is XLA's (:func:`dense_sum_step`): rows read, advanced and
put back.

Kernel structure, ``ssm_step.py``'s: ONE program instance that walks the
LIVE rows (their indices compacted in XLA and prefetched with their
count), a (row, key-value head) in blocks of ``ob`` rotations, ``ob`` the
most that divide the rotations and whose tiles are :data:`_BLOCK_BYTES`
(13 of 65 at ``d`` 128: 832 KiB).  The pool stays in HBM; each block is
one ``make_async_copy`` into one of three VMEM buffers, advanced where it
lies and copied back to where it came from, block k + 1 arriving and
block k - 1 leaving while block k is worked on.  **A row that is not
live moves no bytes.**  The small operands of a (row, head) are ONE tile
``[8, d]``: the head's ``r`` queries, ``k``, ``v`` and ``exp(a)`` a row
each.  At a head's first block the tile is rotated ``rotations`` times
(static lane rotations, ``c_o`` folded in) into a table in VMEM; a
rotation's ``phi`` of all the rows is then ONE product of the tile with
its table entry.  ``v`` is laid along sublanes by one transpose of its
broadcast; the numerators accumulate as ``[value, a]`` tiles a query
head (their sum over ``a`` is taken ONCE, at the head's last block, by a
transpose and a sublane sum).  Everything is float32 on the vector
units: the state is never rounded.

Dispatch is ``ops/paged_kv.py``'s (``PagedKVCache.kernel``); interpret
mode in tests rides ``paged_attention._INTERPRET``, as every kernel of
the cache does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.pallas import paged_attention as _pa

# a block of rotations' tiles, and the VMEM buffers a block goes through
_BLOCK_BYTES = 1 << 20
_BUFFERS = 3
_SUBLANES = 8
# the buffers, the table, the accumulators, the small operands and the
# numerators whole, and room for Mosaic
_VMEM_LIMIT = 32 << 20


def rotations(d: int) -> int:
    """Rotations of ``phi`` at head size ``d``."""
    return d // 2 + 1


def phi_weights(d: int) -> tuple:
    """``c_o``, a Python float a rotation."""
    return tuple(1.0 if o in (0, d // 2) else math.sqrt(2.0)
                 for o in range(rotations(d)))


def phi(x: jax.Array) -> jax.Array:
    """``x`` [..., d] -> ``phi(x)`` [..., rotations, d] in float32, with
    ``phi(x) . phi(y) == (x . y)^2``; a product is ``x[a] * (c_o x[(a +
    o) mod d])``, the kernel's."""
    x = x.astype(jnp.float32)
    d = x.shape[-1]
    table = jnp.stack([c * jnp.roll(x, -o, axis=-1)
                       for o, c in enumerate(phi_weights(d))], axis=-2)
    return x[..., None, :] * table


def dense_sum_step(z, q, k, a):
    """The normaliser's step on rows: ``z`` [b, g, O, d] float32 as each
    row finds it.  Returns ``phi(q) . z_new`` [b, g, r] and ``z_new``."""
    z = jnp.exp(a)[..., None, None] * z + phi(k)
    return jnp.einsum("bgroa,bgoa->bgr", phi(q), z,
                      precision=jax.lax.Precision.HIGHEST), z


def dense_retention_step(S, z, q, k, v, a):
    """The recurrence on rows: ``S`` [b, g, O, d, d] and ``z`` [b, g, O,
    d] float32 as each row finds them (``PagedKVCache.step_retention``
    reads them and puts the new ones back), the other operands as the
    module docstring has them.  Returns numerators [b, g, r, d],
    normalisers [b, g, r] and the new ``S`` and ``z``: the XLA path, and
    what the kernel's tests compare against."""
    v = v.astype(jnp.float32)
    S = (jnp.exp(a)[..., None, None, None] * S
         + v[..., None, :, None] * phi(k)[..., :, None, :])
    num = jnp.einsum("bgroa,bgoda->bgrd", phi(q), S,
                     precision=jax.lax.Precision.HIGHEST)
    den, z = dense_sum_step(z, q, k, a)
    return num, den, S, z


def rotation_block(O: int, d: int) -> int:
    """Rotations a block: the most that divide ``O`` and whose float32
    tiles are at most :data:`_BLOCK_BYTES`."""
    most = max(1, _BLOCK_BYTES // (d * d * 4))
    return max(ob for ob in range(1, O + 1) if O % ob == 0 and ob <= most)


def _body(rows_ref, fresh_ref, n_ref, ops_ref, pool_ref, y_ref, out_ref,
          buf, tab, acc, sem_in, sem_out, *, ob, nb, G, r, d):
    """``rows_ref`` [b]: the live rows' indices first; ``n_ref`` [1]:
    how many.  ``pool_ref`` and ``out_ref`` are the one pool in HBM."""
    O = ob * nb
    weights = phi_weights(d)
    n = n_ref[0] * (G * nb)         # blocks: (live row, head, rotations)
    # a row that is not live has no block: its numerators are zeros
    y_ref[...] = jnp.zeros_like(y_ref)

    def where(k):
        pair = k // nb
        return rows_ref[pair // G], pair % G, k % nb

    def fetch(k):
        row, g, j = where(k)
        return pltpu.make_async_copy(
            pool_ref.at[row, g, pl.ds(j * ob, ob)], buf.at[k % _BUFFERS],
            sem_in.at[k % _BUFFERS])

    def put_back(k):
        row, g, j = where(k)
        return pltpu.make_async_copy(
            buf.at[k % _BUFFERS], out_ref.at[row, g, pl.ds(j * ob, ob)],
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
        row, g, j = where(k)
        x = ops_ref[row, g]                     # [8.., d]: q.., k, v, decay

        @pl.when(j == 0)
        def _head():
            # the tile rotated once a rotation, c_o folded in: phi of
            # every row at rotation o is x * tab[o]
            for o in range(O):
                tab[o] = weights[o] * pltpu.roll(x, (d - o) % d, axis=1)
            acc[...] = jnp.zeros_like(acc)

        fresh = fresh_ref[row] > 0
        decay = x[r + 2:r + 3, :]                           # [1, d]
        # v along sublanes: [value, a], a value's own in every lane
        v_col = jnp.broadcast_to(x[r + 1:r + 2, :], (d, d)).T
        state = buf.at[k % _BUFFERS]
        for t in range(ob):
            ph = x * tab[j * ob + t]                        # [8.., d]
            S = jnp.where(fresh, 0.0, state[t])             # [value, a]
            S = decay * S + v_col * ph[r:r + 1, :]
            state[t] = S
            for h in range(r):
                acc[h] += S * ph[h:h + 1, :]
        put_back(k).start()

        @pl.when(j == nb - 1)
        def _sum():
            for h in range(r):
                y_ref[row, g, h:h + 1, :] = jnp.sum(
                    acc[h].T, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, n, block, 0)
    for last in (2, 1):
        @pl.when(n >= last)
        def _drain():
            put_back(n - last).wait()


def retention_state_step(pool, q, k, v, a, live, fresh):
    """One token of every live row's recurrence, the pool updated in
    place (module docstring).  Returns the numerators and the pool."""
    return _step(pool, q, k, v, a, live, fresh, interpret=_pa._INTERPRET)


# jitted so that a program's retention layers, which call it at one set
# of shapes, trace and lower the unrolled rotations ONCE between them
@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(pool, q, k, v, a, live, fresh, *, interpret):
    b, G, r, d = q.shape
    O = pool.shape[2]
    ob = rotation_block(O, d)
    nb = O // ob
    f32 = jnp.float32
    # the live rows' indices first, in their order
    rows = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    # a (row, head)'s small operands, a row each: q.., k, v, exp(a)
    tile = -(-(r + 3) // _SUBLANES) * _SUBLANES
    ops = jnp.concatenate([
        q.astype(f32), k.astype(f32)[:, :, None], v.astype(f32)[:, :, None],
        jnp.broadcast_to(jnp.exp(a.astype(f32))[..., None, None],
                         (b, G, 1, d)),
        jnp.zeros((b, G, tile - r - 3, d), f32)], axis=2)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, pool = pl.pallas_call(
        functools.partial(_body, ob=ob, nb=nb, G=G, r=r, d=d),
        name="retention_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole, in_hbm],
            out_specs=[whole, in_hbm],
            scratch_shapes=[pltpu.VMEM((_BUFFERS, ob, d, d), pool.dtype),
                            pltpu.VMEM((O, tile, d), f32),
                            pltpu.VMEM((r, d, d), f32),
                            pltpu.SemaphoreType.DMA((_BUFFERS,)),
                            pltpu.SemaphoreType.DMA((_BUFFERS,))]),
        out_shape=[jax.ShapeDtypeStruct(ops.shape, f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 4 (after the three prefetched scalars and the small
        # operands) is the pool; output 1 is the pool
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(rows, fresh.astype(jnp.int32),
      live.sum(dtype=jnp.int32).reshape(1), ops, pool)
    return y[:, :, :r], pool
