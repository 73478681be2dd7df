"""A prefill chunk's power retention (``models/retention.py``) as ONE
kernel that forms ``phi`` in VMEM and walks a chunk's blocks in order
over a key-value head's state held there (Pallas Mosaic TPU); a sibling
of ``retention_step.py``.

The algebra is ``retention.retention_chunk``'s, block for block and
rounding for rounding: inside a block of :data:`BLOCK` rows the quadratic
form ``(q k^T)^2`` under the causal mask and the gates' decay, across
blocks ``phi(q)^T S`` and ``S <- exp(A_end) S + sum_j exp(A_end - A_j)
phi(k_j) v_j^T``.  What XLA's form pays for is ``phi`` itself: a block's
``phi(q)`` is ``[128, 40, 65, 128]``, 85 MB in bf16, written to HBM a
rotation at a time and read back, four blocks a chunk a layer, and the
state is copied into another layout and back (51 of a chunk's 73 ms at
Brumby's widths against 2.2 ms of operations; chip runs, PR 54).  Here a
rotation's row of ``phi`` is the tile times a lane-rotation of itself
(``pltpu.roll``, as the step's kernel forms its table), rounded once to
the compute dtype and handed to the MXU from VMEM: NO ``phi`` OF ANYTHING
IS WRITTEN TO HBM, and a head's state comes in once a layer a chunk and
goes back once.

Shape contract (``ops/paged_kv.py``'s state group):

* ``state`` — ``[slots + 1, kv_heads, rotations, d, d]`` float32, WHOLE:
  it goes in and comes out as the same buffer (``input_output_aliases``;
  a chunk owns its pool where no layer keeps pages);
* ``sums`` — ``[b, kv_heads, rotations, d]`` float32, the normaliser AS
  EACH ROW FINDS IT (a 128th of the state's bytes: the cache reads the
  rows and puts the new ones back, ``PagedKVCache.chunk_retention``, as
  it does around the step's kernel);
* ``q`` — ``[b, n, kv_heads, r, d]``, ``k``, ``v`` — ``[b, n, kv_heads,
  d]`` in the compute dtype, ``a`` — ``[b, n, kv_heads]`` float32
  log-gates;
* ``slots``, ``valid``, ``fresh`` — ``[b]``: the row's slot, its real
  tokens (the tokens past them neither decay nor add: ``k`` and ``a`` are
  zeroed here, as the mixer does for XLA's form), and whether its request
  starts here, so that it starts from zeros whatever the slot held.

Returns numerators ``[b, n, kv_heads, r, d]`` and normalisers ``[b, n,
kv_heads, r]`` in float32 (zeros in a block with no real token), the
pool and the rows' new sums.  A row with no token reads its state and
puts it back as it was; its slot is its own, not the garbage row.

Kernel structure: a grid of (row, key-value head, block), the blocks
innermost and in order.  The state's block is the slot's ``[rotations,
d, d]`` by a prefetched index, so the pipeline brings head g + 1's 4.26
MB in while head g is worked on and writes head g - 1's back; the
output's buffer IS the state for the chunk's blocks (copied from the
input, or zeroed, at the first), and the sums' likewise.  A
block's ``r`` query heads lie one under the other as ``[r x BLOCK, d]``
rows in float32 once a distinct weight ``c_o`` of the layout
(``retention_step.phi_weights``: 1 and ``sqrt 2``; ``c_o`` folded in, a
rotation reads the one or the other by a prefetched table).  The
rotations are walked in GROUPS of ``U`` (:func:`rotation_group`): for
each, ``phi(q)`` of the group is formed a row tile of :data:`_ROW_TILE`
at a time (the tile, its rotation and the normaliser's partial sum in
registers; one rotation, one product, one rounding and one store of
bf16 a vreg), laid side by side as ``[r x BLOCK, U x d]``, and ONE
product against the group's tiles of ``S`` as they lie, side by side too
(``NT``, the contraction ``U x d`` long, so the MXU sums over the
group's rotations itself and the float32 numerators are read and written
once a group).  Then the group's update, a rotation at a time:
``phi(k)`` under ``exp(A_end - A_j)``, rounded once, ``v^T phi(k)`` on
the MXU, ``S`` and ``z`` advanced where they lie in float32.  A block
with no real token is skipped.

Tile choices, measured with the kernel alone on a TPU v5e at Brumby's
widths (one row of 512 tokens from a carried state, 8 key-value heads of
5 x 128, bf16, a loop of 8 layers' calls over one pool, six timings
each; chip run, PR 55).  Milliseconds a layer, least to most:

    retention_chunk (XLA, compiled alone)      6.089 - 6.119
    groups of 13, row tiles of 128 (these)     0.651 - 0.680
    groups of 13, row tiles of 64              0.666 - 0.697
    groups of 13, row tiles of 320             0.652 - 0.684
    groups of 13, row tiles of 640             0.666 - 0.696
    groups of  5, row tiles of 128             0.716 - 0.755
    a rotation at a time, row tiles of 128     1.067 - 1.102

0.65 ms is 20 us a head's block, 290 cycles a rotation where the MXU's
products alone are some 190: the group matters (the numerators'
accumulator is read and written once a group), the row tile hardly.
The state and the sums the kernel leaves are XLA's bit for bit on the
chip (float32 sums of the same rounded products in the same order), the
outputs 2e-4 of theirs apart (the MXU sums a group's rotations in
another order), both 1e-3 from float32's.  VMEM: the state's block in
and out twice each, 17 MB, and 4 MB of tiles, under :data:`_VMEM_LIMIT`.

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
from megatron_llm_tpu.ops.pallas import retention_step as _step

# rows of a block (``models/retention.py::BLOCK`` is this one): what the
# configuration file states as ``assumed.chunk_block``
BLOCK = 128
# rotations a product against the state sums over, at most
_GROUP = 13
# rows of phi(q) formed at a time
_ROW_TILE = 128
# the state's block in and out, each twice (the pipeline's: 17 MB at a
# head size of 128), the block's tiles, and room for Mosaic: the step's
_VMEM_LIMIT = 32 << 20


def rotation_group(O: int) -> int:
    """Rotations a group: the most that divide ``O``, at most
    :data:`_GROUP` (13 of 65 at a head size of 128)."""
    return max(u for u in range(1, min(O, _GROUP) + 1) if O % u == 0)


def _body(slots_ref, fresh_ref, valid_ref, level_ref, q_ref, k_ref, v_ref,
          acol_ref, arow_ref, s_in, z_in, num_ref, den_ref, s_ref, z_ref,
          q2, k2, ph, sb, accn, accd, *, Q, r, d, U, tm, levels, cdtype):
    """One block of one (row, key-value head).  ``s_ref`` / ``z_ref``:
    the slot's state and sums, the same buffers for every block of the
    pair.  ``level_ref`` [rotations]: which of ``levels``, the distinct
    ``c_o``, a rotation is weighted by."""
    f32 = jnp.float32
    i, c = pl.program_id(0), pl.program_id(2)

    def weighted(x):
        """``x`` times each of ``levels``."""
        return [x if w == 1.0 else w * x for w in levels]

    @pl.when(c == 0)
    def _arrive():
        # a row with no token keeps what its slot holds, fresh or not
        kept = jnp.logical_or(fresh_ref[i] == 0, valid_ref[i] == 0)
        s_ref[...] = jnp.where(kept, s_in[...], 0.0)
        z_ref[...] = z_in[...]

    live = c * Q < valid_ref[i]

    @pl.when(jnp.logical_not(live))
    def _skip():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    @pl.when(live)
    def _block():
        k = k_ref[...]                                      # [Q, d]
        v = v_ref[...]
        A_t, A_s = acol_ref[...], arow_ref[...]             # [Q, 1], [1, Q]
        # inside the block: (q . k)^2 under the mask and the decay
        t_id = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        s_id = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        causal = s_id <= t_id
        decay = jnp.where(causal,
                          jnp.exp(jnp.where(causal, A_t - A_s, 0.0)), 0.0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (Q, d), 1)
        den = jnp.zeros((Q, d), f32)        # head h's in lane h
        for h in range(r):
            qh = q_ref[:, h * d:(h + 1) * d]
            qk = jax.lax.dot_general(qh, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=f32)
            w = (jnp.square(qk) * decay).astype(cdtype)
            accn[h * Q:(h + 1) * Q, :] = jnp.dot(
                w, v, preferred_element_type=f32)
            den = jnp.where(lane == h,
                            w.astype(f32).sum(axis=1, keepdims=True), den)
            # the heads one under the other, under each weight c_o
            for lv, x in enumerate(weighted(qh.astype(f32))):
                q2[lv, h * Q:(h + 1) * Q, :] = x
        kf = k.astype(f32)
        for lv, x in enumerate(weighted(kf)):
            k2[lv] = x
        A_end = A_s[:, Q - 1:Q]                             # [1, 1]
        ke = kf * jnp.exp(A_end - A_t)                      # [Q, d]
        kept = jnp.exp(A_end)
        vT = v.astype(f32).T.astype(cdtype)                 # [d (value), Q]
        accd[...] = jnp.zeros_like(accd)
        # exp(A_t) of a token, and once a query head
        before = jnp.exp(A_t)                               # [Q, 1]
        before_rows = jnp.concatenate([before] * r, axis=0)   # [r Q, 1]

        def group(j, carry):
            first = j * U
            # across blocks: phi(q) of the group's rotations, side by
            # side, a row tile at a time
            for lo in range(0, r * Q, tm):
                rows = slice(lo, min(lo + tm, r * Q))
                x = q2[levels.index(1.0), rows, :]
                part = jnp.zeros_like(x)
                for u in range(U):
                    o = first + u
                    turned = pltpu.roll(q2[level_ref[o], rows, :],
                                        (d - o) % d, axis=1)
                    p = (x * turned).astype(cdtype)
                    ph[rows, u * d:(u + 1) * d] = p
                    part = part + p.astype(f32) * z_ref[
                        pl.ds(o, 1), :].astype(cdtype).astype(f32)
                accd[rows, :] += part
            for u in range(U):
                sb[:, u * d:(u + 1) * d] = s_ref[first + u].astype(cdtype)
            accn[...] += before_rows * jax.lax.dot_general(
                ph[...], sb[...], (((1,), (1,)), ((), ())),
                preferred_element_type=f32)
            # the state at the block's end
            for u in range(U):
                o = first + u
                turned = pltpu.roll(k2[level_ref[o]], (d - o) % d, axis=1)
                pk = (ke * turned).astype(cdtype)           # [Q, d]
                s_ref[o] = kept * s_ref[o] + jnp.dot(
                    vT, pk, preferred_element_type=f32)
                z_ref[pl.ds(o, 1), :] = (
                    kept * z_ref[pl.ds(o, 1), :]
                    + pk.astype(f32).sum(axis=0, keepdims=True))
            return carry

        jax.lax.fori_loop(0, s_ref.shape[0] // U, group, 0)
        for h in range(r):
            rows = slice(h * Q, (h + 1) * Q)
            num_ref[:, h * d:(h + 1) * d] = accn[rows, :]
            den = den + jnp.where(
                lane == h, before * accd[rows, :].sum(axis=1, keepdims=True),
                0.0)
        den_ref[...] = den


def retention_state_chunk(state, sums, q, k, v, a, slots, valid, fresh,
                          cdtype):
    """A chunk of every row's recurrence, its slot's state updated in
    place (module docstring).  Returns numerators, normalisers, the pool
    and the rows' new sums."""
    return _chunk(state, sums, q, k, v, a, slots, valid, fresh,
                  cdtype=jnp.dtype(cdtype).name, interpret=_pa._INTERPRET)


# jitted so that a program's retention layers, which call it at one set
# of shapes, trace and lower the kernel ONCE between them
@functools.partial(jax.jit, static_argnames=("cdtype", "interpret"))
def _chunk(state, sums, q, k, v, a, slots, valid, fresh, *, cdtype,
           interpret):
    b, n, g, r, d = q.shape
    O = state.shape[2]
    cdtype = jnp.dtype(cdtype)
    f32 = jnp.float32
    if r > d:
        raise ValueError("a key-value head's query heads lie in the lanes "
                         f"of one row of normalisers: {r} > {d}")
    Q = min(BLOCK, n)
    pad = -n % Q
    live = (jnp.arange(n)[None, :] < valid[:, None])[..., None]   # [b, n, 1]
    k = jnp.where(live[..., None], k, jnp.zeros((), k.dtype))
    a = jnp.where(live, a.astype(f32), 0.0)
    if pad:
        # a token with a = 0 and k = 0 changes nothing
        q, k, v, a = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] *
                              (x.ndim - 2)) for x in (q, k, v, a))
    m = n + pad
    nc = m // Q
    # the running sum of the log-gates inside each block, down a column
    # (a token's own) and along a row (the tokens it looks back on)
    A = jnp.cumsum(a.reshape(b, nc, Q, g), axis=2)
    A = jnp.moveaxis(A, 3, 1)                               # [b, g, nc, Q]
    A_col = A.reshape(b, g, m, 1)
    A_row = A.reshape(b, g, nc, 1, Q)
    U = rotation_group(O)
    tm = min(_ROW_TILE, r * Q)
    # c_o, the layout's own (1 at the first and the last rotation, sqrt 2
    # between): the tiles are held once a distinct weight
    weights = _step.phi_weights(d)
    levels = tuple(sorted(set(weights) | {1.0}))

    def rows(width):
        return pl.BlockSpec((None, Q, width), lambda i, j, c, *_: (i, c, j))

    slot = pl.BlockSpec((None, None, O, d, d),
                        lambda i, j, c, slots, *_: (slots[i], j, 0, 0, 0))
    row_sums = pl.BlockSpec((None, None, O, d),
                            lambda i, j, c, *_: (i, j, 0, 0))

    num, den, state, sums = pl.pallas_call(
        functools.partial(_body, Q=Q, r=r, d=d, U=U, tm=tm, levels=levels,
                          cdtype=cdtype),
        name="retention_state_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, g, nc),
            in_specs=[
                rows(r * d), rows(d), rows(d),
                pl.BlockSpec((None, None, Q, 1),
                             lambda i, j, c, *_: (i, j, c, 0)),
                pl.BlockSpec((None, None, None, 1, Q),
                             lambda i, j, c, *_: (i, j, c, 0, 0)),
                slot, row_sums],
            out_specs=[
                rows(r * d),
                pl.BlockSpec((None, None, Q, d),
                             lambda i, j, c, *_: (i, j, c, 0)),
                slot, row_sums],
            scratch_shapes=[pltpu.VMEM((len(levels), r * Q, d), f32),
                            pltpu.VMEM((len(levels), Q, d), f32),
                            pltpu.VMEM((r * Q, U * d), cdtype),
                            pltpu.VMEM((d, U * d), cdtype),
                            pltpu.VMEM((r * Q, d), f32),
                            pltpu.VMEM((r * Q, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, m, g * r * d), f32),
                   jax.ShapeDtypeStruct((b, g, m, d), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(sums.shape, sums.dtype)],
        # operand 9 (after the four prefetched scalars, q, k, v and the
        # two running sums) is the pool; output 2 is the pool
        input_output_aliases={9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(slots.astype(jnp.int32), fresh.astype(jnp.int32),
      valid.astype(jnp.int32),
      jnp.asarray([levels.index(w) for w in weights], jnp.int32),
      q.reshape(b, m, g * r * d),
      k.reshape(b, m, g * d), v.reshape(b, m, g * d), A_col, A_row,
      state, sums)
    num = num.reshape(b, m, g, r, d)[:, :n]
    den = jnp.moveaxis(den[..., :r], 1, 2)[:, :n]           # [b, n, g, r]
    return num, den, state, sums
