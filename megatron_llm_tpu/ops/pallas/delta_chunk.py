"""A prefill chunk's gated delta rule (``models/gated_delta.py``, step 4)
as ONE kernel that solves a block's triangular systems on the MXU beside
the heads' state held in VMEM (Pallas Mosaic TPU); a sibling of
``delta_step.py`` and of ``retention_chunk.py``, whose shape it has.

The algebra is ``gated_delta.gated_delta_chunk``'s, block for block and
rounding for rounding.  Inside a block of :data:`BLOCK` rows, a value
head: ``G`` the running sum of ``g``, ``Gam_tj = exp(G_t - G_j)`` at ``j
<= t`` (a masked difference before ``exp``), ``A = tril(beta Gam (k
k^T), -1)``, and the block's ``d`` from ``(I + A) X = [beta exp(G) K |
beta V]``; across blocks ``D = U - W S``, ``o = exp(G) (q S) + (Gam (q
k^T)) D`` and ``S <- exp(G_end) S + (exp(G_end - G) K)^T D``.  ``G``,
``Gam``, ``A``, the solve and the carried ``S`` are float32; ``W``, ``M``,
``to_end``, ``D`` and the state as a product's operand are rounded to the
compute dtype and their products accumulated in float32.  What XLA's
form pays for is not the arithmetic: its ``triangular_solve`` is forward
substitution, 64 dependent steps a system, and ``A``, ``Gam``, ``kk``,
``qk``, ``rhs``, ``X``, ``W``, ``U``, ``M`` and ``to_end`` each go to
HBM and come back between its programs (0.88 ms a layer at Qwen3-Next's
widths against some 30 us of bytes; chip runs, PR 58).  Here NOTHING
BETWEEN THE GATES AND ``o`` IS WRITTEN TO HBM, a head's state comes in
once a layer a chunk and goes back once, and the solve is products.

THE SOLVE.  ``I + A`` is unit lower triangular and does not read the
carried state.  Its diagonal blocks of :data:`_SUB` (16) rows are
inverted by substitution on the vector units: the blocks of a program
lie side by side along the lanes (``[16, r x BLOCK]``: a block's own
lanes, so nothing moves), and step k takes row k of every block's
inverse, finished by then, times column k of its block, spread over the
block's lanes by four lane rotations, off the rows below: 15 short steps
for every diagonal block at once.  The rest of the inverse is had by
doubling: with ``T_s`` the inverse of the diagonal blocks of size ``s``
and ``B_s`` the part of ``A`` in the lower left quarter of each diagonal
block of size ``2 s``,

    T_2s = T_s - (T_s B_s) T_s,

the block form of substitution (``[[P, 0], [B, R]]^-1 = [[P^-1, 0],
[-R^-1 B P^-1, R^-1]]``): exact in exact arithmetic and, unlike the
product ``(I - A)(I + A^2)(I + A^4)...``, it never forms a power of
``A``.  Two levels (16 -> 32 -> 64), two products of the whole matrix
each, on the MXU in float32 (``Precision.HIGHEST``: the full-precision
passes, not one bf16 pass), and ``X = T rhs`` one more.

Shape contract (``gated_delta_chunk``'s; ``r`` = value heads a key head):

* ``q``, ``k`` -- ``[b, n, key_heads, d_key]``, ``v`` -- ``[b, n,
  value_heads, d_value]`` in the compute dtype; key head j serves value
  heads ``j r .. j r + r - 1``;
* ``g`` (log decay, <= 0), ``beta`` -- ``[b, n, value_heads]`` float32,
  both 0 at a token that is not real (it neither decays nor writes);
* ``S`` -- ``[b, value_heads, d_key, d_value]`` float32, the rows' state
  as ``PagedKVCache.read_state`` gives it.  THE POOL IS NOT AN OPERAND:
  this model's chunk is lent its pool (an attention layer keeps pages),
  so the rows' state is read and put back by the cache as around XLA's
  form.

Returns ``o`` ``[b, n, value_heads, d_value]`` float32 and the state
after the last real token.  A row with no token gets its state back as
it was; ``n`` is padded to whole blocks (a chunk shorter than a block is
one block).

Kernel structure: a grid of (row, key head, :data:`_STEP_BLOCKS` blocks),
the blocks innermost and in order.  A program holds ONE KEY HEAD'S ``r``
VALUE HEADS, a block's rows one head under the other (``[r x BLOCK, .]``:
128 rows at ``r`` = 2), so that ``k k^T`` and ``q k^T`` are one product
each for both, every mask is "same head", and the systems of the ``r``
heads are ONE block diagonal matrix of ``r x BLOCK`` rows whose products
fill the MXU's 128 rows.  The heads' state ``[r, d_key, d_value]``
float32 (128 KB) is the output's block, revisited by the key head's
steps (copied from the input at the first) and written back once.  What
does not read the state (``G`` to ``X``, ``M``, ``to_end``) is formed
for each block of the step first, then the blocks are walked in order
over the state.  ``beta`` comes in down a column (``[r x BLOCK, 1]``)
and ``g`` along a row (``[1, r x BLOCK]``): ``G`` down a column is a
masked sum along the row, and ``G`` along a row is the column's, moved
exactly by a masked sum of one term, so that ``Gam``'s diagonal is 1 and
nothing is transposed.

VMEM: the state in and out, twice each (0.5 MB), a step's operands and
``o`` twice (1.5 MB) and a few MB of ``[128, 128]`` and ``[128, 256]``
float32 temporaries of its four blocks, under :data:`_VMEM_LIMIT`.

Measured with the kernel alone on a TPU v5e at Qwen3-Next's widths (one
row of 512 tokens from a carried state, 16 key heads serving 32 value
heads of 128 x 128, bf16, a scan over six layers' operands, six timings
each; chip runs, PR 59).  Milliseconds a layer, least to most:

    every product ONE bf16 pass (another result)    0.232 - 0.236
    substitution to 16, 8 blocks a step             0.325 - 0.327
    substitution to 16, 4 blocks a step (these)     0.331 - 0.334
    substitution to 16, 2 blocks a step             0.332 - 0.337
    substitution to 16, 1 block a step              0.342 - 0.346
    substitution to 8 (three levels of doubling)    0.366 - 0.369
    substitution to 32 (one level)                  0.380 - 0.381
    doubling from 2 (five levels, no substitution)  0.429 - 0.432
    gated_delta_chunk (XLA, compiled alone)         0.902 - 0.906
    substitution to 64 (no doubling)                2.759 - 2.762

(The last five rows but XLA's were timed at one block a step.)  0.33 ms
is 2.6 us a (key head, block): the float32 passes cost 0.10 of
it, and what is left is not the MXU's (some 0.03 at its peak) but the
vector units' and the stores': every array between the gates and ``X``
is ``[128, 128]`` float32, sixteen registers, half of them the zeros
between the two heads' systems.  The blocks a step hardly matter: the
chains were not waiting on each other.  On the chip the kernel and XLA's
form stand at the SAME distance from the recurrence in float64 (``o``:
4.586e-4 at most and 6.68e-5 in the root mean square at outputs of 0.135
at most, both forms to four digits; the state 4.60e-3 at most at 1.46),
and 4.5e-4 / 3.9e-3 from each other.

Dispatch is ``models/gated_delta.py``'s (a ``PagedKVCache`` whose
resolved ``kernel`` is ``'pallas'``); interpret mode in tests rides
``paged_attention._INTERPRET``, as every kernel of the cache does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_llm_tpu.ops.pallas import paged_attention as _pa

# rows of a chunk's block: the triangular system is BLOCK x BLOCK a value
# head (a chunk of 512 is eight blocks); 64 is the published code's, and
# what the model leaves free, so no flag (``models/gated_delta.py::BLOCK``
# is this one).  A power of two: the solve doubles up to it
BLOCK = 64
# the diagonal blocks inverted by substitution on the vector units, before
# the doubling takes over on the MXU
_SUB = 16
# blocks a grid step, at most: their solves are independent chains
_STEP_BLOCKS = 4
_VMEM_LIMIT = 32 << 20


def _inverse(A, row, col, Q):
    """``(I + A)^-1`` of ``A`` [R, R] float32, strictly lower triangular
    and block diagonal in blocks of ``Q`` (module docstring: THE SOLVE).
    ``row`` / ``col``: the indices as iotas."""
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    R = A.shape[0]
    s = _SUB
    sh = s.bit_length() - 1
    # the diagonal blocks of size s, side by side: block j's [s, s] at
    # lanes j s .., where the matrix has it too
    L = sum(jnp.where((row >> sh) == (col >> sh), A, 0.0)[j * s:(j + 1) * s]
            for j in range(R // s))                         # [s, R]
    # (iotas of their own: Mosaic does not slice one)
    sub = jax.lax.broadcasted_iota(jnp.int32, (s, R), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (s, R), 1)
    at = lane & (s - 1)                     # a lane's column in its block
    T = (sub == at).astype(f32)
    for k in range(s - 1):
        # column k of each block beside the block's lanes 0 .. k: row k
        # of its inverse is 0 beyond them, so what spills into the
        # block before meets zeros
        x = jnp.where(at == k, L, 0.0)
        d = 1
        while d < s:
            x = x + pltpu.roll(x, R - d, axis=1)
            d *= 2
        T = T - x * T[k:k + 1]
    # back where the matrix has them
    T = jnp.concatenate([jnp.where((lane >> sh) == j, T, 0.0)
                         for j in range(R // s)], axis=0)
    while s < Q:
        # the lower left quarter of each diagonal block of size 2 s
        rb, cb = row >> sh, col >> sh
        B = jnp.where(jnp.logical_and(rb == cb + 1, (rb & 1) == 1), A, 0.0)
        P = jnp.dot(T, B, precision=hi, preferred_element_type=f32)
        T = T - jnp.dot(P, T, precision=hi, preferred_element_type=f32)
        s, sh = 2 * s, sh + 1
    return T


def _body(q_ref, k_ref, v_ref, beta_ref, g_ref, s_in, o_ref, s_ref, *,
          Q, r, dk, dv, nb, cdtype):
    """``nb`` blocks of one (row, key head): its ``r`` value heads, head
    h's rows of a block at ``h Q .. h Q + Q - 1``.  ``s_ref``: the heads'
    state, the same buffer for every step of the pair.  What does not
    read the state is formed for every block of the step first (the
    blocks' chains are independent, so the scheduler has ``nb`` of them
    to interleave), then the blocks are walked in order."""
    f32 = jnp.float32
    R = r * Q
    hi = jax.lax.Precision.HIGHEST
    # a product "in the compute dtype": float32 operands (tests) are
    # taken whole
    prec = hi if cdtype == f32 else None
    nt = (((1,), (1,)), ((), ()))

    @pl.when(pl.program_id(2) == 0)
    def _arrive():
        s_ref[...] = s_in[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, R), 1)
    sh = Q.bit_length() - 1
    same = (row >> sh) == (col >> sh)               # one head's rows
    seen = jnp.logical_and(same, col <= row)
    strictly = jnp.logical_and(same, col < row)
    diagonal = row == col
    last = jnp.logical_and(same, (col & (Q - 1)) == Q - 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) >> sh

    def stacked(x):
        """A key head's [Q, .] under itself, once a value head."""
        return jnp.concatenate([x] * r, axis=0) if r > 1 else x

    def solved(c):
        """Block c as far as the state is not read."""
        beta = beta_ref[c * R:(c + 1) * R]                  # [R, 1]
        g_row = g_ref[c]                                    # [1, R]
        # G down a column, then the same numbers along a row
        G = jnp.sum(jnp.where(seen, g_row, 0.0), axis=1, keepdims=True)
        G_row = jnp.sum(jnp.where(diagonal, G, 0.0), axis=0, keepdims=True)
        # Gam_tj = exp(G_t - G_j) at j <= t: differences, masked BEFORE
        # exp
        Gam = jnp.where(seen, jnp.exp(jnp.where(seen, G - G_row, 0.0)), 0.0)
        # G at the head's last row, beside each of its rows
        G_end = jnp.sum(jnp.where(last, G_row, 0.0), axis=1, keepdims=True)
        k = stacked(k_ref[c * Q:(c + 1) * Q])               # [R, dk]
        qh = stacked(q_ref[c * Q:(c + 1) * Q])
        kk = jax.lax.dot_general(k, k, nt, precision=prec,
                                 preferred_element_type=f32)
        qk = jax.lax.dot_general(qh, k, nt, precision=prec,
                                 preferred_element_type=f32)
        # (I + A) X = [beta exp(G) K | beta V], A strictly lower
        A = jnp.where(strictly, beta * Gam * kk, 0.0)
        kf = k.astype(f32)
        vf = jnp.concatenate(
            [v_ref[c * Q:(c + 1) * Q, h * dv:(h + 1) * dv]
             for h in range(r)], axis=0).astype(f32)        # [R, dv]
        before = jnp.exp(G)                                 # [R, 1]
        rhs = jnp.concatenate([(beta * before) * kf, beta * vf], axis=1)
        X = jnp.dot(_inverse(A, row, col, Q), rhs, precision=hi,
                    preferred_element_type=f32)
        W, U = X[:, :dk].astype(cdtype), X[:, dk:]
        M = (Gam * qk).astype(cdtype)                       # [R, R]
        # turned, then rounded: [dk, R]
        to_end_t = (jnp.exp(G_end - G) * kf).T.astype(cdtype)
        kept = [jnp.exp(G_row[:, (h + 1) * Q - 1:(h + 1) * Q])  # [1, 1]
                for h in range(r)]
        return W, U, M, qh, before, to_end_t, kept

    blocks = [solved(c) for c in range(nb)]
    for c, (W, U, M, qh, before, to_end_t, kept) in enumerate(blocks):
        D, qS = [], []
        for h in range(r):
            rows = slice(h * Q, (h + 1) * Q)
            Sc = s_ref[h].astype(cdtype)
            both = jnp.dot(jnp.concatenate([W[rows], qh[rows]], axis=0), Sc,
                           precision=prec, preferred_element_type=f32)
            D.append(U[rows] - both[:Q])
            qS.append(both[Q:])
        D = jnp.concatenate(D, axis=0).astype(cdtype)       # [R, dv]
        o = before * jnp.concatenate(qS, axis=0) + jnp.dot(
            M, D, precision=prec, preferred_element_type=f32)
        for h in range(r):
            o_ref[c * Q:(c + 1) * Q, h * dv:(h + 1) * dv] = \
                o[h * Q:(h + 1) * Q]
            own = D if r == 1 else jnp.where(head == h, D,
                                             jnp.zeros_like(D))
            s_ref[h] = kept[h] * s_ref[h] + jnp.dot(
                to_end_t, own, precision=prec, preferred_element_type=f32)


def delta_state_chunk(q, k, v, g, beta, S, cdtype):
    """A chunk of every row's recurrence (module docstring):
    ``gated_delta.gated_delta_chunk``'s contract.  Returns ``o`` and the
    rows' new state."""
    return _chunk(q, k, v, g, beta, S, cdtype=jnp.dtype(cdtype).name,
                  interpret=_pa._INTERPRET)


# jitted so that a program's delta-rule layers, which call it at one set
# of shapes, trace and lower the kernel ONCE between them
@functools.partial(jax.jit, static_argnames=("cdtype", "interpret"))
def _chunk(q, k, v, g, beta, S, *, cdtype, interpret):
    b, n, kh, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // kh
    cdtype = jnp.dtype(cdtype)
    f32 = jnp.float32
    Q = BLOCK
    pad = -n % Q
    if pad:
        # a token with g = 0 and beta = 0 changes nothing
        q, k, v, g, beta = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] *
                                    (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    m = n + pad
    nc = m // Q
    R = r * Q
    # blocks a grid step: the most that divide the chunk's
    nb = max(x for x in range(1, min(nc, _STEP_BLOCKS) + 1) if nc % x == 0)

    def by_key_head(x):
        """[b, m, hv] -> [b, kh, nc, r Q]: a key head's value heads one
        after the other, a block at a time."""
        x = x.astype(f32).reshape(b, nc, Q, kh, r)
        return jnp.transpose(x, (0, 3, 1, 4, 2)).reshape(b, kh, nc, R)

    beta_col = by_key_head(beta).reshape(b, kh, nc * R, 1)
    g_row = by_key_head(g).reshape(b, kh, nc, 1, R)

    def rows(width):
        return pl.BlockSpec((None, nb * Q, width), lambda i, j, c: (i, c, j))

    state = pl.BlockSpec((None, r, dk, dv), lambda i, j, c: (i, j, 0, 0))
    o, S = pl.pallas_call(
        functools.partial(_body, Q=Q, r=r, dk=dk, dv=dv, nb=nb, cdtype=cdtype),
        name="delta_state_chunk",
        grid=(b, kh, nc // nb),
        in_specs=[
            rows(dk), rows(dk), rows(r * dv),
            pl.BlockSpec((None, None, nb * R, 1),
                         lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((None, None, nb, 1, R),
                         lambda i, j, c: (i, j, c, 0, 0)),
            state],
        out_specs=[rows(r * dv), state],
        out_shape=[jax.ShapeDtypeStruct((b, m, hv * dv), f32),
                   jax.ShapeDtypeStruct(S.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(q.reshape(b, m, kh * dk), k.reshape(b, m, kh * dk),
      v.reshape(b, m, hv * dv), beta_col, g_row, S.astype(f32))
    return o.reshape(b, m, hv, dv)[:, :n], S
