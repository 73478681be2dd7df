"""Top-k / top-p / temperature sampling.

Reference: ``megatron/text_generation/sampling.py:14-93`` —
``modify_logits_for_top_k/top_p`` + ``sample``.  Pure-jnp, jit-safe.

Two families.  ``modify_logits`` / ``sample`` take STATIC knobs (the
legacy generation path: static top_k, top_p via sorted cumulative mass).
``modify_logits_batched`` / ``sample_batched`` take one knob per row as
traced arrays (the serving engine's three programs) and do the work the
step's LIVE rows ask for, decided on the device inside the one compiled
program: an argmax and nothing else when every live row is greedy; a
draw with no sort when some live row samples and none of those filters;
one descending sort of ``[S, V]`` when some live sampling row has an
active top-k or top-p.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e10


def modify_logits(
    logits: jax.Array,
    top_k: int = 0,
    top_p: float = 0.0,
    temperature: float = 1.0,
) -> jax.Array:
    """logits [..., V] -> filtered/scaled logits."""
    logits = logits.astype(jnp.float32)
    if temperature != 1.0 and temperature > 0:
        logits = logits / temperature
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    # top_p may be a traced scalar (per-step decayed value, the
    # reference's top_p_decay/top_p_bound machinery) — the filter is then
    # built unconditionally and gated with jnp.where
    dynamic_p = isinstance(top_p, jax.Array)
    if dynamic_p or (top_p > 0.0 and top_p < 1.0):
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens until cumulative mass exceeds top_p (always keep top-1)
        cutoff_idx = jnp.sum((cum - probs) < top_p, axis=-1, keepdims=True) - 1
        cutoff_idx = jnp.maximum(cutoff_idx, 0)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        filtered = jnp.where(logits < cutoff, NEG_INF, logits)
        if dynamic_p:
            active = (top_p > 0.0) & (top_p < 1.0)
            logits = jnp.where(active, filtered, logits)
        else:
            logits = filtered
    return logits


def sample(
    logits: jax.Array,
    key: jax.Array,
    top_k: int = 0,
    top_p: float = 0.0,
    temperature: float = 1.0,
    greedy: bool = False,
) -> jax.Array:
    """Sample token ids from [..., V] logits (reference: sampling.py:45-93;
    greedy when top_k==1 or temperature==0)."""
    if greedy or top_k == 1 or temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = modify_logits(logits, top_k, top_p, temperature)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def rows_asking(top_k, top_p, temperature, live, vocab: int):
    """Per row, what ``sample_batched`` has to do for it: ``(greedy,
    drawing, filtering)``.  Greedy rows (temperature 0 or top_k 1) take
    the argmax; a live row that is not greedy draws; a drawing row with an
    active top-k (``0 < k < vocab``) or top-p (``0 < p < 1``) filters.
    Operators only, so the engine's host-side record (numpy) counts the
    very rows the program's predicates (traced) see."""
    greedy = (temperature <= 0.0) | (top_k == 1)
    drawing = live & ~greedy
    filtering = drawing & (((top_k > 0) & (top_k < vocab))
                           | ((top_p > 0.0) & (top_p < 1.0)))
    return greedy, drawing, filtering


def _scale_rows(logits: jax.Array, temperature: jax.Array) -> jax.Array:
    """float32 logits [S, V] over each row's temperature (rows at 0 are
    greedy and stay as they are)."""
    t = temperature[:, None]
    return jnp.where(t > 0.0, logits / jnp.maximum(t, 1e-6), logits)


def _filter_rows(logits: jax.Array, top_k: jax.Array,
                 top_p: jax.Array) -> jax.Array:
    """Top-k then top-p on scaled rows, from ONE descending sort.

    The top-p cutoff is taken over what survived top-k, so it needs the
    filtered row sorted; that row is the sorted row with the same mask
    applied (what lies below the k-th logit is a suffix of a descending
    row, and ties with the k-th are kept on both sides), so no second
    sort runs.  An exact top-p needs the whole row: no bounded
    ``lax.top_k`` here."""
    V = logits.shape[-1]
    sorted_l = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        sorted_l, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
    k_active = ((top_k > 0) & (top_k < V))[:, None]
    logits = jnp.where(k_active & (logits < kth), NEG_INF, logits)
    sorted_p = jnp.where(k_active & (sorted_l < kth), NEG_INF, sorted_l)
    probs = jax.nn.softmax(sorted_p, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum((cum - probs) < top_p[:, None], axis=-1,
                         keepdims=True) - 1
    cutoff = jnp.take_along_axis(sorted_p, jnp.maximum(cutoff_idx, 0),
                                 axis=-1)
    p_active = ((top_p > 0.0) & (top_p < 1.0))[:, None]
    return jnp.where(p_active & (logits < cutoff), NEG_INF, logits)


def modify_logits_batched(
    logits: jax.Array,          # [S, V]
    top_k: jax.Array,           # [S] int32 (0 = off)
    top_p: jax.Array,           # [S] float32 (0 or 1 = off)
    temperature: jax.Array,     # [S] float32 (0 = greedy rows, untouched)
) -> jax.Array:
    """Per-row traced sampling knobs — the serving engine's decode step
    co-batches requests with different params in one fixed-shape call, so
    none of them can be static (a static knob would recompile the step
    whenever a new request joins the batch).  Same semantics as
    ``modify_logits`` applied row-wise: temperature scale, then top-k,
    then top-p over the top-k-filtered distribution, all rows through one
    descending sort.  ``sample_batched`` runs this only in a step that
    needs it."""
    return _filter_rows(_scale_rows(logits.astype(jnp.float32), temperature),
                        top_k, top_p)


def sample_batched(
    logits: jax.Array,          # [S, V]
    keys: jax.Array,            # [S, 2] uint32 — one PRNG chain per slot
    top_k: jax.Array,
    top_p: jax.Array,
    temperature: jax.Array,
    live: jax.Array,            # [S] bool — rows whose token is read
) -> jax.Array:
    """Row-wise ``sample``: greedy rows (temperature 0 or top_k 1) take
    the raw argmax exactly like ``sample``'s greedy branch; the rest draw
    from the filtered distribution with their own PRNG key, so a
    request's sample stream is independent of who it shares the batch
    with.

    The step does what its ``live`` rows ask for (a dead slot keeps its
    last request's knobs, so they do not count): no draw unless a live
    row is not greedy, and no sort unless such a row has an active top-k
    (``0 < k < V``) or top-p (``0 < p < 1``).  The predicates are traced
    scalars under ``lax.cond``: one program, whatever joins the batch.  A
    live row's token is the same whichever branch ran (a row with no
    active filter leaves the sort path as it entered); rows that are not
    live may return anything."""
    logits = logits.astype(jnp.float32)
    greedy, drawing, filtering = rows_asking(top_k, top_p, temperature,
                                             live, logits.shape[-1])
    best = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw():
        scaled = _scale_rows(logits, temperature)
        filtered = jax.lax.cond(
            filtering.any(), lambda: _filter_rows(scaled, top_k, top_p),
            lambda: scaled)
        drawn = jax.vmap(lambda l, k: jax.random.categorical(k, l))(
            filtered, keys)
        return jnp.where(greedy, best, drawn.astype(jnp.int32))

    return jax.lax.cond(drawing.any(), draw, lambda: best)
