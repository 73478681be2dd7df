"""KV-cached autoregressive generation + beam search.

Reference: ``megatron/text_generation/generation.py`` —
``generate_tokens_probs_and_return_on_first_stage`` (:89-287): incremental
forward with an inference KV cache, per-step sampling, EOD early stop,
optional per-token log-probs; beam search (:288-416) with hypothesis
management in ``beam_utils.py``.

TPU design: the whole decode — prefill, while-loop over positions,
sampling, done-flag early exit — is one compiled function; nothing
round-trips to the host per token.  Ragged prompts follow the reference's
scheme: decoding starts at the minimum prompt length and prompt tokens
override samples until each row's length is passed.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from megatron_llm_tpu.config import (ROLLING_CACHE, TransformerConfig,
                                     refusal)
from megatron_llm_tpu.models.language_model import language_model_forward
from megatron_llm_tpu.models.transformer import rotary_freqs
from megatron_llm_tpu.text_generation.sampling import modify_logits, sample

NEG_INF_LOGIT = -1e10


def init_kv_caches(cfg: TransformerConfig, batch: int, max_len: int,
                   dtype=None, rolling: bool = False,
                   quantized: bool = False):
    """Per-layer decode caches, one a layer A PASS (``cfg.cache_layers``:
    a looped stack's pass attends its own keys).  ``rolling=True`` (sliding-window models
    only) allocates a ring buffer of exactly ``sliding_window_size``
    slots instead of ``max_len`` — decode memory O(window) rather than
    O(total), a beyond-reference memory mode (the reference's inference
    cache is always full-length).  Forwards of any chunk length are
    exact: attention reads [pre-chunk ring || current chunk] and the
    ring is written after (models/transformer.py rolling branch)."""
    dtype = dtype or cfg.compute_jnp_dtype
    ng, d = cfg.num_query_groups, cfg.head_dim
    said = rolling and refusal(cfg, (ROLLING_CACHE,))
    if said:
        raise ValueError(said)
    if rolling:
        assert cfg.sliding_window_size is not None, \
            "rolling caches need a sliding-window model"
        size = min(max_len, cfg.sliding_window_size)
    else:
        size = max_len
    if quantized:
        # int8 K/V + per-(batch, position, group) fp32 absmax scales
        # (models/transformer.py int8 branch) — halves decode KV HBM
        # traffic vs bf16.  Linear cache only (the rolling ring is
        # already O(window)).
        assert not rolling, "int8 KV cache: linear cache only"
        return [
            {
                "k_q": jnp.zeros((batch, size, ng, d), jnp.int8),
                "k_scale": jnp.ones((batch, size, ng), jnp.float32),
                "v_q": jnp.zeros((batch, size, ng, d), jnp.int8),
                "v_scale": jnp.ones((batch, size, ng), jnp.float32),
                "index": jnp.int32(0),
            }
            for _ in range(cfg.cache_layers)
        ]
    return [
        {
            "k": jnp.zeros((batch, size, ng, d), dtype),
            "v": jnp.zeros((batch, size, ng, d), dtype),
            "index": jnp.int32(0),
            # presence marker (value None = empty pytree node): the flag
            # must be STRUCTURAL, not a leaf, so the decode while-loop
            # carry doesn't trace it into a bool array
            **({"rolling": None} if rolling else {}),
        }
        for _ in range(cfg.cache_layers)
    ]


def _forward_with_cache(model, params, tokens, caches, start_pos):
    """Run the model over ``tokens`` [b, n] writing KV at ``start_pos``;
    returns (logits [b, n, V], new caches)."""
    cfg = model.cfg
    caches = [dict(c, index=jnp.int32(start_pos)) for c in caches]
    b, n = tokens.shape
    position_ids = start_pos + jnp.arange(n)[None, :]
    position_ids = jnp.broadcast_to(position_ids, (b, n))
    logits, new_caches = language_model_forward(
        params, tokens, position_ids, None, cfg,
        rng_key=None, train=False, kv_caches=caches,
    )
    return logits, new_caches


def _prefill_chunks(b: int, n: int, threshold: Optional[int]) -> int:
    """Micro-batch count for the prefill forward: smallest divisor C of b
    with (b/C)*n <= threshold.  Reference ``_with_pipelining_forward_step``
    (text_generation/forward_step.py:17-204) splits exactly these
    over-threshold batch*seqlen forwards into micro batches."""
    if threshold is None or b * n <= threshold or b <= 1:
        return 1
    for c in range(2, b + 1):
        if b % c == 0 and (b // c) * n <= threshold:
            return c
    return b


@functools.partial(
    jax.jit,
    static_argnames=("model", "max_new_tokens", "min_prompt_len", "top_k",
                     "top_p", "temperature", "greedy", "eod_id",
                     "return_log_probs", "batch_times_seqlen_threshold",
                     "top_p_decay", "top_p_bound", "extra_stop_ids",
                     "stop_pairs", "ban_pairs", "rolling_cache",
                     "cache_len", "int8_kv_cache"),
)
def generate_tokens(
    model,
    params,
    prompt_tokens: jax.Array,      # [b, max_prompt] right-padded
    prompt_lengths: jax.Array,     # [b]
    rng_key,
    *,
    max_new_tokens: int,
    min_prompt_len: int,
    top_k: int = 0,
    top_p: float = 0.0,
    temperature: float = 1.0,
    greedy: bool = False,
    eod_id: Optional[int] = None,
    return_log_probs: bool = False,
    batch_times_seqlen_threshold: Optional[int] = None,
    top_p_decay: float = 0.0,
    top_p_bound: float = 0.0,
    extra_stop_ids: tuple = (),
    stop_pairs: tuple = (),
    ban_pairs: tuple = (),
    rolling_cache: bool = False,
    cache_len: Optional[int] = None,
    int8_kv_cache: bool = False,
):
    """Returns (tokens [b, total], gen_lengths [b], log_probs [b, total]).

    ``cache_len``: allocate the KV cache with at least this many slots
    (>= prompt + max_new_tokens).  Decode masks cache positions beyond
    the current index, so results are identical; per-step attention
    cost then depends on the allocation, not on max_new_tokens — which
    is what lets a measurement difference two generation lengths at
    equal per-step cost.  NOTE this alone does NOT
    make compiles reusable across request shapes: the jit still keys
    on the prompt array shape and the static max_new_tokens — a server
    wanting few compiles must pad prompts to bucket widths and fix
    max_new_tokens per bucket (at which point the cache size is
    already uniform).  Ignored for rolling caches, which are already
    fixed-size (the sliding window).

    ``int8_kv_cache``: store K/V as int8 with per-(batch, position,
    group) absmax scales — half the decode KV HBM traffic vs bf16,
    the dominant bytes at long context.  Logits shift by the ~0.4%
    per-entry quantization error (tests bound the drift); linear cache
    only.

    ``batch_times_seqlen_threshold``: prefill forwards whose batch*seqlen
    exceeds it run micro-batched (sequential ``lax.map`` chunks), so the
    [b, n, vocab] prefill logits are never materialized at once —
    the reference's ``--inference_batch_times_seqlen_threshold``.

    Reference server semantics (text_generation/generation.py:89-287):
    ``top_p_decay``/``top_p_bound`` multiply top_p by decay each generated
    token with a floor at bound; ``extra_stop_ids`` stop a row like eod
    (stop_on_eol / stop_on_double_eol); ``stop_pairs`` stop on a
    (prev, cur) token bigram (two consecutive newlines); ``ban_pairs``
    zero out token ``b`` whenever the previous token is ``a``
    (prevent_newline_after_colon)."""
    cfg = model.cfg
    b, max_prompt = prompt_tokens.shape
    total = max_prompt + max_new_tokens
    cache_total = total if (cache_len is None or rolling_cache) \
        else max(cache_len, total)
    caches = init_kv_caches(cfg, b, cache_total, rolling=rolling_cache,
                            quantized=int8_kv_cache)

    tokens = jnp.concatenate(
        [prompt_tokens,
         jnp.zeros((b, max_new_tokens), prompt_tokens.dtype)], axis=1
    )
    log_probs = jnp.zeros((b, total), jnp.float32)

    # ---- prefill up to the shortest prompt --------------------------------
    prefill = max(min_prompt_len, 1)
    C = _prefill_chunks(b, prefill, batch_times_seqlen_threshold)
    if C == 1:
        logits, caches = _forward_with_cache(
            model, params, tokens[:, :prefill], caches, 0
        )
        last_logits = logits[:, -1]
        if return_log_probs:
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            # log_probs[i, t] = logp of tokens[i, t] given prefix (t >= 1)
            picked = jnp.take_along_axis(
                lp[:, :-1], tokens[:, 1:prefill, None].astype(jnp.int32),
                axis=-1,
            )[..., 0]
            log_probs = jax.lax.dynamic_update_slice(log_probs, picked,
                                                     (0, 1))
    else:
        # micro-batched prefill: per-chunk forward reduces its own logits
        # to (last_logits, picked log-probs) so the full [b, n, vocab]
        # tensor never exists
        bc = b // C
        toks_c = tokens[:, :prefill].reshape(C, bc, prefill)
        # generic over cache layouts (plain k/v, int8 k_q/.../scales,
        # rolling marker): batch-leading tensors reshape, index
        # broadcasts, structural markers pass through — a new cache
        # key can't silently miss this path
        caches_c = [
            {key: (jnp.broadcast_to(val, (C,)) if key == "index"
                   else val if val is None
                   else val.reshape(C, bc, *val.shape[1:]))
             for key, val in c.items()}
            for c in caches
        ]

        def one(chunk):
            toks_i, caches_i = chunk
            logits_i, caches_i = _forward_with_cache(
                model, params, toks_i, caches_i, 0)
            if return_log_probs:
                lp_i = jax.nn.log_softmax(logits_i.astype(jnp.float32), -1)
                picked_i = jnp.take_along_axis(
                    lp_i[:, :-1], toks_i[:, 1:, None].astype(jnp.int32),
                    axis=-1)[..., 0]
            else:
                picked_i = jnp.zeros((bc, prefill - 1), jnp.float32)
            return logits_i[:, -1], picked_i, caches_i

        last_c, picked_c, caches_out = jax.lax.map(one, (toks_c, caches_c))
        last_logits = last_c.reshape(b, -1)
        if return_log_probs:
            log_probs = jax.lax.dynamic_update_slice(
                log_probs, picked_c.reshape(b, prefill - 1), (0, 1))
        caches = [
            {key: (val[0] if key == "index" else val if val is None
                   else val.reshape(b, *val.shape[2:]))
             for key, val in c.items()}
            for c in caches_out
        ]

    # ---- decode loop ------------------------------------------------------
    def cond(state):
        pos, _, _, _, _, done, _ = state
        return (pos < total) & ~jnp.all(done)

    def body(state):
        pos, tokens, caches, last_logits, log_probs, done, key = state
        key, sub = jax.random.split(key)
        prev = jax.lax.dynamic_index_in_dim(tokens, pos - 1, 1,
                                            keepdims=False)
        for a, b_id in ban_pairs:
            # ban token b after token a (prevent_newline_after_colon)
            hit = (prev == a)
            last_logits = last_logits.at[:, b_id].add(
                jnp.where(hit, NEG_INF_LOGIT, 0.0))
        if top_p_decay > 0.0 and top_p > 0.0:
            step_ix = (pos - prefill).astype(jnp.float32)
            top_p_t = jnp.maximum(top_p * top_p_decay ** step_ix,
                                  top_p_bound)
        else:
            top_p_t = top_p
        nxt = sample(last_logits, sub, top_k=top_k, top_p=top_p_t,
                     temperature=temperature, greedy=greedy)
        in_prompt = pos < prompt_lengths
        cur = jax.lax.dynamic_index_in_dim(tokens, pos, 1, keepdims=False)
        new_tok = jnp.where(in_prompt, cur, nxt.astype(tokens.dtype))
        new_tok = jnp.where(done, cur, new_tok)
        tokens = jax.lax.dynamic_update_slice(
            tokens, new_tok[:, None], (0, pos)
        )
        if return_log_probs:
            lp = jax.nn.log_softmax(last_logits.astype(jnp.float32), axis=-1)
            picked = jnp.take_along_axis(
                lp, new_tok[:, None].astype(jnp.int32), axis=-1
            )[..., 0]
            log_probs = jax.lax.dynamic_update_slice(
                log_probs, picked[:, None], (0, pos)
            )
        if eod_id is not None:
            done = done | ((new_tok == eod_id) & ~in_prompt)
        for s in extra_stop_ids:
            done = done | ((new_tok == s) & ~in_prompt)
        for a, b_id in stop_pairs:
            done = done | ((prev == a) & (new_tok == b_id) & ~in_prompt)
        logits, caches = _forward_with_cache(
            model, params, new_tok[:, None], caches, pos
        )
        return (pos + 1, tokens, caches, logits[:, -1], log_probs, done, key)

    state = (jnp.int32(prefill), tokens, caches, last_logits, log_probs,
             jnp.zeros((b,), bool), rng_key)
    pos, tokens, caches, last_logits, log_probs, done, _ = (
        jax.lax.while_loop(cond, body, state)
    )
    return tokens, pos, log_probs


def greedy_generate(model, params, prompt_tokens, prompt_lengths,
                    max_new_tokens, eod_id=None):
    return generate_tokens(
        model, params, prompt_tokens, prompt_lengths, jax.random.PRNGKey(0),
        max_new_tokens=max_new_tokens,
        min_prompt_len=int(prompt_lengths.min()),
        greedy=True, eod_id=eod_id,
    )


# ---------------------------------------------------------------------------
# Beam search (reference: generation.py:288-416 + beam_utils.py)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    # length_penalty is deliberately TRACED (it only feeds a trailing
    # scalar power): the server reads it per request, and a static arg
    # would recompile the whole decode for every new value.
    static_argnames=("model", "beam_size", "max_new_tokens", "eod_id"),
)
def beam_search(
    model,
    params,
    prompt_tokens: jax.Array,     # [1, prompt_len]
    *,
    beam_size: int,
    max_new_tokens: int,
    eod_id: int,
    length_penalty: float = 1.0,
):
    """Single-prompt beam search.  Beams ride the batch axis; the KV cache
    is gathered along batch on every reorder (the reference mutates
    per-layer cache tensors in place, generation.py:288-416).

    Jitted with a ``lax.while_loop`` decode (early-exits when every beam
    hit EOD), like ``generate_tokens``: one compile instead of a Python
    step loop — and with a mesh active the GSPMD activation constraints
    compile for any beam count, so beams serve under tp-sharded params
    exactly like sampling (the reference serves beams through the same
    TP x PP path, api.py:147-201)."""
    cfg = model.cfg
    _, prompt_len = prompt_tokens.shape
    total = prompt_len + max_new_tokens
    B = beam_size

    # prefill ONCE at batch 1, then broadcast the caches across the beam
    # axis (all beams share the prompt; a tiled prefill would do B-fold
    # redundant FLOPs and cache writes)
    caches = init_kv_caches(cfg, 1, total)
    logits, caches = _forward_with_cache(
        model, params, prompt_tokens, caches, 0
    )
    caches = [dict(c,
                   k=jnp.broadcast_to(c["k"], (B,) + c["k"].shape[1:]),
                   v=jnp.broadcast_to(c["v"], (B,) + c["v"].shape[1:]))
              for c in caches]
    lp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)

    tokens = jnp.tile(prompt_tokens, (B, 1))
    tokens = jnp.concatenate(
        [tokens, jnp.zeros((B, max_new_tokens), tokens.dtype)], axis=1
    )

    # first expansion: take top beam_size from beam 0 only
    top_lp, top_idx = jax.lax.top_k(lp[0], B)
    scores = top_lp
    tokens = tokens.at[:, prompt_len].set(top_idx.astype(tokens.dtype))
    done = top_idx == eod_id
    # per-hypothesis token count (prompt + own generated tokens, incl. a
    # closing EOD; NOT the filler EODs finished beams keep appending)
    hyp_len = jnp.full((B,), prompt_len + 1, jnp.int32)

    V = lp.shape[-1]

    def cond(state):
        pos, _, _, _, done, _ = state
        return (pos < total - 1) & ~jnp.all(done)

    def body(state):
        pos, tokens, caches, scores, done, hyp_len = state
        cur = jax.lax.dynamic_index_in_dim(tokens, pos, 1, keepdims=True)
        logits, caches = _forward_with_cache(model, params, cur, caches,
                                             pos)
        lp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
        # finished beams only propose EOD with frozen score
        lp = jnp.where(done[:, None],
                       jnp.full_like(lp, -1e9).at[:, eod_id].set(0.0), lp)
        cand = scores[:, None] + lp               # [B, V]
        flat_scores, flat_idx = jax.lax.top_k(cand.reshape(-1), B)
        beam_src = flat_idx // V
        tok_next = (flat_idx % V).astype(tokens.dtype)

        tokens = jax.lax.dynamic_update_slice(
            tokens[beam_src], tok_next[:, None], (0, pos + 1)
        )
        caches = [dict(c, k=c["k"][beam_src], v=c["v"][beam_src])
                  for c in caches]
        was_done = done[beam_src]
        hyp_len = hyp_len[beam_src] + jnp.where(was_done, 0, 1)
        done = was_done | (tok_next == eod_id)
        return (pos + 1, tokens, caches, flat_scores, done, hyp_len)

    state = (jnp.int32(prompt_len), tokens, caches, scores, done, hyp_len)
    _, tokens, _, scores, _, hyp_len = jax.lax.while_loop(cond, body, state)

    # length-penalised final ranking (reference beam_utils score/len**alpha),
    # normalized by each hypothesis's OWN length so a beam's rank never
    # depends on when the other beams finished
    final = scores / (hyp_len.astype(jnp.float32) ** length_penalty)
    order = jnp.argsort(-final)
    return tokens[order], final[order]
