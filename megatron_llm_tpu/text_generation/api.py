"""High-level generation API.

Reference: ``megatron/text_generation/api.py`` —
``generate_and_post_process`` (:19) / ``beam_search_and_post_process``
(:147).  The reference broadcasts inputs from rank 0 to all ranks before
running (api.py:70-146); under a single JAX controller there is nothing to
broadcast — the functions are plain calls.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from megatron_llm_tpu.text_generation.generation import (
    beam_search,
    generate_tokens,
)


def _tokenize_prompts(tokenizer, prompts: Sequence[str], pad_id: int,
                      add_bos: bool = False):
    tokenized = [tokenizer.tokenize(p) for p in prompts]
    if add_bos:
        # reference tokenization.py prepends eod as the BOS sentinel for
        # GPT-family tokenizers; use a real bos id when the tokenizer has
        # one
        bos = getattr(tokenizer, "bos_token_id", None)
        if bos is None:
            bos = tokenizer.eod
        tokenized = [[bos] + t for t in tokenized]
    lengths = [len(t) for t in tokenized]
    max_len = max(lengths)
    arr = np.full((len(prompts), max_len), pad_id, np.int32)
    for i, t in enumerate(tokenized):
        arr[i, : len(t)] = t
    return jnp.asarray(arr), jnp.asarray(lengths, jnp.int32)


def _single_token_id(tokenizer, text, quiet=False):
    # Resolve ``text`` to the single token id it produces
    # mid-sequence.  BPE vocabs encode '\n' to one id; sentencepiece-
    # style tokenizers can encode it to [] (stripped) or to multiple /
    # context-dependent ids, where blindly taking ids[-1] would make
    # the stop/ban target the wrong id and silently never fire.
    ids = tokenizer.tokenize(text)
    if len(ids) == 1:
        return ids[0]
    # Retry with a leading anchor: if 'a'+text adds exactly one id
    # over 'a', that id is the real mid-sequence encoding.  Guarded:
    # int-only tokenizers (NullTokenizer) raise on alphabetic input,
    # and the graceful answer there is the old None-disable.
    try:
        anchor = tokenizer.tokenize("a")
        ctx = tokenizer.tokenize("a" + text)
    except Exception:
        anchor = ctx = None
    if ctx is not None and len(ctx) == len(anchor) + 1 \
            and ctx[:len(anchor)] == anchor:
        return ctx[-1]
    if not quiet:  # "\n\n" callers expect multi-token encodings
        import warnings
        warnings.warn(
            f"tokenizer encodes {text!r} to {len(ids)} ids "
            f"({ids}); stop/ban rules targeting it are "
            + ("disabled" if not ids
               else "approximate (using last id)"))
    return ids[-1] if ids else None


def resolve_stop_rules(tokenizer, stop_on_eol=False,
                       stop_on_double_eol=False,
                       prevent_newline_after_colon=False):
    """(extra_stop_ids, stop_pairs, ban_pairs) token-id rules for the
    server's eol knobs — shared by the batch ``generate`` path and the
    continuous-batching engine (serving/engine.py), so both stop/ban on
    exactly the same ids."""
    extra_stop, stop_pairs, ban_pairs = [], [], []
    if stop_on_eol or stop_on_double_eol:
        eol = _single_token_id(tokenizer, "\n")
        if stop_on_eol and eol is not None:
            extra_stop.append(eol)
        if stop_on_double_eol:
            # quiet: "\n\n" legitimately encodes to two eol ids on many
            # tokenizers, and that case is fully handled by stop_pairs.
            dbl = _single_token_id(tokenizer, "\n\n", quiet=True)
            if dbl is not None and dbl != eol:
                extra_stop.append(dbl)      # single '\n\n' merge token
            if eol is not None:
                stop_pairs.append((eol, eol))  # two consecutive newlines
    if prevent_newline_after_colon:
        colon = _single_token_id(tokenizer, ":")
        eol = _single_token_id(tokenizer, "\n")
        if colon is not None and eol is not None:
            ban_pairs.append((colon, eol))
    return tuple(extra_stop), tuple(stop_pairs), tuple(ban_pairs)


def generate(
    model,
    params,
    tokenizer,
    prompts: Sequence[str],
    tokens_to_generate: int = 64,
    *,
    top_k: int = 0,
    top_p: float = 0.0,
    temperature: float = 1.0,
    greedy: bool = False,
    seed: int = 0,
    return_log_probs: bool = False,
    batch_times_seqlen_threshold: int = 512,
    add_bos: bool = False,
    top_p_decay: float = 0.0,
    top_p_bound: float = 0.0,
    stop_on_eol: bool = False,
    stop_on_double_eol: bool = False,
    prevent_newline_after_colon: bool = False,
    rolling_cache: Optional[bool] = None,
    cache_len: Optional[int] = None,
    int8_kv_cache: bool = False,
):
    """Returns (texts, token_lists, log_probs or None).

    ``cache_len``: minimum KV-cache allocation (slots); decode masks
    the unused tail, outputs are identical
    (tests/test_generation.py::test_cache_len_padding_is_invisible).
    Decouples per-step attention cost from max_new_tokens.  Does not
    by itself avoid recompiles —
    the jit keys on prompt shape and tokens_to_generate.

    ``batch_times_seqlen_threshold``: micro-batch the prefill forward
    above this batch*seqlen (reference
    ``--inference_batch_times_seqlen_threshold``, default 512).

    ``rolling_cache``: None (default) auto-enables the O(window) ring
    KV cache exactly when it saves memory — a sliding-window model
    decoding past its window; logits are identical either way
    (tests/test_rolling_kv_cache.py)."""
    pad = getattr(tokenizer, "pad", 0) or 0
    eod = getattr(tokenizer, "eod", None)
    toks, lens = _tokenize_prompts(tokenizer, prompts, pad, add_bos)
    if rolling_cache is None:
        window = model.cfg.sliding_window_size
        rolling_cache = (window is not None
                         and toks.shape[1] + tokens_to_generate > window)
    if int8_kv_cache and rolling_cache:
        # checked AFTER the auto-enable above: the ring cache is already
        # O(window) and has no int8 variant — say so instead of silently
        # serving bf16 KV
        print(" > NOTE: int8_kv_cache is ignored for this request — the "
              "rolling (sliding-window) cache engaged and has no int8 "
              "variant; KV stays bf16", flush=True)

    extra_stop, stop_pairs, ban_pairs = resolve_stop_rules(
        tokenizer, stop_on_eol=stop_on_eol,
        stop_on_double_eol=stop_on_double_eol,
        prevent_newline_after_colon=prevent_newline_after_colon)

    out_tokens, _, log_probs = generate_tokens(
        model, params, toks, lens, jax.random.PRNGKey(seed),
        max_new_tokens=tokens_to_generate,
        min_prompt_len=int(lens.min()),
        top_k=top_k, top_p=top_p, temperature=temperature, greedy=greedy,
        eod_id=eod, return_log_probs=return_log_probs,
        batch_times_seqlen_threshold=batch_times_seqlen_threshold,
        top_p_decay=top_p_decay, top_p_bound=top_p_bound,
        extra_stop_ids=tuple(extra_stop), stop_pairs=tuple(stop_pairs),
        ban_pairs=tuple(ban_pairs), rolling_cache=bool(rolling_cache),
        cache_len=cache_len,
        int8_kv_cache=int8_kv_cache and not rolling_cache,
    )
    out_tokens = np.asarray(out_tokens)
    stop_set = set(extra_stop)
    if eod is not None:
        stop_set.add(eod)
    pair_set = set(stop_pairs)
    texts, token_lists = [], []
    for i, row in enumerate(out_tokens):
        row = row.tolist()
        # trim at the first stop condition after the prompt (eod, an
        # extra stop id, or a stop bigram) — rows frozen by a stop leave
        # the rest of the row at its zero init, which must not reach the
        # caller as detokenized id-0 tokens
        start = int(lens[i])
        end = len(row)
        for j in range(start, len(row)):
            if row[j] in stop_set or (j > 0
                                      and (row[j - 1], row[j]) in pair_set):
                end = j + 1
                break
        row = row[:end]
        token_lists.append(row)
        texts.append(tokenizer.detokenize(row))
    return texts, token_lists, (np.asarray(log_probs) if return_log_probs
                                else None)


def generate_and_post_process(
    model, params, tokenizer, prompts,
    tokens_to_generate: int = 64,
    return_output_log_probs: bool = False,
    top_k_sampling: int = 0,
    top_p_sampling: float = 0.0,
    temperature: float = 1.0,
    random_seed: int = 0,
    batch_times_seqlen_threshold: int = 512,
    add_BOS: bool = False,
    top_p_decay: float = 0.0,
    top_p_bound: float = 0.0,
    stop_on_eol: bool = False,
    stop_on_double_eol: bool = False,
    prevent_newline_after_colon: bool = False,
    int8_kv_cache: bool = False,
    **_unused,
):
    """Reference signature compatibility (api.py:19-69)."""
    texts, tokens, log_probs = generate(
        model, params, tokenizer, prompts, tokens_to_generate,
        top_k=top_k_sampling, top_p=top_p_sampling, temperature=temperature,
        greedy=(top_k_sampling == 1), seed=random_seed,
        return_log_probs=return_output_log_probs,
        batch_times_seqlen_threshold=batch_times_seqlen_threshold,
        add_bos=add_BOS, top_p_decay=top_p_decay, top_p_bound=top_p_bound,
        stop_on_eol=stop_on_eol, stop_on_double_eol=stop_on_double_eol,
        prevent_newline_after_colon=prevent_newline_after_colon,
        int8_kv_cache=int8_kv_cache,
    )
    segments = [[tokenizer.detokenize([t]) for t in row] for row in tokens]
    return texts, segments, log_probs, tokens


def beam_search_and_post_process(
    model, params, tokenizer, prompts,
    tokens_to_generate: int = 64,
    beam_size: int = 4,
    length_penalty: float = 1.0,
    stop_token=None,
    add_BOS: bool = False,
    **_unused,
):
    """Reference: api.py:147-201 (batch of 1); ``stop_token`` overrides
    eod as the beam termination token (the server's stop_token knob)."""
    assert len(prompts) == 1, "beam search supports a single prompt"
    toks, lens = _tokenize_prompts(tokenizer, prompts,
                                   getattr(tokenizer, "pad", 0) or 0,
                                   add_BOS)
    beams, scores = beam_search(
        model, params, toks[:1], beam_size=beam_size,
        max_new_tokens=tokens_to_generate,
        eod_id=(int(stop_token) if stop_token is not None
                else tokenizer.eod),
        length_penalty=length_penalty,
    )
    beams = np.asarray(beams)
    texts = [tokenizer.detokenize(b.tolist()) for b in beams]
    return texts, np.asarray(scores)
