"""What the serving probe loads for LFM2: ``lfm2.py``'s plain forward, and
beside it a comparison of the LOGITS THE ENGINE'S OWN PROGRAMS COMPUTED,
over the engine's own pool (64-wide heads, two a row) and state group at
the timed 128 slots, and of the COLUMNS each conv layer leaves in the
request's slot, with the reference's.

The method is ``nemotron_h_probe.py``'s, whose docstring says why and how
(``keye_probe.py``'s taps over ``engine._prefill_step`` and
``engine._decode_step`` in ``mellum_probe.py``'s form: the engine's
experts given to the reference at EVERY tapped row, a tapped prefill
position the FIRST ROW OF A CHUNK, each group of positions held by its
median and each position by itself, the router's slack, NaN back to the
harness beyond any limit; Nemotron's long decode, 1,536 prompt tokens in
three chunks of 512 and 256 answer tokens, every decode step tapped, and
the engine's experts given at the ``probe.context_rows`` rows BEFORE each
tapped prefill row too: a conv layer reads the two tokens before a row,
eleven of them one over the other, so an expert seated otherwise at a row
just before a tapped row is read by it).  That file is loaded here as a
private copy with its reference replaced by ``lfm2.py``.

What differs:

* THE STATE IS TWO COLUMNS A LAYER, not a recurrence's sum: the probe's
  sequence is served once more and what its last step leaves in the
  request's slot, ``conv_state`` ``[2, hidden]`` of every conv layer, is
  compared with the reference's ``B * X`` at the sequence's last two
  tokens: per layer the root mean square of engine minus reference over
  the reference's, no layer beyond ``probe.state_apart_tolerance`` and
  the FIRST conv layer (whose input is the embedding alone, so that its
  columns carry one rounding and no layer's noise before it) within
  ``probe.state_first_layer_apart_tolerance``, which is what tells bf16
  columns from columns kept in float8 (``lfm2_controls.py``).  And the
  decode steps' MEDIAN has a limit of its own
  (``probe.decode_median_tolerance``): over 255 positions it stands
  within a hundredth of itself from seed to seed, which three prefill
  rows' median does not, and the choice bias added to the gates moves it
  by a tenth;
* the routing record's rows are the SPARSE layers (12 of the 14), over
  all 32 experts, all held;
* the program's pattern, taps, dense layers, router and normaliser are
  compared with the file's here (``shape_as_run``), in the published
  config's own keys: ``harness/shape.py`` reports none of them; and the
  pool's bytes a token an attention layer are printed
  (``kv_bytes_a_token_a_layer``: 2,048), since ``kv_held_bytes_per_token``
  reads a model with a window group only.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = {"conv": "conv", "attention": "full_attention"}


def _load(name: str, as_name: str):
    spec = importlib.util.spec_from_file_location(
        as_name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


plain = _load("lfm2", "bench_lfm2_plain")
# nemotron_h_probe.py's engine_against_reference (context rows, every
# decode step), over its own private copies of granite_probe.py,
# mellum_probe.py and keye_probe.py; granite_probe.py's ``plain`` is
# looked up when its WithStates is called
shared = _load("nemotron_h_probe", "bench_lfm2_shared_probe")
shared.plain = plain
shared.shared.plain = plain
reference = shared.reference

position_losses = plain.position_losses
cross_entropy = plain.cross_entropy
LastRows = shared.LastRows
engine_of = shared.engine_of
engine_against_reference = shared.engine_against_reference
settings_as_run = shared.settings_as_run


def shape_as_run(mcfg) -> dict:
    """The published config's keys that ``harness/shape.py`` does not
    report, as the program was really given them."""
    period = list(mcfg.layer_period)
    types = (period * (mcfg.num_layers // len(period)))[:mcfg.num_layers]
    return {
        "layer_types": [NAMES.get(t, t) for t in types],
        "conv_L_cache": mcfg.conv_taps,
        "conv_bias": bool(mcfg.conv_mixer_bias),
        "num_dense_layers": mcfg.moe_first_dense_layers,
        "num_experts": mcfg.num_experts,
        "moe_intermediate_size": mcfg.expert_hidden_size,
        "norm_eps": mcfg.layernorm_epsilon,
        "norm_topk_prob": bool(mcfg.norm_topk_prob),
        "routed_scaling_factor": mcfg.moe_routed_scale,
        "use_expert_bias": bool(mcfg.moe_choice_bias),
        "router": (mcfg.moe_score_function, mcfg.moe_gate_norm_eps,
                   bool(mcfg.moe_gate_norm_added)),
        "head_dim": mcfg.head_dim,
        "qk_norm_per_head": bool(mcfg.qk_norm_per_head),
        "position_embedding": mcfg.position_embedding_type.value,
        "tie_word_embeddings": bool(mcfg.tie_embed_logits),
        "shared_experts": mcfg.moe_shared_experts,
    }


def file_says(cfg: dict) -> dict:
    """The same keys as the configuration file states them (what the
    file cannot say in a published key is what this model is)."""
    return {**cfg, "router": ("sigmoid", 1e-6, True), "head_dim": 64,
            "qk_norm_per_head": True, "position_embedding": "rotary",
            "tie_word_embeddings": True, "shared_experts": 0}


def engine_states(engine, tokens, n_prompt: int):
    """(the columns ``[taps - 1, hidden]`` (float32, on the host) each
    conv layer of the engine is left with by ``tokens``, the probe's
    sequence served once more: the prompt prefilled in chunks and every
    answer token but the last stepped through, all in one slot; whether
    the engine answered as the sequence says)."""
    from megatron_llm_tpu.ops import paged_kv
    from megatron_llm_tpu.serving.request import SamplingParams

    tokens = [int(t) for t in tokens]
    slots, inner = [], engine._prefill_step

    def tapped(params, pages, chunk, start, valid, table):
        slots.append(int(np.asarray(table[paged_kv.STATE])[0]))
        return inner(params, pages, chunk, start, valid, table)

    engine._prefill_step = tapped
    try:
        req = engine.submit(tokens[:n_prompt], SamplingParams(
            max_new_tokens=len(tokens) - n_prompt + 1, temperature=0.0))
        req.result(timeout=300)
    finally:
        engine._prefill_step = inner
    # a finished request's columns stay in its slot until the slot's next
    # request starts from zeros; nothing else is being served
    with engine._st.pool_lock:
        states = [np.asarray(pool["conv_state"][slots[-1]], np.float32)
                  for pool in engine._st.pages if paged_kv.is_state(pool)]
    return states, list(req.out_tokens)[:-1] == tokens[n_prompt:]


def state_against_reference(engine, p: dict, tokens, states=None) -> dict:
    """The report of the columns' comparison (``within`` among its keys)
    against ``reference.states``, which the logits' comparison just
    left; ``states``: what ``engine_states`` gave, where it was asked
    already."""
    n_prompt = len(tokens) - int(p["answer_tokens"]) + 1
    mine, alike = states or engine_states(engine, tokens, n_prompt)
    theirs = [np.asarray(s, np.float32) for s in reference.states or []]
    apart = [float(np.sqrt(np.sum((a - b) ** 2) / np.sum(b ** 2)))
             for a, b in zip(mine, theirs)]
    tolerance = float(p["state_apart_tolerance"])
    first = float(p["state_first_layer_apart_tolerance"])
    return {"layers": len(mine), "answered_alike": alike,
            "tolerance": tolerance, "first_layer_tolerance": first,
            "worst": max(apart, default=None),
            "columns_apart": [float(f"{a:.4g}") for a in apart],
            "within": bool(alike and mine and len(mine) == len(theirs)
                           and max(apart) <= tolerance
                           and apart[0] <= first)}


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``lfm2.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position and its
    columns in every conv layer, the engine's experts given to the
    reference: what comes back is that pass, NaN when the engine is
    apart."""
    from megatron_llm_tpu.ops import paged_kv

    tokens = np.asarray(tokens, np.int32)
    engine = engine_of(weights.p)
    p = settings_as_run(cfg, len(tokens))
    rehearsed = p["prompt_tokens"] != cfg["probe"]["prompt_tokens"]
    as_run = shape_as_run(engine.model.cfg)
    says = file_says(cfg)
    differs = sorted(k for k, v in as_run.items() if says.get(k) != v)
    if turned:
        # harness/probe.py turns ties only where turned_ties_allowed > 0
        raise NotImplementedError(
            "this configuration turns no tie: the engine's own experts "
            "are given to the reference instead")
    weights.use({**cfg, **as_run})
    report, within, answers, margins, _ = engine_against_reference(
        engine, weights, weights.cfg, p, tokens)
    report["state"] = state_against_reference(engine, p, tokens)
    # the 255 decode steps' median stands steadier than three prefill
    # rows' (0.0252-0.0255 over five seeds) and has a limit of its own
    report["decode_median_tolerance"] = float(p["decode_median_tolerance"])
    # a rehearsal runs tiny widths by design, and is never correct
    within = (within and report["state"]["within"]
              and report["decode"]["median"]
              <= report["decode_median_tolerance"]
              and (rehearsed or not differs))
    if router_margins is not None:
        router_margins.extend(margins)
    stats = engine.stats()
    plan = engine._cache
    print(json.dumps({"note": "probe_logits", "positions": len(tokens),
                      "of": "the engine's programs over its pool of "
                            "64-wide heads and its state group",
                      "differs_from_the_file": differs,
                      "slots": int(engine.config.num_slots),
                      "kv_bytes_a_token_a_layer": paged_kv.block_bytes(
                          engine._st.pages) // (
                          int(engine.config.block_size)
                          * max(1, (plan.groups or ()).count(paged_kv.FULL))),
                      "state_bytes_a_slot": plan.state_bytes_per_slot,
                      "paged_kernel": engine.paged_kernel,
                      "prefill_kernel": engine.prefill_kernel,
                      "conv_rows_live": stats.get("conv_rows_live"),
                      "conv_tokens": stats.get("conv_tokens"),
                      "moe_assignments": stats.get("moe_assignments"),
                      "moe_experts_touched_held":
                          stats.get("moe_experts_touched_held"),
                      "moe_expert_tiles": stats.get("moe_expert_tiles"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
