"""What the serving probe loads for Nemotron-H: ``nemotron_h.py``'s plain
forward, and beside it a comparison of the LOGITS THE ENGINE'S OWN
PROGRAMS COMPUTED, over the engine's own state group and pages, and of
the RECURRENT STATE they leave in the request's slot, with the
reference's.

The method is ``granite_probe.py``'s, whose docstring says why and how
(``keye_probe.py``'s taps over ``engine._prefill_step`` and
``engine._decode_step`` in ``mellum_probe.py``'s form: the engine's
experts given to the reference at EVERY tapped row, a tapped prefill
position the FIRST ROW OF A CHUNK, each group of positions held by its
median and each position by itself, the router's slack, NaN back to the
harness beyond any limit; and Granite's comparison of the state in every
state-space layer, the slowest quarter of the first layer's heads held
tightly enough that ``S`` kept in bf16 fails).  That file is loaded here
as a private copy with its reference replaced by ``nemotron_h.py``.

What differs:

* THE PROBE DECODES LONG, as the cell's traffic does: 1,536 prompt
  tokens in three chunks of 512 and 256 answer tokens, so the state is
  carried in its slot across two chunk boundaries and 255 steps.  Tapped:
  the first row of chunks two and three (512, 1024), the prompt's last
  row (1535) and ALL 255 decode steps (1536-1790), the last among them:
  the decode step at one live row of 64 slots is what the cell times at
  64 live rows, the same program;
* THE ROWS BEFORE A TAPPED PREFILL ROW ARE GIVEN THE ENGINE'S EXPERTS
  TOO (``probe.context_rows`` of them, ``engine_against_reference``
  here).  A layer is one sublayer, so what an expert layer gives a token
  goes straight into the next mixer, whose convolution reads the three
  tokens before a row and whose fast heads weigh the last few; where the
  reference's float32 router seats another expert than the engine's bf16
  one at a row just BEFORE a tapped row, the tapped row reads it.  Read
  on the chip (PR 44, seed 2147484101, no context rows): row 512 read
  0.154 and row 1024 0.0124, the prompt's last row 0.048, the first
  decode steps 0.039, 0.037, 0.017, 0.014 and the 250 after them
  0.0112-0.0149: the distance falls to the floor as soon as the few rows
  before a row are rows whose experts were given, and stands at it where
  no choice was turned nearby.  So each tapped prefill row's
  ``context_rows`` predecessors are served as prefixes of their own (a
  row's experts: its chunk's record less the record of the same chunk
  one row shorter) and given to the reference, and compared are the
  tapped rows alone;
* the routing record's rows are the EXPERT layers (six of the fourteen),
  over all 128 experts the router scores, of which the program computes
  the held 64; ``nemotron_h.py`` counts its ``routing`` and ``forced``
  by expert layer too;
* the program's pattern, state-space sizes, groups, router and share of
  experts are compared with the file's here (``shape_as_run``), in the
  published config's own keys: ``harness/shape.py`` reports none of
  them.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
LETTERS = {"mamba": "M", "attention": "*", "moe": "E"}


def _load(name: str, as_name: str):
    spec = importlib.util.spec_from_file_location(
        as_name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


plain = _load("nemotron_h", "bench_nemotron_h_plain")
# granite_probe.py's WithStates, taps and state comparison, over its own
# private copies of mellum_probe.py and keye_probe.py; its ``plain`` is
# looked up when called
shared = _load("granite_probe", "bench_nemotron_h_shared_probe")
shared.plain = plain
reference = shared.reference

position_losses = plain.position_losses
cross_entropy = plain.cross_entropy
LastRows = shared.LastRows
engine_of = shared.engine_of
engine_states = shared.engine_states
# keye_probe.py's taps and distance, through the two private copies
_taps = shared.shared.shared
state_against_reference = shared.state_against_reference
settings_as_run = shared.settings_as_run


def shape_as_run(mcfg) -> dict:
    """The published config's keys that ``harness/shape.py`` does not
    report, as the program was really given them."""
    return {
        "hybrid_override_pattern": "".join(
            LETTERS[t] for t in mcfg.layer_types)[:mcfg.num_layers],
        "position_embedding": mcfg.position_embedding_type.value,
        "tie_word_embeddings": bool(mcfg.tie_embed_logits),
        "head_dim": mcfg.head_dim,
        "layer_norm_epsilon": mcfg.layernorm_epsilon,
        "mlp_hidden_act": mcfg.mlp_activation,
        "gated": mcfg.glu_activation,
        "mamba_num_heads": mcfg.mamba_n_heads,
        "mamba_head_dim": mcfg.mamba_d_head,
        "ssm_state_size": mcfg.mamba_d_state,
        "n_groups": mcfg.mamba_n_groups,
        "conv_kernel": mcfg.mamba_d_conv,
        "chunk_size": mcfg.mamba_chunk_size,
        "use_conv_bias": bool(mcfg.mamba_conv_bias),
        "moe_intermediate_size": mcfg.expert_hidden_size,
        "moe_shared_expert_intermediate_size": (
            mcfg.moe_shared_experts * mcfg.expert_hidden_size),
        "router": (mcfg.moe_score_function, bool(mcfg.moe_choice_bias)),
        "routed_scaling_factor": mcfg.moe_routed_scale,
        "norm_topk_prob": bool(mcfg.norm_topk_prob),
        "n_routed_experts": mcfg.num_experts,
        "experts_first": mcfg.moe_experts_first,
        "routed_experts": mcfg.routed_experts,
    }


def file_says(cfg: dict) -> dict:
    """The same keys as the configuration file states them (the router's
    count is the PUBLISHED ``n_routed_experts``; what the file cannot
    say in a published key is what this model is)."""
    return {**cfg, "position_embedding": "none", "gated": None,
            "router": ("sigmoid", True),
            "routed_experts": cfg["published"]["n_routed_experts"]}


def engine_logits(engine, tokens, n_prompt: int, ends):
    """The engine's own logits and routing records over the probe's
    sequence ``tokens``: the prompt submitted again for as many answer
    tokens as the probe asked, then the prefix ending at each position
    of ``ends`` for one token, one request at a time (a model with
    state-space layers adopts no prefix: each is prefilled whole)."""
    from megatron_llm_tpu.serving.request import SamplingParams

    tokens = [int(t) for t in tokens]
    taps = _taps.Taps(engine)
    with taps.laid():
        for end, n_new in ([(n_prompt, len(tokens) - n_prompt + 1)]
                           + [(int(t) + 1, 1) for t in ends]):
            req = engine.submit(tokens[:end], SamplingParams(
                max_new_tokens=n_new, temperature=0.0))
            req.result(timeout=300)
            if taps.answer is None:
                taps.answer = list(req.out_tokens)
    return taps


def engine_against_reference(engine, weights, cfg: dict, p: dict, tokens,
                             taps=None, faults=frozenset()):
    """The engine's tapped logits over ``tokens`` against the
    reference's, the engine's experts given to the reference at every
    tapped row AND at the ``probe.context_rows`` rows before each tapped
    prefill row (module docstring).  Returns (the report, whether every
    limit holds, the reference's logits at the answer positions as the
    engine's logits were held to them, the reference's own router
    margins an expert layer, the taps)."""
    tokens = np.asarray(tokens, np.int32)
    n_prompt = len(tokens) - int(p["answer_tokens"]) + 1
    top_k = int(cfg["num_experts_per_tok"])
    C = int(engine.config.prefill_chunk)
    firsts = shared.shared.tapped_rows(engine, n_prompt,
                                       int(p["prefill_rows"]))
    compared = firsts + [n_prompt - 1]
    context = sorted({t - k for t in compared
                      for k in range(1, int(p["context_rows"]) + 1)
                      if t - k >= 0} - set(compared))
    decode_rows = list(range(n_prompt, len(tokens)))
    if taps is None:
        # a row's experts: its chunk's record less the same chunk's one
        # row shorter (a chunk's first row: its record as it is)
        wanted = set(context) | set(compared)
        ends = sorted((wanted | {t - 1 for t in wanted if t % C})
                      - {n_prompt - 1})
        taps = engine_logits(engine, tokens, n_prompt, ends)
    rows = np.asarray(compared + decode_rows)
    own, margins = [], []
    reference.forward_logits(weights, cfg, tokens, rows=rows, faults=faults,
                             routing=own, router_margins=margins)
    given, unknown, differing, known = {}, [], 0, 0
    for t in context + compared + decode_rows:
        theirs = taps.experts(t, top_k)
        if theirs is None:
            unknown.append(t)
            continue
        known += 1
        for i, e in enumerate(theirs):
            differing += sorted(own[i][0][t].tolist()) != e
            given.setdefault(i, {})[t] = e
    routed = []
    logits = reference.forward_logits(weights, cfg, tokens, rows=rows,
                                      faults=faults, forced=given,
                                      routing=routed)
    slack = max((float(routed[i][1][t]) for i, at_t in given.items()
                 for t in at_t), default=0.0)
    at = {int(t): i for i, t in enumerate(rows)}
    tolerance = float(p["logits_apart_tolerance"])
    at_one = float(p["position_apart_tolerance"])
    margin = float(p["margin"])
    report = {"tolerance": tolerance, "position_tolerance": at_one,
              "router_slack_tolerance": float(p["router_slack_tolerance"]),
              "router_slack_worst": slack,
              "context_rows": len(context),
              "experts_differ_share": differing / max(
                  len(margins) * known, 1),
              "experts_unknown_at": unknown}
    # the engine answered as it answered the probe, at every position it
    # was asked to tap, and each step's own token is its tap's choice
    report["answered_alike"] = (taps.answer[:-1]
                                == [int(t) for t in tokens[n_prompt:]])
    report["tapped_every_row"] = (set(taps.prefill) >= set(compared)
                                  and sorted(taps.decode) == decode_rows)
    deficit = [float(taps.decode[t].max()
                     - taps.decode[t][taps.step_token[t]])
               for t in sorted(taps.decode)]
    report["step_token_deficit_worst"] = max(deficit, default=0.0)
    within = (report["answered_alike"] and report["tapped_every_row"]
              and not unknown
              and report["step_token_deficit_worst"] <= margin
              and slack <= report["router_slack_tolerance"])
    for name, taken, positions in (("prefill", taps.prefill, compared),
                                   ("decode", taps.decode, decode_rows)):
        positions = [t for t in positions if t in taken]
        apart = np.asarray(_taps.positions_apart(
            jnp.asarray(np.stack([taken[t] for t in positions])),
            logits[jnp.asarray([at[t] for t in positions])]))
        beyond = [t for t, a in zip(positions, apart) if not a <= at_one]
        within = (within and not beyond
                  and bool(np.median(apart) <= tolerance))
        report[name] = {
            "positions": len(positions), "beyond": beyond,
            "median": float(np.median(apart)), "worst": float(apart.max()),
            "apart": [float(f"{a:.4g}") for a in apart]}
    return report, bool(within), logits[len(firsts):], margins, taps


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``nemotron_h.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position and its
    state in every state-space layer, the engine's experts given to the
    reference: what comes back is that pass, NaN when the engine is
    apart."""
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
    # a rehearsal runs tiny widths by design, and is never correct
    within = (within and report["state"]["within"]
              and (rehearsed or not differs))
    if router_margins is not None:
        router_margins.extend(margins)
    stats = engine.stats()
    print(json.dumps({"note": "probe_logits", "positions": len(tokens),
                      "of": "the engine's programs over its state group "
                            "and pages",
                      "differs_from_the_file": differs,
                      "ssm_rows_live": stats.get("ssm_rows_live"),
                      "moe_assignments_held":
                          stats.get("moe_assignments_held"),
                      "moe_assignments": stats.get("moe_assignments"),
                      "moe_experts_touched_held":
                          stats.get("moe_experts_touched_held"),
                      "moe_expert_tiles": stats.get("moe_expert_tiles"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
