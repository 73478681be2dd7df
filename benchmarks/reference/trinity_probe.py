"""What the serving probe loads for Trinity: ``trinity.py``'s plain
forward, and beside it a comparison of the LOGITS THE ENGINE'S OWN
PROGRAMS COMPUTED, over the engine's own two-group pool, with the
reference's.

The method is ``keye_probe.py``'s, whose docstring says why and how (taps
over ``engine._prefill_step`` and ``engine._decode_step`` while this file
submits requests of its own through ``engine.submit``; each group of
tapped positions held by its MEDIAN within
``probe.logits_apart_tolerance`` and each position within
``probe.position_apart_tolerance``; the engine's experts GIVEN to the
reference where a bf16 router's close choice is not the float32 one's,
within ``probe.router_slack_tolerance``; NaN back to the harness beyond
any limit), in the form ``mellum_probe.py`` gave it, which is loaded here
as a private copy with its reference replaced by ``trinity.py``:

* the engine's experts are given at EVERY tapped row in the pass the
  engine is held to (``EveryRowGiven``);
* the decode step without its sampler hands each layer ITS GROUP'S block
  table (the two dense layers' among the window group's);
* a patterned model adopts no prefix, so a tapped prefill position is
  reached by prefilling its whole prefix again, and the tapped positions
  are the FIRST ROW OF A CHUNK (``tapped_rows``): of the
  ``probe.prefill_rows`` chunks before the prompt's last row.  At 6,144
  prompt tokens in twelve chunks of 512 those are 2048, 2560 ... 5632,
  then 6143 and the decode steps from 6144 on.  At every one of them two
  keys in three, or more, lie behind the window of 2,048 on the six
  window layers, whose pages went back to the allocator chunks ago, and
  the two full layers, WHICH CARRY NO POSITIONS, weigh keys thousands of
  tokens back by their content alone.

What differs from Mellum's and is Kanana's: the engine's routing record
has a row a SPARSE layer (the two leading dense layers route nothing),
over all the experts the router scores, and ``trinity.py`` counts
``routing``, ``forced`` and ``router_margins`` the same way; a margin and
a given expert's slack are in the units of the choice (sigmoid score plus
bias).  What is this file's own: the program's layer types, window,
rotating types, dense layers, gate, output norms, router and SHARE of
experts are compared with the file's here (``shape_as_run`` against
``file_says``): ``harness/shape.py`` reports no list, knows experts only
as ``num_local_experts`` and holds ``intermediate_size`` to the dense
layers' width.

Only the rows that are compared are computed: what comes back can be
sliced from any answer position to the end, which is the one thing the
probe does with it.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os

import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = {"sliding": "sliding_attention", "full": "full_attention"}


def _load(name: str, as_name: str):
    spec = importlib.util.spec_from_file_location(
        as_name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


plain = _load("trinity", "bench_trinity_plain")
# mellum_probe.py's EveryRowGiven, tapped_rows and decode step, over a
# private copy of keye_probe.py; its ``plain`` is looked up when called
shared = _load("mellum_probe", "bench_trinity_shared_probe")
shared.plain = plain

position_losses = plain.position_losses
cross_entropy = plain.cross_entropy
LastRows = shared.LastRows
engine_of = shared.engine_of
engine_against_reference = shared.engine_against_reference
settings_as_run = shared.settings_as_run


def shape_as_run(mcfg) -> dict:
    """The published config's keys that ``harness/shape.py`` does not
    report (and four that no published key says), as the program was
    really given them."""
    period = [NAMES.get(t, t) for t in mcfg.layer_period]
    multiplier = mcfg.embedding_multiplier
    return {
        "layer_types": period * (mcfg.num_layers // len(period)),
        "head_dim": mcfg.head_dim,
        "tie_word_embeddings": bool(mcfg.tie_embed_logits),
        "mup_enabled": bool(multiplier is not None and math.isclose(
            multiplier, math.sqrt(mcfg.hidden_size), rel_tol=1e-12)),
        "num_dense_layers": mcfg.moe_first_dense_layers,
        "moe_intermediate_size": mcfg.expert_hidden_size,
        "num_experts": mcfg.num_experts,
        "num_shared_experts": mcfg.moe_shared_experts,
        "score_func": mcfg.moe_score_function,
        "route_norm": bool(mcfg.norm_topk_prob),
        "route_scale": mcfg.moe_routed_scale,
        "experts_first": mcfg.moe_experts_first,
        "routed_experts": mcfg.routed_experts,
        "choice_bias": bool(mcfg.moe_choice_bias),
        "rotating_layer_types": sorted({
            NAMES.get(t, t) for t in mcfg.layer_period if mcfg.rotates(t)}),
        "attention_output_gate": bool(mcfg.attention_output_gate),
        "sublayer_output_norm": bool(mcfg.sublayer_output_norm),
        "qk_norm_per_head": bool(mcfg.qk_norm_per_head),
    }


def file_says(cfg: dict) -> dict:
    """The same keys as the configuration file states them: the router's
    count is the PUBLISHED ``num_experts``, and what no published key
    says is what this family is (``assumed`` in the file gives each its
    basis)."""
    return {**cfg, "experts_first": int(cfg.get("experts_first", 0)),
            "routed_experts": cfg["published"]["num_experts"],
            "choice_bias": True,
            "rotating_layer_types": ["sliding_attention"],
            "attention_output_gate": True, "sublayer_output_norm": True,
            "qk_norm_per_head": True}


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``trinity.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position, the
    engine's experts given to the reference: what comes back is that
    pass, NaN when the engine is apart."""
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
    # a rehearsal runs tiny widths by design, and is never correct
    within = within and (rehearsed or not differs)
    if router_margins is not None:
        router_margins.extend(margins)
    stats = engine.stats()
    print(json.dumps({"note": "probe_logits", "positions": len(tokens),
                      "of": "the engine's programs over its two-group pool",
                      "differs_from_the_file": differs,
                      "window_pages_returned":
                          stats.get("kv_window_pages_returned"),
                      "moe_assignments_held":
                          stats.get("moe_assignments_held"),
                      "moe_assignments": stats.get("moe_assignments"),
                      "moe_experts_touched_held":
                          stats.get("moe_experts_touched_held"),
                      "moe_expert_tiles": stats.get("moe_expert_tiles"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
