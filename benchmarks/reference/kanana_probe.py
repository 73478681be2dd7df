"""What the serving probe loads for Kanana: ``kanana.py``'s plain forward,
and beside it a comparison of the LOGITS THE ENGINE'S OWN PROGRAMS
COMPUTED, over the engine's own latent pool, with the reference's.

The method is ``keye_probe.py``'s, whose docstring says why and how (taps
over ``engine._prefill_step`` and ``engine._decode_step`` while this file
submits requests of its own through ``engine.submit``; each group of
tapped positions held by its MEDIAN within
``probe.logits_apart_tolerance`` and each position within
``probe.position_apart_tolerance``; the engine's experts GIVEN to the
reference where a bf16 router's close choice is not the float32 one's,
within ``probe.router_slack_tolerance``; NaN back to the harness beyond
any limit), in the form ``mellum_probe.py`` gave it, which is loaded here
as a private copy with its reference replaced by ``kanana.py``:

* the engine's experts are given at EVERY tapped row in the pass the
  engine is held to (``EveryRowGiven``);
* the tapped prefill positions are the FIRST ROW OF A CHUNK
  (``tapped_rows``): of the ``probe.prefill_rows`` chunks before the
  prompt's last row.  The latent pool's pages are adopted by the prefix
  cache like any others, so each such prefix adopts every page before
  its last token and computes a chunk of ONE live row over the whole
  cached context: the absorbed chunk walk over latents that an earlier
  request wrote.  At 6,144 prompt tokens in twelve chunks of 512 those
  are 2048, 2560 ... 5632, then 6143 and the 15 decode steps 6144-6158,
  through ``mla_attention_prefill`` and ``mla_attention_decode``.

What differs from both: the engine's routing record has a row a SPARSE
layer (the leading dense layer routes nothing), and ``kanana.py`` counts
``routing``, ``forced`` and ``router_margins`` the same way; a margin and
a given expert's slack are in the units of the choice (sigmoid score plus
bias: a quarter of a logit at most).  The program's latent shape, router
and depth of dense layers are compared with the file's here
(``shape_as_run``): ``harness/shape.py`` reports none of those keys.

Only the rows that are compared are computed: what comes back can be
sliced from any answer position to the end, which is the one thing the
probe does with it.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str, as_name: str):
    spec = importlib.util.spec_from_file_location(
        as_name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


plain = _load("kanana", "bench_kanana_plain")
# mellum_probe.py's EveryRowGiven, tapped_rows and decode step, over a
# private copy of keye_probe.py; its ``plain`` is looked up when called
shared = _load("mellum_probe", "bench_kanana_shared_probe")
shared.plain = plain

position_losses = plain.position_losses
cross_entropy = plain.cross_entropy
LastRows = shared.LastRows
engine_of = shared.engine_of
engine_against_reference = shared.engine_against_reference
settings_as_run = shared.settings_as_run


def shape_as_run(mcfg) -> dict:
    """The published config's keys that ``harness/shape.py`` does not
    report, as the program was really given them."""
    return {
        "kv_lora_rank": mcfg.kv_lora_rank,
        "q_lora_rank": mcfg.q_lora_rank,
        "qk_nope_head_dim": mcfg.qk_nope_head_dim,
        "qk_rope_head_dim": mcfg.qk_rope_head_dim,
        "qk_head_dim": mcfg.qk_head_dim,
        "v_head_dim": mcfg.v_head_dim,
        "n_routed_experts": mcfg.num_experts,
        "n_shared_experts": mcfg.moe_shared_experts,
        "moe_intermediate_size": mcfg.expert_hidden_size,
        "first_k_dense_replace": mcfg.moe_first_dense_layers,
        "routed_scaling_factor": mcfg.moe_routed_scale,
        "scoring_func": mcfg.moe_score_function,
        "norm_topk_prob": bool(mcfg.norm_topk_prob),
        "n_group": mcfg.moe_n_group,
        "topk_group": mcfg.moe_topk_group,
    }


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``kanana.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position, the
    engine's experts given to the reference: what comes back is that
    pass, NaN when the engine is apart."""
    tokens = np.asarray(tokens, np.int32)
    engine = engine_of(weights.p)
    p = settings_as_run(cfg, len(tokens))
    rehearsed = p["prompt_tokens"] != cfg["probe"]["prompt_tokens"]
    as_run = shape_as_run(engine.model.cfg)
    differs = sorted(k for k, v in as_run.items() if cfg.get(k) != v)
    if turned:
        # harness/probe.py turns ties only where turned_ties_allowed > 0
        raise NotImplementedError(
            "this configuration turns no tie: the engine's own experts "
            "are given to the reference instead")
    report, within, answers, margins, _ = engine_against_reference(
        engine, weights, {**cfg, **as_run}, p, tokens)
    # a rehearsal runs tiny widths by design, and is never correct
    within = within and (rehearsed or not differs)
    if router_margins is not None:
        router_margins.extend(margins)
    stats = engine.stats()
    print(json.dumps({"note": "probe_logits", "positions": len(tokens),
                      "of": "the engine's programs over its latent pool",
                      "differs_from_the_file": differs,
                      "mla_keys_live": stats.get("mla_keys_live"),
                      "mla_pairs": stats.get("mla_pairs"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
