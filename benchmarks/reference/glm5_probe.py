"""What the serving probe loads for GLM-5: ``glm5.py``'s plain forward,
and beside it a comparison of the LOGITS THE ENGINE'S OWN PROGRAMS
COMPUTED, over the engine's own two-array latent pool, with the
reference's.

The method is ``keye_probe.py``'s, whose docstring says why and how (taps
over ``engine._prefill_step`` and ``engine._decode_step`` while this file
submits requests of its own through ``engine.submit``; each group of
tapped positions held by its MEDIAN within
``probe.logits_apart_tolerance`` and each position within
``probe.position_apart_tolerance``; the engine's experts GIVEN to the
reference where a bf16 router's close choice is not the float32 one's,
within ``probe.router_slack_tolerance``; NaN back to the harness beyond
any limit), in the form ``mellum_probe.py`` gave it and ``kanana_probe.py``
took over a latent pool, loaded here as a private copy with its
reference replaced by ``glm5.py``:

* the engine's experts are given at EVERY tapped row in the pass the
  engine is held to (``EveryRowGiven``);
* the tapped prefill positions are the FIRST ROW OF A CHUNK
  (``tapped_rows``) of the ``probe.prefill_rows`` chunks before the
  prompt's last row.  A page of this pool is a page of BOTH its arrays,
  so the prefix cache adopts a latent row's page and its indexer key's
  as one: each such prefix adopts every page before its last token and
  computes a chunk of ONE live row over latents AND indexer keys that an
  earlier request wrote.  At 6,144 prompt tokens in twelve chunks of 512
  those are 2048, 2560 ... 5632, then 6143 and the 15 decode steps
  6144-6158: at every one of them 2,048 of 2,049 to 6,159 latents are
  chosen (``dsa_index_scores_*``, ``dsa_select_*``), and attended
  through ``mla_attention_prefill_masked`` and
  ``mla_attention_sparse_decode``.

What differs from Kanana's: the reference is given THE SHARE of the
experts the program holds (``weights.use``: ``n_routed_experts`` of them
from ``experts_first`` on, of the router's ``published.n_routed_experts``
outputs), and the program's compressed query, indexer (heads, head size,
how much of a head rotates, what its queries read, top-k), latent shape,
router and depth of dense layers are compared with the file's here
(``shape_as_run`` against ``file_says``): ``harness/shape.py`` reports
none of those keys.

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


plain = _load("glm5", "bench_glm5_plain")
# mellum_probe.py's EveryRowGiven, tapped_rows and decode step, over a
# private copy of keye_probe.py; its ``plain`` is looked up when called
shared = _load("mellum_probe", "bench_glm5_shared_probe")
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
    return {
        "kv_lora_rank": mcfg.kv_lora_rank,
        "q_lora_rank": mcfg.q_lora_rank,
        "qk_nope_head_dim": mcfg.qk_nope_head_dim,
        "qk_rope_head_dim": mcfg.qk_rope_head_dim,
        "qk_head_dim": mcfg.qk_head_dim,
        "v_head_dim": mcfg.v_head_dim,
        "index_n_heads": mcfg.dsa_index_heads,
        "index_head_dim": mcfg.dsa_index_head_dim,
        "index_topk": mcfg.dsa_topk,
        "index_rope_dim": mcfg.dsa_index_rope_dim,
        "index_query": mcfg.dsa_index_query,
        "n_routed_experts": mcfg.num_experts,
        "experts_first": mcfg.moe_experts_first,
        "routed_experts": mcfg.routed_experts,
        "n_shared_experts": mcfg.moe_shared_experts,
        "moe_intermediate_size": mcfg.expert_hidden_size,
        "first_k_dense_replace": mcfg.moe_first_dense_layers,
        "routed_scaling_factor": mcfg.moe_routed_scale,
        "scoring_func": mcfg.moe_score_function,
        "norm_topk_prob": bool(mcfg.norm_topk_prob),
        "n_group": mcfg.moe_n_group,
        "topk_group": mcfg.moe_topk_group,
    }


def file_says(cfg: dict) -> dict:
    """The same keys as the configuration file states them: the router's
    count is the PUBLISHED ``n_routed_experts``, an indexer head rotates
    in its first ``qk_rope_head_dim`` dimensions, and its queries read
    the compressed query (``assumed`` in the file gives each its
    basis)."""
    return {**cfg, "experts_first": int(cfg.get("experts_first", 0)),
            "routed_experts": cfg["published"]["n_routed_experts"],
            "index_rope_dim": cfg["qk_rope_head_dim"],
            "index_query": "compressed"}


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``glm5.forward_logits`` at the answer positions, after the
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
                      "of": "the engine's programs over its latent pool "
                            "and indexer keys",
                      "differs_from_the_file": differs,
                      "dsa_keys_live": stats.get("dsa_keys_live"),
                      "dsa_keys_selected": stats.get("dsa_keys_selected"),
                      "mla_pairs": stats.get("mla_pairs"),
                      "moe_assignments_held":
                          stats.get("moe_assignments_held"),
                      "moe_assignments": stats.get("moe_assignments"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
