"""What the serving probe loads for Ouro: ``ouro.py``'s plain forward, and
beside it a comparison of the LOGITS THE ENGINE'S OWN PROGRAMS COMPUTED,
over the engine's own ``total_ut_steps x num_hidden_layers`` pools, with
the reference's.

The method is ``keye_probe.py``'s, whose docstring says why and how (taps
over ``engine._prefill_step`` and ``engine._decode_step``, the step
without its sampler on the step's own arguments, while this file submits
requests of its own through ``engine.submit``; each group of tapped
positions held by its MEDIAN within ``probe.logits_apart_tolerance`` and
each position within ``probe.position_apart_tolerance``; NaN back to the
harness beyond any limit), in the form ``mellum_probe.py`` gave it,
loaded here as a private copy for its taps and its rows.  The model is
dense: no expert is given to anyone, and the comparison is this file's
own (``engine_against_reference``), a page long.

* The tapped prefill positions are the FIRST ROW OF A CHUNK
  (``tapped_rows``): at 1,536 prompt tokens in three chunks of 512 those
  are 512 and 1,024, each reached by a request that adopts every page
  before it from the prefix cache, ALL 48 PLANES of each, and computes
  one live row whose every pass walks pages that an earlier request's
  same pass wrote; then the prompt's last row (1,535) and the 15 decode
  steps 1,536-1,550.
* The reference keeps no cache: a pass computes its keys from its own
  stream, so what the engine's planes are held to is "pass t attends
  pass t's keys" by construction.
* The program's passes, head size, exit threshold and untied head are
  compared with the file's here (``shape_as_run`` against the file):
  ``harness/shape.py`` reports none of those keys.

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


plain = _load("ouro", "bench_ouro_plain")
# mellum_probe.py's tapped_rows, settings and decode step, over a private
# copy of keye_probe.py (``.shared``: the taps and the distance)
shared = _load("mellum_probe", "bench_ouro_shared_probe")

position_losses = plain.position_losses
cross_entropy = plain.cross_entropy
LastRows = shared.LastRows
engine_of = shared.engine_of
settings_as_run = shared.settings_as_run
tapped_rows = shared.tapped_rows


def shape_as_run(mcfg) -> dict:
    """The published config's keys that ``harness/shape.py`` does not
    report, as the program was really given them."""
    return {"total_ut_steps": int(mcfg.loop_steps),
            "early_exit_threshold": float(mcfg.early_exit_threshold),
            "head_dim": int(mcfg.head_dim),
            "tie_word_embeddings": bool(mcfg.tie_embed_logits),
            "sandwich_norms": bool(mcfg.sublayer_output_norm)}


def file_says(cfg: dict) -> dict:
    """The same keys as the configuration file states them (the four
    norms a layer are ``assumed`` there, with their basis)."""
    return {**cfg, "sandwich_norms": True,
            "early_exit_threshold": float(cfg["early_exit_threshold"])}


def engine_against_reference(engine, weights, cfg: dict, p: dict, tokens,
                             taps=None, faults=frozenset()):
    """The engine's tapped logits over ``tokens`` against the
    reference's.  Returns (the report, whether every limit holds, the
    reference's logits at the answer positions, [] for the routers a
    dense model has none of, the taps)."""
    tokens = np.asarray(tokens, np.int32)
    n_prompt = len(tokens) - int(p["answer_tokens"]) + 1
    extra = tapped_rows(engine, n_prompt, int(p["prefill_rows"]))
    rows = np.asarray(extra + list(range(n_prompt - 1, len(tokens))))
    if taps is None:
        taps = shared.shared.engine_logits(engine, tokens, n_prompt, extra)
    logits = plain.forward_logits(weights, cfg, tokens, rows=rows,
                                  faults=faults)
    at = {int(t): i for i, t in enumerate(rows)}
    tolerance = float(p["logits_apart_tolerance"])
    at_one = float(p["position_apart_tolerance"])
    margin = float(p["margin"])
    report = {"tolerance": tolerance, "position_tolerance": at_one}
    # the engine answered as it answered the probe, at every position it
    # was asked to tap, and each step's own token is its tap's choice
    report["answered_alike"] = (taps.answer[:-1]
                                == [int(t) for t in tokens[n_prompt:]])
    decode_rows = list(range(n_prompt, len(tokens)))
    report["tapped_every_row"] = (
        set(taps.prefill) >= set(extra + [n_prompt - 1])
        and sorted(taps.decode) == decode_rows)
    deficit = [float(taps.decode[t].max()
                     - taps.decode[t][taps.step_token[t]])
               for t in sorted(taps.decode)]
    report["step_token_deficit_worst"] = max(deficit, default=0.0)
    within = (report["answered_alike"] and report["tapped_every_row"]
              and report["step_token_deficit_worst"] <= margin)
    for name, taken, positions in (
            ("prefill", taps.prefill, extra + [n_prompt - 1]),
            ("decode", taps.decode, decode_rows)):
        positions = [t for t in positions if t in taken]
        apart = np.asarray(shared.shared.positions_apart(
            jnp.asarray(np.stack([taken[t] for t in positions])),
            logits[jnp.asarray([at[t] for t in positions])]))
        beyond = [t for t, a in zip(positions, apart) if not a <= at_one]
        within = (within and not beyond
                  and bool(np.median(apart) <= tolerance))
        report[name] = {
            "positions": len(positions), "beyond": beyond,
            "median": float(np.median(apart)), "worst": float(apart.max()),
            "apart": [float(f"{a:.4g}") for a in apart]}
    return report, bool(within), logits[len(extra):], [], taps


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``ouro.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position: NaN
    when the engine is apart."""
    tokens = np.asarray(tokens, np.int32)
    engine = engine_of(weights.p)
    p = settings_as_run(cfg, len(tokens))
    rehearsed = p["prompt_tokens"] != cfg["probe"]["prompt_tokens"]
    as_run = shape_as_run(engine.model.cfg)
    says = file_says(cfg)
    differs = sorted(k for k, v in as_run.items() if says.get(k) != v)
    if turned:
        raise NotImplementedError("a dense model has no tie to turn")
    report, within, answers, _, _ = engine_against_reference(
        engine, weights, {**cfg, **as_run}, p, tokens)
    # a rehearsal runs tiny widths by design, and is never correct
    within = within and (rehearsed or not differs)
    stats = engine.stats()
    print(json.dumps({"note": "probe_logits", "positions": len(tokens),
                      "of": "the engine's programs over a pool a pass",
                      "differs_from_the_file": differs,
                      "planes": len(engine._st.pages),
                      "loop_layer_runs": stats.get("loop_layer_runs"),
                      "walks": stats.get("walks"),
                      "prefill_tokens_cached":
                          stats.get("prefill_tokens_cached"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
