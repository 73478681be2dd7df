"""What the serving probe loads for Qwen3-Next: ``qwen3_next.py``'s plain
forward, and beside it a comparison of the LOGITS THE ENGINE'S OWN
PROGRAMS COMPUTED, over the engine's own state group and pages, with the
reference's.

The method is ``keye_probe.py``'s, whose docstring says why and how (taps
over ``engine._prefill_step`` and ``engine._decode_step`` while this file
submits requests of its own through ``engine.submit``; each group of
tapped positions held by its MEDIAN within
``probe.logits_apart_tolerance`` and each position within
``probe.position_apart_tolerance``; the engine's experts GIVEN to the
reference where a bf16 router's close choice is not the float32 one's,
within ``probe.router_slack_tolerance``; NaN back to the harness beyond
any limit), in the form ``mellum_probe.py`` gave it and
``granite_probe.py`` carried to a model with a state, which is loaded
here as a private copy with its reference replaced by ``qwen3_next.py``:

* the engine's experts are given at EVERY tapped row in the pass the
  engine is held to (``EveryRowGiven``);
* a model with state layers adopts no prefix, so a tapped prefill
  position is reached by prefilling its whole prefix again, and the
  positions whose chunk has ONE live row are the FIRST ROW OF A CHUNK:
  here of the chunks ``probe.tapped_chunks`` names (two, five and nine of
  the prompt's nine: positions 512, 2048 and 4096), each of which reads
  a state and three columns that were carried across every chunk
  boundary before it, in its slot, and keys of the two attention layers
  from its pages; then the prompt's last row (4607) and every decode
  step (4608-4630: the step form of the mixer, the state read and
  written in place at its live row);
* the decode step without its sampler hands each layer its group's
  table, and a state layer none (row s is slot s):
  ``mellum_probe.py::decode_logits_program`` as it stands.

What is ADDED, as Granite's: the engine's RECURRENT STATE is held to the
reference's.  The probe's sequence is served once more, the state its
last step leaves in the request's slot is read out of the engine's state
group (``engine_states``), and each delta layer's is compared with the
state ``qwen3_next.py``'s token-by-token recurrence is left with in the
pass the logits are held to (``WithStates``): per value head, the root
mean square of engine minus reference over the reference's, the largest
head of a layer (``states_apart``).  Two limits.  The FIRST delta
layer's, within ``probe.state_apart_tolerance``: that layer reads the
embedding through one norm, so no expert's close choice and no earlier
layer's rounding stands between the two sides, and its state holds the
delta rule's own arithmetic over the whole sequence: the fast heads
tell a decay misplaced by a token, the slow ones a state summed in bf16
(a float32 sum averages the rounding of its bf16 terms out while a bf16
sum adds its own at every token).  And no head of any layer beyond
``probe.state_any_head_apart_tolerance`` (a state lost or misplaced,
not a rounding: the deeper layers inherit every router's close choices
at every row of the prefix, which the reference is given at the tapped
rows alone).  Every layer's number is in the report.

The engine's routing record is the ROUTER's histogram over all the
experts it scores (512), of which the program computes the held ones;
``qwen3_next.py`` chooses over all of them too and computes the held
ones, so a record of one live row is that row's ten experts, held or
not.  The program's delta sizes, partial rotary, layer pattern and share
of experts are compared with the file's here (``shape_as_run``):
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


plain = _load("qwen3_next", "bench_qwen3_next_plain")


class WithStates:
    """``qwen3_next.py`` as the comparison calls it, keeping the state
    every delta layer is left with by the LAST pass made: the one the
    engine's logits are held to."""

    def __init__(self):
        self.states = None

    def __getattr__(self, name):
        return getattr(plain, name)

    def forward_logits(self, *args, **kwargs):
        self.states = []
        return plain.forward_logits(*args, states=self.states, **kwargs)


reference = WithStates()
# mellum_probe.py's EveryRowGiven and decode step, over a private copy of
# keye_probe.py; its ``plain`` is looked up when called
shared = _load("mellum_probe", "bench_qwen3_next_shared_probe")
shared.plain = reference
# the chunks whose first row is tapped, as the probe's settings name them
TAPPED = {"chunks": ()}


def tapped_rows(engine, n_prompt: int, n_rows: int) -> list:
    """The prefill positions tapped beside the prompt's last: the first
    row of each chunk ``probe.tapped_chunks`` names (1-based), ascending."""
    C = int(engine.config.prefill_chunk)
    rows = sorted(C * (int(c) - 1) for c in TAPPED["chunks"])
    assert rows and len(rows) == n_rows, (rows, n_rows)
    assert 0 < rows[0] and rows[-1] < n_prompt - 1, (rows, n_prompt)
    return rows


shared.shared.tapped_rows = tapped_rows

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
    return {
        "full_attention_interval": (
            len(period) if period[:-1] == ["gated_delta"] * (len(period) - 1)
            and period[-1] == "attention" else None),
        "head_dim": mcfg.head_dim,
        "partial_rotary_factor": mcfg.rotary_percent,
        "tie_word_embeddings": bool(mcfg.tie_embed_logits),
        "linear_num_key_heads": mcfg.delta_key_heads,
        "linear_num_value_heads": mcfg.delta_value_heads,
        "linear_key_head_dim": mcfg.delta_key_dim,
        "linear_value_head_dim": mcfg.delta_value_dim,
        "linear_conv_kernel_dim": mcfg.delta_conv_taps,
        "moe_intermediate_size": mcfg.expert_hidden_size,
        "shared_expert_intermediate_size": (mcfg.moe_shared_experts
                                            * mcfg.expert_hidden_size),
        "norm_topk_prob": bool(mcfg.norm_topk_prob),
        "num_experts": mcfg.num_experts,
        "experts_first": mcfg.moe_experts_first,
        "routed_experts": mcfg.routed_experts,
    }


def engine_states(engine, tokens, n_prompt: int):
    """(the state ``[value heads, d_key, d_value]`` (float32, on the
    host) each delta layer of the engine is left with by ``tokens``, the
    probe's sequence served once more: the prompt prefilled in chunks
    and every answer token but the last stepped through, all in one
    slot; whether the engine answered as the sequence says)."""
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
    # a finished request's state stays in its slot until the slot's next
    # request starts from zeros; nothing else is being served
    states = [np.asarray(pool["delta_state"][slots[-1]], np.float32)
              for pool in engine._st.pages if paged_kv.is_state(pool)]
    return states, list(req.out_tokens)[:-1] == tokens[n_prompt:]


def head_rates(engine) -> np.ndarray:
    """[delta layers, value heads]: ``exp(A_log) softplus(dt_bias)``, the
    decay's exponent a token at a zero ``a``, from the engine's own
    weights."""
    m = engine.params["transformer"]["layers"]["gated_delta"]
    return np.logaddexp(0.0, np.asarray(m["dt_bias"], np.float32)) * np.exp(
        np.asarray(m["A_log"], np.float32))


def states_apart(program, reference_states) -> list:
    """For each delta layer the root mean square of engine minus
    reference over the reference's, a head: the largest over all its
    heads."""
    out = []
    for mine, theirs in zip(program, reference_states):
        theirs = np.asarray(theirs, np.float32)
        d = np.sqrt(np.sum((mine - theirs) ** 2, axis=(1, 2))
                    / np.sum(theirs ** 2, axis=(1, 2)))
        out.append(float(np.max(d)))
    return out


def state_against_reference(engine, p: dict, tokens, states=None) -> dict:
    """The report of the state's comparison (``within`` among its keys)
    against ``reference.states``, which the logits' comparison just
    left; ``states``: what ``engine_states`` gave, where it was asked
    already."""
    n_prompt = len(tokens) - int(p["answer_tokens"]) + 1
    mine, alike = states or engine_states(engine, tokens, n_prompt)
    apart = states_apart(mine, reference.states)
    tolerance = float(p["state_apart_tolerance"])
    any_head = float(p["state_any_head_apart_tolerance"])
    rate = head_rates(engine)
    return {"layers": len(mine), "answered_alike": alike,
            "tolerance": tolerance, "first_layer_apart": apart[0],
            "any_head_tolerance": any_head, "worst": max(apart),
            "head_apart": [float(f"{a:.4g}") for a in apart],
            # what the drawn gates give: a head's half-life in tokens at
            # a zero ``a``, the shortest and the longest of all layers
            "half_life_tokens": [float(f"{np.log(2) / r:.4g}")
                                 for r in (rate.max(), rate.min())],
            "within": bool(alike and len(mine) == len(reference.states)
                           and apart[0] <= tolerance
                           and max(apart) <= any_head)}


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``qwen3_next.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position, the
    engine's experts given to the reference: what comes back is that
    pass, NaN when the engine is apart."""
    tokens = np.asarray(tokens, np.int32)
    engine = engine_of(weights.p)
    p = settings_as_run(cfg, len(tokens))
    rehearsed = p["prompt_tokens"] != cfg["probe"]["prompt_tokens"]
    as_run = shape_as_run(engine.model.cfg)
    file_says = {**cfg, "routed_experts": cfg["published"]["num_experts"]}
    differs = sorted(k for k, v in as_run.items() if file_says.get(k) != v)
    if turned:
        # harness/probe.py turns ties only where turned_ties_allowed > 0
        raise NotImplementedError(
            "this configuration turns no tie: the engine's own experts "
            "are given to the reference instead")
    weights.use({**cfg, **as_run})
    TAPPED["chunks"] = tuple(p["tapped_chunks"])
    p = {**p, "prefill_rows": len(TAPPED["chunks"])}
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
                      "delta_rows_live": stats.get("delta_rows_live"),
                      "delta_tokens": stats.get("delta_tokens"),
                      "moe_assignments_held":
                          stats.get("moe_assignments_held"),
                      "moe_assignments": stats.get("moe_assignments"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
