"""What the serving probe loads for Granite: ``granite.py``'s plain
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
any limit), in the form ``mellum_probe.py`` gave it, which is loaded here
as a private copy with its reference replaced by ``granite.py``:

* the engine's experts are given at EVERY tapped row in the pass the
  engine is held to (``EveryRowGiven``);
* a model with state-space layers adopts no prefix, so a tapped prefill
  position is reached by prefilling its whole prefix again, and the
  positions whose chunk has ONE live row are the FIRST ROW OF A CHUNK
  (``tapped_rows``): the state that row reads was carried across every
  chunk boundary before it, in its slot.  At 6,144 prompt tokens in
  twelve chunks of 512 those are 2048, 2560 ... 5632, then 6143 and the
  15 decode steps 6144-6158 (the step form of the mixer, the state read
  and written at every live row);
* the decode step without its sampler hands each layer its group's
  table, and a state-space layer none (row s is slot s):
  ``mellum_probe.py::decode_logits_program`` as it stands.

What is ADDED to both: the engine's RECURRENT STATE is held to the
reference's.  Rounding ``S`` to bf16 in its slot does not show in the
logits of 6,144 tokens (read on the chip, PR 40: the engine with ``S``
in bf16 reads 0.01075 / 0.01047 where the sound one reads 0.01087 /
0.01047: a stored state's rounding is of the size of every bf16
activation's), so the logits cannot hold the file's assumption of
float32.  The state itself can, in the heads whose decay is SLOW and
in the FIRST state-space layer: such a head sums hundreds of tokens, so
a float32 sum averages the rounding of its bf16 terms out while a bf16
sum adds its own at every token; a head that forgets within a token or
two reads its terms' own rounding either way, and a deeper layer
inherits the noise of every activation before it.  (Read on the chip,
PR 40, sound against ``S`` in bf16: the largest distance over ALL heads
of a layer 0.027-0.040 against 0.032-0.045, no telling; the slowest
quarter of the heads in the last layers 0.010-0.012 against 0.014-0.017;
in the first layer 0.0047-0.0057 against 0.018-0.019.)  So the probe's
sequence is served once more, the state its last step leaves in the
request's slot is read out of the engine's state group
(``engine_states``; the slot from the chunk's own table argument), and
each state-space layer's is compared with the state ``granite.py``'s
token-by-token recurrence is left with in the pass the logits are held
to (``WithStates``): per head, the root mean square of engine minus
reference over the reference's (``states_apart``).  Two limits: over
the ``probe.state_slow_share`` of the FIRST state-space layer's heads
whose decay a token is slowest by the engine's own weights
(``slowest_heads``), the root mean square of that within
``probe.state_apart_tolerance``; and no head of any layer beyond
``probe.state_any_head_apart_tolerance`` (a state lost or misplaced,
not a rounding).  Every layer's two numbers are in the report.  That
state was carried in its slot across every chunk boundary and step of
the sequence.

What differs from both: the engine's routing record is the ROUTER's
histogram over all the experts it scores (72), of which the program
computes the held ones; ``granite.py`` chooses over all of them too and
computes the held ones, so a record of one live row is that row's ten
experts, held or not.  The program's state-space sizes, multipliers,
layer types and share of experts are compared with the file's here
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


plain = _load("granite", "bench_granite_plain")


class WithStates:
    """``granite.py`` as the comparison calls it, keeping the state
    every state-space layer is left with by the LAST pass made: the one
    the engine's logits are held to."""

    def __init__(self):
        self.states = None

    def __getattr__(self, name):
        return getattr(plain, name)

    def forward_logits(self, *args, **kwargs):
        self.states = []
        return plain.forward_logits(*args, states=self.states, **kwargs)


reference = WithStates()
# mellum_probe.py's EveryRowGiven, tapped_rows and decode step, over a
# private copy of keye_probe.py; its ``plain`` is looked up when called
shared = _load("mellum_probe", "bench_granite_shared_probe")
shared.plain = reference

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
        "layer_types": period * (mcfg.num_layers // len(period)),
        "position_embedding_type": {"none": "nope"}.get(
            mcfg.position_embedding_type.value,
            mcfg.position_embedding_type.value),
        "tie_word_embeddings": bool(mcfg.tie_embed_logits),
        "attention_multiplier": mcfg.attention_multiplier,
        "embedding_multiplier": mcfg.embedding_multiplier,
        "residual_multiplier": mcfg.residual_multiplier,
        "logits_scaling": mcfg.logits_scaling,
        "mamba_n_heads": mcfg.mamba_n_heads,
        "mamba_d_head": mcfg.mamba_d_head,
        "mamba_d_state": mcfg.mamba_d_state,
        "mamba_n_groups": mcfg.mamba_n_groups,
        "mamba_d_conv": mcfg.mamba_d_conv,
        "mamba_chunk_size": mcfg.mamba_chunk_size,
        "mamba_conv_bias": bool(mcfg.mamba_conv_bias),
        "mamba_expand": mcfg.mamba_d_inner // mcfg.hidden_size,
        "shared_intermediate_size": (mcfg.moe_shared_experts
                                     * mcfg.expert_hidden_size),
        "experts_first": mcfg.moe_experts_first,
        "routed_experts": mcfg.routed_experts,
    }


def engine_states(engine, tokens, n_prompt: int):
    """(the state ``[heads, d_head, d_state]`` (float32, on the host)
    each state-space layer of the engine is left with by ``tokens``, the
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
    states = [np.asarray(pool["ssm_state"][slots[-1]], np.float32)
              for pool in engine._st.pages if paged_kv.is_state(pool)]
    return states, list(req.out_tokens)[:-1] == tokens[n_prompt:]


def slowest_heads(engine, share: float) -> list:
    """For each state-space layer the ``share`` of its heads whose state
    decays slowest: the smallest ``softplus(dt_bias) * exp(A_log)``, the
    decay's exponent a token at a zero ``dt``, from the engine's own
    weights."""
    m = engine.params["transformer"]["layers"]["mamba"]
    dt_bias = np.asarray(m["dt_bias"], np.float32)          # [layers, heads]
    rate = np.logaddexp(0.0, dt_bias) * np.exp(
        np.asarray(m["A_log"], np.float32))
    keep = max(1, int(round(share * rate.shape[1])))
    return [np.sort(np.argsort(r, kind="stable")[:keep]) for r in rate]


def states_apart(program, reference_states, heads=None) -> list:
    """For each state-space layer the root mean square of engine minus
    reference over the reference's, a head: the largest over all its
    heads or, with ``heads`` (``slowest_heads``), the root mean square
    over those."""
    out = []
    for i, (mine, theirs) in enumerate(zip(program, reference_states)):
        theirs = np.asarray(theirs, np.float32)
        d = np.sqrt(np.sum((mine - theirs) ** 2, axis=(1, 2))
                    / np.sum(theirs ** 2, axis=(1, 2)))
        out.append(float(np.max(d) if heads is None
                         else np.sqrt(np.mean(d[heads[i]] ** 2))))
    return out


def state_against_reference(engine, p: dict, tokens, states=None) -> dict:
    """The report of the state's comparison (``within`` among its keys)
    against ``reference.states``, which the logits' comparison just
    left; ``states``: what ``engine_states`` gave, where it was asked
    already."""
    n_prompt = len(tokens) - int(p["answer_tokens"]) + 1
    mine, alike = states or engine_states(engine, tokens, n_prompt)
    slow = states_apart(mine, reference.states,
                        slowest_heads(engine, float(p["state_slow_share"])))
    apart = states_apart(mine, reference.states)
    tolerance = float(p["state_apart_tolerance"])
    any_head = float(p["state_any_head_apart_tolerance"])
    return {"layers": len(mine), "answered_alike": alike,
            "tolerance": tolerance, "first_layer_slow_heads_apart": slow[0],
            "slow_heads_apart": [float(f"{a:.4g}") for a in slow],
            "any_head_tolerance": any_head, "worst": max(apart),
            "head_apart": [float(f"{a:.4g}") for a in apart],
            "within": bool(alike and len(mine) == len(reference.states)
                           and slow[0] <= tolerance
                           and max(apart) <= any_head)}


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``granite.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position, the
    engine's experts given to the reference: what comes back is that
    pass, NaN when the engine is apart."""
    tokens = np.asarray(tokens, np.int32)
    engine = engine_of(weights.p)
    p = settings_as_run(cfg, len(tokens))
    rehearsed = p["prompt_tokens"] != cfg["probe"]["prompt_tokens"]
    as_run = shape_as_run(engine.model.cfg)
    file_says = {**cfg, "routed_experts": cfg["published"]["num_local_experts"]}
    differs = sorted(k for k, v in as_run.items() if file_says.get(k) != v)
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
                      "ssm_tokens": stats.get("ssm_tokens"),
                      "moe_assignments_held":
                          stats.get("moe_assignments_held"),
                      "moe_assignments": stats.get("moe_assignments"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
