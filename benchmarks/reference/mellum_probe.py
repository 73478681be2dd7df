"""What the serving probe loads for Mellum: ``mellum.py``'s plain forward,
and beside it a comparison of the LOGITS THE ENGINE'S OWN PROGRAMS
COMPUTED, over the engine's own two-group pool, with the reference's.

The method is ``keye_probe.py``'s, whose docstring says why and how (taps
over ``engine._prefill_step`` and ``engine._decode_step`` while this file
submits requests of its own through ``engine.submit``; each group of
tapped positions held by its MEDIAN within
``probe.logits_apart_tolerance`` and each position within
``probe.position_apart_tolerance``; the engine's experts GIVEN to the
reference where a bf16 router's close choice is not the float32 one's,
within ``probe.router_slack_tolerance``; NaN back to the harness beyond
any limit).  That file is loaded here as a private copy and three of its
names are replaced, because three things differ:

* the reference is ``mellum.py``, with the engine's experts given at
  EVERY tapped row in the pass the engine is held to, not only where the
  reference's first pass chose others (``plain``: ``EveryRowGiven``,
  which says what was read on the chip without it);
* the decode step without its sampler hands each layer ITS GROUP'S block
  table (``decode_logits_program``: ``paged_kv.step_caches`` with the
  engine's ``_layer_groups``, as ``serving/engine.py::_decode_impl``);
* a patterned model adopts no prefix, so a tapped prefill position is
  reached by prefilling its whole prefix again, and the positions whose
  chunk has ONE live row (whose routing record is that row's experts)
  are the FIRST ROW OF A CHUNK, not of a block (``tapped_rows``): of the
  ``probe.prefill_rows`` chunks before the prompt's last row.  At 6,144
  prompt tokens in twelve chunks of 512 those are 2048, 2560 ... 5632,
  then 6143 (its experts: its chunk's record less the record of the same
  chunk one row shorter) and the 15 decode steps 6144-6158.  At every one
  of them three keys in four, or more, lie behind the window on the six
  window layers, whose pages went back to the allocator chunks ago, and
  the two full layers' positions are rotated by YaRN well past the
  original 8,192... no: within it; the factor and the remapped
  frequencies act at every position.

Only the rows that are compared are computed: what comes back can be
sliced from any answer position to the end, which is the one thing the
probe does with it.
"""

from __future__ import annotations

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name: str, as_name: str):
    spec = importlib.util.spec_from_file_location(
        as_name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


plain = _load("mellum", "bench_mellum_plain")
shared = _load("keye_probe", "bench_mellum_shared_probe")

position_losses = plain.position_losses
cross_entropy = plain.cross_entropy
LastRows = shared.LastRows
engine_of = shared.engine_of


def decode_logits_program(engine):
    """The engine's decode step without its sampler: the same caches
    (each layer its group's table) over the same arguments, the same
    forward, the logits [S, vocab] (float32) and the step's routing
    [layers, E]."""
    from megatron_llm_tpu.models.language_model import language_model_forward
    from megatron_llm_tpu.ops import paged_kv

    def engine_decode_logits(params, pages, last_tokens, context_lens,
                             block_tables, active):
        caches = paged_kv.step_caches(pages, block_tables, context_lens,
                                      active, engine.paged_kernel,
                                      engine._layer_groups)
        logits, new_caches = language_model_forward(
            params, last_tokens[:, None], context_lens[:, None], None,
            engine.model.cfg, rng_key=None, train=False, kv_caches=caches)
        return (logits[:, 0, :].astype(jnp.float32),
                paged_kv.routing_of(new_caches))

    return jax.jit(engine_decode_logits)


def tapped_rows(engine, n_prompt: int, n_rows: int) -> list:
    """The prefill positions tapped beside the prompt's last: the first
    row of each of the ``n_rows`` chunks before the prompt's last row,
    ascending."""
    C = int(engine.config.prefill_chunk)
    last = (n_prompt - 2) // C
    rows = [C * (last - k) for k in range(n_rows - 1, -1, -1)]
    assert rows and rows[0] > 0, (n_prompt, n_rows, C)
    return rows


class EveryRowGiven:
    """``mellum.py`` as ``engine_against_reference`` calls it, with the
    engine's experts given at EVERY tapped row in the pass the engine is
    held to, not only at the rows where the reference's own choice was
    another.  That function gives a row its experts where its first pass
    (nothing given) differs from the engine there, and compares the
    SECOND pass; but what is given at one row moves, a little, what the
    rows after it see of its keys, and a router that stood 0.001 from a
    tie at a row that agreed in the first pass can fall the other way in
    the second, where nothing holds it.  Read on the chip (PR 32, seed
    213089078): position 6148, the engine's experts in the first pass at
    all 8 layers and so not given, chose another expert at layers 1 and
    7 in the second (its first-pass margin at layer 1: 0.0012; position
    6144, four keys back, given at five layers) and read 0.0221 where
    the 23 others read 0.0068-0.0082; with every row given it reads
    0.0078.  A row that agreed in the first pass has the engine's
    experts as its own there, so the first pass's record is what this
    gives it."""

    def __init__(self):
        self.rows = self.own = None

    def __getattr__(self, name):
        return getattr(plain, name)

    def forward_logits(self, weights, cfg, tokens, rows=None, routing=None,
                       forced=None, **more):
        if forced is None:
            own = [] if routing is None else routing
            out = plain.forward_logits(weights, cfg, tokens, rows=rows,
                                       routing=own, **more)
            self.rows, self.own = [int(t) for t in rows], own
            return out
        everywhere = {
            i: {**{t: chose[t].tolist() for t in self.rows},
                **forced.get(i, {})}
            for i, (chose, _) in enumerate(self.own)}
        return plain.forward_logits(weights, cfg, tokens, rows=rows,
                                    routing=routing, forced=everywhere,
                                    **more)


shared.plain = EveryRowGiven()
shared.decode_logits_program = decode_logits_program
shared.tapped_rows = tapped_rows
engine_against_reference = shared.engine_against_reference


def settings_as_run(cfg: dict, length: int) -> dict:
    """The probe's settings: ``harness/probe.py`` keeps its rehearsal
    sizes to itself, so a sequence of the rehearsal's length is a
    rehearsal, with ``probe.rehearsal``'s settings."""
    p = dict(cfg["probe"])
    small = p.get("rehearsal", {})
    if length == (int(small.get("prompt_tokens", -1))
                  + int(small.get("answer_tokens", 0)) - 1):
        p.update(small)
    return p


def pattern_as_run(engine, cfg: dict) -> bool:
    """Whether the program's layer types, window and rotary variants are
    the file's: ``harness/shape.py`` reports no list and no nested key,
    so they are compared here."""
    mcfg = engine.model.cfg
    names = {"sliding": "sliding_attention", "full": "full_attention"}
    period = [names[t] for t in mcfg.layer_period]
    types = period * (mcfg.num_layers // len(period))
    yarn = cfg["rope_parameters"]["full_attention"]
    plain_rope = cfg["rope_parameters"]["sliding_attention"]
    return (types == list(cfg["layer_types"])
            and mcfg.attention_of("sliding") == (int(cfg["sliding_window"]),
                                                 None)
            and mcfg.attention_of("full") == (None, (
                float(yarn["factor"]),
                int(yarn["original_max_position_embeddings"]),
                float(yarn["beta_fast"]), float(yarn["beta_slow"]),
                float(yarn["attention_factor"])))
            and float(mcfg.rope_theta) == float(yarn["rope_theta"])
            == float(plain_rope["rope_theta"])
            and plain_rope["rope_type"] == "default")


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``mellum.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position, the
    engine's experts given to the reference where they are not its own:
    what comes back is that pass, NaN when the engine is apart."""
    tokens = np.asarray(tokens, np.int32)
    engine = engine_of(weights.p)
    p = settings_as_run(cfg, len(tokens))
    if turned:
        # harness/probe.py turns ties only where turned_ties_allowed > 0
        raise NotImplementedError(
            "this configuration turns no tie: the engine's own experts "
            "are given to the reference instead")
    report, within, answers, margins, _ = engine_against_reference(
        engine, weights, cfg, p, tokens)
    pattern = pattern_as_run(engine, cfg)
    within = within and pattern
    if router_margins is not None:
        router_margins.extend(margins)
    stats = engine.stats()
    print(json.dumps({"note": "probe_logits", "positions": len(tokens),
                      "of": "the engine's programs over its two-group pool",
                      "pattern_is_the_files": pattern,
                      "window_pages_returned":
                          stats.get("window_pages_returned"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
