"""What the serving probe loads for Keye: ``keye.py``'s plain forward, and
beside it a comparison of the LOGITS THE ENGINE'S OWN PROGRAMS COMPUTED,
over the engine's own pool, with the reference's.

The probe (``harness/probe.py``) sees the engine's tokens, not its
logits: it asks that the reference's logit of each token the engine
chose lie within a margin of the reference's largest.  That holds the
engine's path and gross faults; a subtle fault shows there only where it
turns a choice.  The engine hands out no logits and the probe may not be
edited, so the tight comparison is made here, where the probe calls the
reference, and it is made on WHAT THE CELL TIMES: the engine's
``engine_prefill`` program over ``[1, 512]`` chunks and its decode step
at all its slots, both through the three-array pool and the kernels of
``ops/pallas/dsa_attention.py`` (the indexer's scores walk, the choice,
the paged walk under the mask).  No cache-less forward is read.

How the engine's logits are reached without a change to the engine
(``engine_logits``).  The probe's request has been served when this file
is loaded, so its pages lie in the prefix cache.  Two taps are laid over
the engine's two program attributes for as long as this file submits
requests of its own, through ``engine.submit`` like any client:

* ``engine._prefill_step`` already returns the logits at a chunk's last
  live row (the engine samples the first token from them) and the
  chunk's routing record; the tap keeps both.  The probe's prompt is
  submitted again (its pages are adopted, its last block is computed:
  the logits at the prompt's last position), and prefixes of the probe's
  sequence that end on the FIRST token of each of ``probe.prefill_rows``
  earlier blocks (each adopts every page before and computes a chunk of
  one live row over the whole cached context, through the selection).
* ``engine._decode_step`` returns tokens only.  The tap runs, on the
  step's own arguments (the engine's pool, block tables, context
  lengths, all its slots) and just before the step itself, THE STEP
  WITHOUT ITS SAMPLER: ``paged_kv.step_caches`` with the engine's
  resolved kernel and ``language_model_forward``, as
  ``serving/engine.py::_decode_impl`` calls them, returning the live
  row's logits and the step's routing record.  The step's own token must
  lie within the probe's margin of those logits' largest, which ties the
  tap to the step.

The engine must answer the resubmitted prompt with the tokens it gave
the probe.  Then at every tapped position: root mean square of engine
minus reference, each centred over the vocabulary, as a share of the
reference's standard deviation there.  Each group of positions (the
prefill rows, the decode rows) is held by its MEDIAN, within
``probe.logits_apart_tolerance`` (the tight limit: a fault of the
mathematics or of the precision moves every position), and no single
position may lie beyond ``probe.position_apart_tolerance`` (a fault at
one context length or block boundary).

THE ROUTER'S CLOSE CHOICES.  The engine computes in bf16, the reference
in float32, and a router's eighth and ninth logits lie a few hundredths
apart at one position in three (logit standard deviation 0.9): read on
the chip, the engine's experts are not the reference's in 30% of
(position, layer) pairs, such a position's logits then lie 0.02 to 0.08
of their deviation from the reference's where 0.013 is the rounding's
own, and one in some hundreds turns the largest logit (PERF.md section
6, PR 30).  A launch with ONE live row records
exactly that row's experts (``DispatchRecord``'s routing histogram, PR
26), so where the engine's experts at a tapped position are not the
reference's, the reference runs again with THE ENGINE'S EXPERTS GIVEN
at that position (``keye.forward_logits(forced=...)``: the gates still
the reference's own float32 softmax over them), and it is that pass the
engine's logits are held to and the probe's token comparison reads.
What is given must have been a close choice: the lowest of the given
experts lies no more than ``probe.router_slack_tolerance`` below the
reference's own last choice, in the reference's router logits.  (The
prompt's last position is computed in a chunk of up to 16 live rows:
its experts are that chunk's record less the record of the same chunk
one row shorter, which a prefix one token shorter gives.)

Beyond any of these limits, or where the indexer's top-k as run is not
the file's, the logits come back as NaN, which the probe takes for a
failure: there is no other way to tell it.  The configuration file gives
the readings the limits rest on; ``keye_controls.py`` makes them.

Only the rows that are compared are computed (the head over 151,936 rows
of vocabulary at every one of 6,159 positions would be 3.7 GB): what
comes back can be sliced from any answer position to the end, which is
the one thing the probe does with it.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_keye_plain",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "keye.py"))
plain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(plain)

position_losses = plain.position_losses
cross_entropy = plain.cross_entropy


class LastRows:
    """Logits of the last rows of a sequence of ``length`` positions:
    ``x[t:]`` for any ``t`` among them."""

    def __init__(self, length: int, rows: jax.Array):
        self.length, self.rows = length, rows
        self.first = length - rows.shape[0]

    def __getitem__(self, index):
        if not (isinstance(index, slice) and index.stop is None
                and index.step is None and index.start is not None
                and self.first <= index.start <= self.length):
            raise IndexError(
                f"only x[t:] with {self.first} <= t <= {self.length} was "
                f"computed, got {index!r}")
        return self.rows[index.start - self.first:]


def engine_of(params):
    """The engine that serves ``params`` (the probe hands the reference
    the weights, not the engine)."""
    from megatron_llm_tpu.serving.engine import InferenceEngine

    for o in gc.get_objects():
        if isinstance(o, InferenceEngine) and o.params is params:
            return o
    raise RuntimeError("no live engine serves these parameters")


def decode_logits_program(engine):
    """The engine's decode step without its sampler: the same caches
    over the same arguments, the same forward, the logits [S, vocab]
    (float32) and the step's routing [layers, E]."""
    from megatron_llm_tpu.models.language_model import language_model_forward
    from megatron_llm_tpu.ops import paged_kv

    def engine_decode_logits(params, pages, last_tokens, context_lens,
                             block_tables, active):
        caches = paged_kv.step_caches(pages, block_tables, context_lens,
                                      active, engine.paged_kernel)
        logits, new_caches = language_model_forward(
            params, last_tokens[:, None], context_lens[:, None], None,
            engine.model.cfg, rng_key=None, train=False, kv_caches=caches)
        return (logits[:, 0, :].astype(jnp.float32),
                paged_kv.routing_of(new_caches))

    return jax.jit(engine_decode_logits)


class Taps:
    """What the engine's programs computed while the taps lay:
    ``prefill[t]`` the logits at position t (a chunk's last live row)
    and ``chunk[t]`` that chunk's (first position, routing record);
    ``decode[t]`` the logits of the step whose input token stood at t,
    ``step_token[t]`` the token that step itself chose and
    ``routing[t]`` its routing record.  A record is [layers, E] live
    assignments: of one live row, that row's experts."""

    def __init__(self, engine):
        self.engine = engine
        self.prefill, self.chunk, self.decode = {}, {}, {}
        self.step_token, self.routing = {}, {}
        self.answer = None
        self._decode_logits = decode_logits_program(engine)

    def _prefill(self, inner):
        def tapped(params, pages, tokens, start, valid, table):
            out = inner(params, pages, tokens, start, valid, table)
            t = int(start) + int(valid) - 1
            self.prefill[t] = np.asarray(out[0])
            self.chunk[t] = (int(start), None if out[2] is None
                             else np.asarray(out[2]))
            return out
        return tapped

    def _decode(self, inner):
        def tapped(params, pages, last_tokens, context_lens, block_tables,
                   active, *rest):
            live = np.flatnonzero(np.asarray(active) > 0)
            logits = routing = None
            if len(live) == 1:
                logits, routing = self._decode_logits(
                    params, pages, last_tokens, context_lens, block_tables,
                    active)
                logits = np.asarray(logits[int(live[0])])
                routing = None if routing is None else np.asarray(routing)
            out = inner(params, pages, last_tokens, context_lens,
                        block_tables, active, *rest)
            if logits is not None:
                t = int(np.asarray(context_lens)[live[0]])
                self.decode[t] = logits
                self.step_token[t] = int(np.asarray(out[0])[live[0]])
                self.routing[t] = routing
            return out
        return tapped

    @contextlib.contextmanager
    def laid(self):
        e = self.engine
        before = e._prefill_step, e._decode_step
        e._prefill_step = self._prefill(before[0])
        e._decode_step = self._decode(before[1])
        try:
            yield self
        finally:
            e._prefill_step, e._decode_step = before

    def experts(self, t: int, top_k: int):
        """The engine's experts at tapped position ``t``, a sorted list a
        layer, from a one-row record (a decode step's; a chunk's of one
        live row; a chunk's less the same chunk's one row shorter); None
        where there is no such record."""
        record = self.routing.get(t)
        if record is None and t in self.chunk:
            start, record = self.chunk[t]
            if record is not None and t > start:
                shorter = self.chunk.get(t - 1, (None, None))
                record = (record - shorter[1] if shorter[0] == start
                          and shorter[1] is not None else None)
        if record is None or not ((record.sum(axis=1) == top_k).all()
                                  and record.min() >= 0
                                  and record.max() <= 1):
            return None
        return [np.flatnonzero(r).tolist() for r in record]


def tapped_rows(engine, n_prompt: int, n_rows: int) -> list:
    """The prefill positions tapped beside the prompt's last: the first
    token of each of the ``n_rows`` blocks before the prompt's last
    block, ascending."""
    bs = int(engine.config.block_size)
    last = (n_prompt - 1) // bs
    rows = [bs * (last - k) for k in range(n_rows - 1, -1, -1)]
    assert rows and rows[0] > 0, (n_prompt, n_rows, bs)
    return rows


def engine_logits(engine, tokens, n_prompt: int, prefill_rows) -> Taps:
    """The engine's own logits over the probe's sequence ``tokens``
    (prompt and all but the last answer token): the prompt submitted
    again for as many answer tokens as the probe asked, then the prompt
    less its last token and the prefixes ending at ``prefill_rows`` for
    one token each, one request at a time.  ``taps.answer`` is what the
    engine answered this time."""
    from megatron_llm_tpu.serving.request import SamplingParams

    tokens = [int(t) for t in tokens]
    n_answer = len(tokens) - n_prompt + 1
    taps = Taps(engine)
    with taps.laid():
        for end, n_new in ([(n_prompt, n_answer), (n_prompt - 1, 1)]
                           + [(int(t) + 1, 1) for t in prefill_rows]):
            req = engine.submit(tokens[:end], SamplingParams(
                max_new_tokens=n_new, temperature=0.0))
            req.result(timeout=300)
            if taps.answer is None:
                taps.answer = list(req.out_tokens)
    return taps


@jax.jit
def positions_apart(program, reference) -> jax.Array:
    """[r]: at each position the root mean square of program minus
    reference, centred over the vocabulary, as a share of the
    reference's standard deviation there."""
    d = program - reference
    d = d - jnp.mean(d, axis=-1, keepdims=True)
    return jnp.sqrt(jnp.mean(d * d, axis=-1)) / jnp.std(reference, axis=-1)


def sizes_as_run(cfg: dict, length: int, topk: int):
    """(the probe's settings, the configuration with the indexer's top-k
    as the program was really given it, ``topk``, whether that is the
    file's).  ``harness/shape.py`` reports no nested key and the probe
    keeps its rehearsal sizes to itself, so both are told here: a
    sequence of the rehearsal's length is a rehearsal, with
    ``probe.rehearsal``'s settings and top-k."""
    p = dict(cfg["probe"])
    small = p.get("rehearsal", {})
    rehearsed = length == (int(small.get("prompt_tokens", -1))
                           + int(small.get("answer_tokens", 0)) - 1)
    if rehearsed:
        p.update(small)
    allowed = int(small["topk"] if rehearsed else cfg["sa_config"]["topk"])
    return (p, {**cfg, "sa_config": {**cfg["sa_config"], "topk": topk}},
            topk == allowed)


def engine_against_reference(engine, weights, cfg: dict, p: dict, tokens,
                             taps: Taps = None, faults=frozenset()):
    """The engine's tapped logits over ``tokens`` against the
    reference's, the engine's experts given to the reference where they
    are not its own.  Returns (the report, whether every limit holds,
    the reference's logits at the answer positions as the engine's
    logits were held to them, the reference's own router margins a
    layer, the taps)."""
    tokens = np.asarray(tokens, np.int32)
    n_prompt = len(tokens) - int(p["answer_tokens"]) + 1
    top_k = int(cfg["num_experts_per_tok"])
    extra = tapped_rows(engine, n_prompt, int(p["prefill_rows"]))
    rows = np.asarray(extra + list(range(n_prompt - 1, len(tokens))))
    if taps is None:
        taps = engine_logits(engine, tokens, n_prompt, extra)
    own, margins = [], []
    logits = plain.forward_logits(weights, cfg, tokens, rows=rows,
                                  faults=faults, routing=own,
                                  router_margins=margins)
    # the engine's experts, where they are known and not the reference's
    given, unknown, differing = {}, [], 0
    for t in (int(t) for t in rows):
        theirs = taps.experts(t, top_k)
        if theirs is None:
            unknown.append(t)
            continue
        differs = [sorted(own[i][0][t].tolist()) != e
                   for i, e in enumerate(theirs)]
        differing += sum(differs)
        if any(differs):
            # every layer's: a turned choice moves what later routers see
            for i, e in enumerate(theirs):
                given.setdefault(i, {})[t] = e
    slack = 0.0
    if given:
        routed = []
        logits = plain.forward_logits(weights, cfg, tokens, rows=rows,
                                      faults=faults, forced=given,
                                      routing=routed)
        slack = max(float(routed[i][1][t]) for i, at_t in given.items()
                    for t in at_t)
    at = {int(t): i for i, t in enumerate(rows)}
    tolerance = float(p["logits_apart_tolerance"])
    at_one = float(p["position_apart_tolerance"])
    margin = float(p["margin"])
    report = {"tolerance": tolerance, "position_tolerance": at_one,
              "router_slack_tolerance": float(p["router_slack_tolerance"]),
              "router_slack_worst": slack,
              "experts_differ_share": differing / (
                  len(margins) * max(len(rows) - len(unknown), 1)),
              "experts_unknown_at": unknown}
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
              and report["step_token_deficit_worst"] <= margin
              and slack <= report["router_slack_tolerance"])
    for name, taken, positions in (
            ("prefill", taps.prefill, extra + [n_prompt - 1]),
            ("decode", taps.decode, decode_rows)):
        positions = [t for t in positions if t in taken]
        apart = np.asarray(positions_apart(
            jnp.asarray(np.stack([taken[t] for t in positions])),
            logits[jnp.asarray([at[t] for t in positions])]))
        beyond = [t for t, a in zip(positions, apart) if not a <= at_one]
        within = (within and not beyond
                  and bool(np.median(apart) <= tolerance))
        report[name] = {
            "positions": len(positions), "beyond": beyond,
            "median": float(np.median(apart)), "worst": float(apart.max()),
            "apart": [float(f"{a:.4g}") for a in apart]}
    return report, bool(within), logits[len(extra):], margins, taps


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``keye.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position, the
    engine's experts given to the reference where they are not its own:
    what comes back is that pass, NaN when the engine is apart."""
    tokens = np.asarray(tokens, np.int32)
    engine = engine_of(weights.p)
    p, cfg, topk_is_the_files = sizes_as_run(
        cfg, len(tokens), int(engine.model.cfg.dsa_topk))
    if turned:
        # harness/probe.py turns ties only where turned_ties_allowed > 0
        raise NotImplementedError(
            "this configuration turns no tie: the engine's own experts "
            "are given to the reference instead")
    report, within, answers, margins, _ = engine_against_reference(
        engine, weights, cfg, p, tokens)
    within = within and topk_is_the_files
    if router_margins is not None:
        router_margins.extend(margins)
    print(json.dumps({"note": "probe_logits", "positions": len(tokens),
                      "of": "the engine's programs over its pool",
                      "topk": cfg["sa_config"]["topk"],
                      "topk_is_the_files": topk_is_the_files,
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
