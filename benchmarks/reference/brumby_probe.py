"""What the serving probe loads for Brumby: ``brumby.py``'s plain forward
(the quadratic form), and beside it a comparison of the LOGITS THE
ENGINE'S OWN PROGRAMS COMPUTED, over the engine's own state group at the
timed 16 slots, and of the STATE one layer leaves in the request's slot,
with the reference's.

The probe (``harness/probe.py``) sees the engine's tokens, not its
logits, and may not be edited, so the tight comparison is made here,
where the probe calls the reference (``keye_probe.py`` says why).  The
probe's sequence (4,608 prompt tokens in nine chunks of 512, so that the
context passes the 4,128 tokens a state is worth, then 256 answer
tokens) is served again under two taps laid over the engine's program
attributes (the taps' own answer need not be the timed run's: the tapped
step is ANOTHER COMPILE of ``engine._decode_impl``, bf16's logits over
151,936 words stand a few hundredths apart somewhere in 256 steps, and
the TPU's compiler rounds a fused product in one compile and not in the
other; chip run, PR 54: the two answers parted after 16 and after 25
steps.  So the engine's logits are held to the reference over THE
SEQUENCE THE TAPPED RUN DECODED, and the harness is handed the reference
over the sequence the timed run decoded, one more pass where they
differ):

* ``engine._prefill_step`` already returns the logits at a chunk's last
  live row; the tap keeps them.  The prompt is submitted again,
  ``probe.live_rows`` (16: every slot) times AT ONCE, then the prefixes
  that end on the FIRST ROW of chunks ``probe.tapped_chunks`` (two, five
  and nine: positions 512, 2,048 and 4,096), one token each:
  each is prefilled whole (a model with state adopts no prefix) and its
  last chunk is ONE live row over the state the chunks before it left.
* ``engine._decode_step`` returns tokens only, and OWNS the pool: a
  second program over the same pool would copy 4.4 GB of state beside
  8.4 GB of weights, which the chip does not hold.  So for as long as
  the taps lie the step IS ``engine._decode_impl`` itself, jitted under
  the same ownership, with the logits its sampler is handed as one more
  output (``decode_with_logits``: a spy on ``sample_batched`` while the
  engine's own method is traced; nothing of the step is written again
  here): the in-place kernel over the live rows, the sampler, the key
  chain, all as timed.  The copies of the prompt start their answers a
  prefill apart, so the steps walk 1, 2, ... 16 compacted live rows at
  16 different positions and back down, as the timed steps do.  The
  LEAD row (the first to decode) is held to the reference at every
  step; every other live row of every step is compared with the lead's
  logits AT THE SAME POSITION, which another launch computed over
  another set of live rows: where they are the lead's bit for bit they
  are held by the lead's comparison, where they are not they are kept
  and held to the reference themselves, by the same limits
  (``rows_against_reference``).

At every tapped position: root mean square of engine minus reference,
each centred over the vocabulary, as a share of the reference's standard
deviation there (``keye_probe.positions_apart``).  The prefill rows are
held by their median (``probe.logits_apart_tolerance``), the decode rows
by theirs (``probe.decode_median_tolerance``), every position by itself
(``probe.position_apart_tolerance``).  Then THE STATE: what layer
``probe.state_layer`` holds after the last step in the slot of EVERY
row that decoded the lead's tokens to the end, ``ret_state``
``[8, 65, 128, 128]`` and ``ret_sum`` ``[8, 65, 128]``, against ``S =
sum_j exp(A_T - A_j) phi(k_j) v_j^T`` and its ``z`` computed by
``brumby.state_of`` from the REFERENCE's own ``k``, ``v`` and ``a`` of
that layer (its columns relabelled as the program holds them): the root
mean square of engine minus reference over the reference's, within
``probe.state_apart_tolerance`` and ``probe.sum_apart_tolerance``.  The
layer is the FIRST (its input is the embedding alone, so its state
carries its own rounding and no layer's noise before it).  That distance
holds the state's mathematics (a gate left out, a rotation, a neighbour's
head) but NOT its precision: the engine's keys and values are bf16's, so
a sound float32 state already stands 0.0047 from the reference's, and one
rounded to bf16 at every write would add 0.002 in quadrature (chip runs,
PR 54).  So the precision is read where it is: the share of the slot's
values that bf16 cannot hold (``state.float32_share``: all but one in
65,536 of a float32 state's, none of a rounded one's) must reach
``probe.state_float32_share_floor`` (``brumby_controls.py``'s
``state_bf16``).
"""

from __future__ import annotations

import collections
import contextlib
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


plain = _load("brumby", "bench_brumby_plain")
_from_program = _load("brumby_from_program", "bench_brumby_columns")
_keye = _load("keye_probe", "bench_brumby_shared_probe")

position_losses = plain.position_losses
cross_entropy = plain.cross_entropy
LastRows = _keye.LastRows
engine_of = _keye.engine_of
positions_apart = _keye.positions_apart


def decode_with_logits(engine):
    """``engine._decode_impl`` jitted as the engine jits it (the pool
    owned), with the logits its sampler was handed [S, vocab] appended to
    what it returns."""
    from megatron_llm_tpu.serving import engine as engine_module

    def engine_decode(*args):
        seen = {}
        sound = engine_module.sample_batched

        def spy(logits, *rest, **kw):
            seen["logits"] = logits
            return sound(logits, *rest, **kw)

        engine_module.sample_batched = spy
        try:
            out = engine._decode_impl(*args)
        finally:
            engine_module.sample_batched = sound
        return (*out, seen["logits"])

    return jax.jit(engine_decode, donate_argnums=(1,))


class Taps:
    """What the engine's programs computed while the taps lay:
    ``prefill[t]`` the logits at position t (a chunk's last live row);
    of the LEAD row (the first row a decode step saw live, so the row
    that stands furthest on in every later step) ``decode[t]`` the logits
    of the step whose input token stood at t and ``step_token[t]`` the
    token that step chose; and of EVERY OTHER LIVE ROW of every step
    (``others[row]``) how its logits at t stand to the lead's at t, which
    another launch computed over another set of live rows: counted where
    they are the lead's bit for bit (``identical``), kept for the
    reference where they are not (``kept[t]``), for as long as the row's
    tokens are the lead's (``left_at``: where they stopped being).
    ``live[n]`` counts the steps that had n live rows."""

    def __init__(self, engine):
        self.engine = engine
        self.prefill, self.decode, self.step_token = {}, {}, {}
        self.answer = self.lead = None
        self.others, self.live = {}, collections.Counter()
        self._step = decode_with_logits(engine)

    def _prefill(self, inner):
        def tapped(params, pages, tokens, start, valid, table):
            out = inner(params, pages, tokens, start, valid, table)
            self.prefill[int(start) + int(valid) - 1] = np.asarray(out[0])
            return out
        return tapped

    def _decode(self, inner):
        def tapped(params, pages, last_tokens, context_lens, block_tables,
                   active, *rest):
            rows = [int(r) for r in np.flatnonzero(np.asarray(active) > 0)]
            at = np.asarray(context_lens)
            *out, logits = self._step(params, pages, last_tokens,
                                      context_lens, block_tables, active,
                                      *rest)
            logits, chose = np.asarray(logits), np.asarray(out[0])
            self.live[len(rows)] += 1
            if self.lead is None and rows:
                self.lead = rows[0]
            for r in sorted(rows, key=lambda r: r != self.lead):
                t = int(at[r])
                if r == self.lead:
                    self.decode[t] = logits[r].copy()
                    self.step_token[t] = int(chose[r])
                    continue
                row = self.others.setdefault(
                    r, {"identical": 0, "kept": {}, "left_at": None})
                if row["left_at"] is not None:
                    continue
                if np.array_equal(logits[r], self.decode[t]):
                    row["identical"] += 1
                else:
                    row["kept"][t] = logits[r].copy()
                if int(chose[r]) != self.step_token[t]:
                    row["left_at"] = t
            return tuple(out)
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


def engine_run(engine, tokens, n_prompt: int, rows, layer: int,
               live_rows: int = 1):
    """(the taps, the states ``{slot: (S, z)}`` layer ``layer`` held
    after the probe's sequence) of ``tokens`` (prompt and all but the
    last answer token) served again: the prompt ``live_rows`` times AT
    ONCE for as many answer tokens as the probe asked, so that the
    decode steps walk several live rows as the timed ones do (the
    requests start their answers a prefill apart and every one decodes
    what the others do), their slots' states read, then the prefix ending
    at each position of ``rows`` for one token."""
    from megatron_llm_tpu.serving.request import SamplingParams

    tokens = [int(t) for t in tokens]
    n_answer = len(tokens) - n_prompt + 1
    taps = Taps(engine)
    live_rows = min(int(live_rows), int(engine.config.num_slots))
    with taps.laid():
        reqs = [engine.submit(tokens[:n_prompt], SamplingParams(
            max_new_tokens=n_answer, temperature=0.0))
            for _ in range(live_rows)]
        for req in reqs:
            req.result(timeout=600)
        # the lead's answer: the request whose steps chose its tokens
        steps = [taps.step_token[t] for t in sorted(taps.step_token)]
        taps.answer = next(list(r.out_tokens) for r in reqs
                           if list(r.out_tokens)[1:] == steps)
        # a finished request's state stays in its slot until the slot's
        # next request starts from zeros
        with engine._st.pool_lock:
            pool = engine._st.pages[layer]
            states = {slot: (np.asarray(pool["ret_state"][slot]),
                             np.asarray(pool["ret_sum"][slot]))
                      for slot in [taps.lead] + sorted(taps.others)}
        for t in rows:
            engine.submit(tokens[:int(t) + 1], SamplingParams(
                max_new_tokens=1, temperature=0.0)).result(timeout=600)
    return taps, states


def relative(mine, theirs) -> float:
    return float(np.sqrt(np.sum((mine - theirs) ** 2) / np.sum(theirs ** 2)))


def float32_share(state) -> float:
    """The precision a slot HOLDS: the share of the state's values that
    bf16 cannot hold (their low 16 bits are not all 0): all but one in
    65,536 of a float32 state's, none of a state rounded to bf16."""
    bits = np.ascontiguousarray(state, np.float32).view(np.uint32)
    return float(np.mean((bits[state != 0] & 0xFFFF) != 0))


def rows_against_reference(taps, states, logits, steps, S, z) -> dict:
    """The live rows beside the lead (``Taps.others``) against the
    reference: ``logits`` [steps, vocab] are the reference's over the
    lead's sequence, which a row decoded too until ``left_at``.  A row's
    logits that ARE the lead's at that position are held by the lead's
    own comparison; the others are held here, each position by itself and
    a row's kept positions by their median (the worst of each is
    returned, 0.0 where nothing was kept)."""
    at = {t: i for i, t in enumerate(steps)}
    apart, medians, state_apart, sum_apart, share = [], [], [], [], []
    for slot, row in sorted(taps.others.items()):
        if row["kept"]:
            ts = sorted(row["kept"])
            here = np.asarray(positions_apart(
                jnp.asarray(np.stack([row["kept"][t] for t in ts])),
                logits[np.asarray([at[t] for t in ts])]))
            # a position that is not finite is apart
            here = np.where(np.isfinite(here), here, np.inf)
            apart.append(float(np.max(here)))
            medians.append(float(np.median(here)))
        if row["left_at"] is None:
            state_apart.append(relative(states[slot][0], S))
            sum_apart.append(relative(states[slot][1], z))
            share.append(float32_share(states[slot][0]))
    return {
        "live_rows": 1 + len(taps.others),
        "steps_by_live_rows": {str(n): taps.live[n]
                               for n in sorted(taps.live)},
        "row_steps": sum(r["identical"] + len(r["kept"])
                         for r in taps.others.values()),
        "identical_to_the_lead": sum(r["identical"]
                                     for r in taps.others.values()),
        "held_to_the_reference": sum(len(r["kept"])
                                     for r in taps.others.values()),
        "apart_worst": max(apart, default=0.0),
        "median_worst": max(medians, default=0.0),
        "left_the_leads_tokens_at": {str(s): r["left_at"] for s, r in
                                     sorted(taps.others.items())
                                     if r["left_at"] is not None},
        "states_held": len(state_apart),
        "state_apart_worst": max(state_apart, default=0.0),
        "sum_apart_worst": max(sum_apart, default=0.0),
        "float32_share_least": min(share, default=1.0)}


def engine_against_reference(engine, weights, cfg: dict, p: dict, tokens,
                             run=None, faults=frozenset()):
    """The engine's tapped logits and state over ``tokens`` against the
    reference's (``faults``: a faulty reference, for the controls).
    Returns (the report, whether every limit holds, the reference's
    logits at the answer positions, what ``engine_run`` gave)."""
    tokens = np.asarray(tokens, np.int32)
    n_prompt = len(tokens) - int(p["answer_tokens"]) + 1
    C = int(engine.config.prefill_chunk)
    layer = int(p.get("state_layer", 0))
    rows = [C * (int(c) - 1) for c in p["tapped_chunks"]]
    assert rows and 0 < rows[0] and rows[-1] < n_prompt - 1, (rows, n_prompt)
    taps, states = run or engine_run(engine, tokens, n_prompt, rows, layer,
                                     int(p.get("live_rows", 1)))
    state = states[taps.lead]
    # the sequence the TAPPED run decoded: its step is another compile of
    # the engine's own, and where two of bf16's logits stand closer than
    # the compiles' roundings its greedy answer leaves the timed one's
    # (module docstring); the engine's logits are held to the reference
    # over what that run itself decoded
    own = np.concatenate([tokens[:n_prompt],
                          np.asarray(taps.answer[:-1], np.int32)])
    left = np.flatnonzero(own != tokens)
    steps = list(range(n_prompt, len(tokens)))
    at = rows + [n_prompt - 1] + steps
    kept = {layer: {}}
    logits = plain.forward_logits(weights, cfg, own, rows=at,
                                  faults=faults, kept=kept)
    mine = np.stack([taps.prefill[t] for t in rows + [n_prompt - 1]]
                    + [taps.decode[t] for t in steps])
    apart = np.asarray(positions_apart(jnp.asarray(mine), logits))
    n_pre = len(rows) + 1
    # the state, in the program's order of a key's columns
    d = int(cfg["head_dim"])
    k = kept[layer]
    S, z = plain.state_of(k["k"][..., _from_program.state_columns(d)],
                          k["v"], k["a"])
    state_apart = relative(state[0], np.asarray(S))
    sum_apart = relative(state[1], np.asarray(z))
    held_share = float32_share(state[0])
    # every other live row of every step: its logits where they are not
    # the lead's bit for bit, to the reference by the limits that hold the
    # lead's; its slot's state to the reference's if it decoded the
    # lead's tokens to the end
    others = rows_against_reference(taps, states, logits[n_pre:], steps,
                                    np.asarray(S), np.asarray(z))
    # the step's own token against the tapped logits' largest
    deficit = [float(taps.decode[t].max() - taps.decode[t][
        taps.step_token[t]]) for t in steps]
    mean_gate = float(np.mean(np.asarray(k["a"])))
    beyond = [int(t) for t, a in zip(steps, apart[n_pre:])
              if not a <= float(p["position_apart_tolerance"])]
    report = {
        "prefill": {"positions": rows + [n_prompt - 1],
                    "apart": [float(f"{a:.4g}") for a in apart[:n_pre]],
                    "median": float(np.median(apart[:n_pre]))},
        "decode": {"positions": len(steps),
                   "median": float(np.median(apart[n_pre:])),
                   "worst": float(np.max(apart[n_pre:])),
                   "beyond": len(beyond), "first_beyond": beyond[:4]},
        "rows": others,
        "state": {"layer": layer, "slot": taps.lead,
                  "state_apart": state_apart, "sum_apart": sum_apart,
                  "float32_share": held_share,
                  "mean_log_gate": mean_gate,
                  "half_life_tokens": float(np.log(0.5) / min(mean_gate, -1e-30))},
        "step_token_deficit_worst": max(deficit, default=0.0),
        "answered_alike": not left.size,
        "tapped_answer_leaves_the_timed_at": (int(left[0]) if left.size
                                              else None),
        "logit_std": float(jnp.std(logits)),
        "tolerances": {n: float(p[n]) for n in (
            "logits_apart_tolerance", "decode_median_tolerance",
            "position_apart_tolerance", "state_apart_tolerance",
            "sum_apart_tolerance", "state_float32_share_floor")}}
    tol = report["tolerances"]
    within = bool(
        np.all(np.isfinite(apart))
        and report["prefill"]["median"] <= tol["logits_apart_tolerance"]
        and report["decode"]["median"] <= tol["decode_median_tolerance"]
        and float(np.max(apart)) <= tol["position_apart_tolerance"]
        and state_apart <= tol["state_apart_tolerance"]
        and sum_apart <= tol["sum_apart_tolerance"]
        and held_share >= tol["state_float32_share_floor"]
        and others["median_worst"] <= tol["decode_median_tolerance"]
        and others["apart_worst"] <= tol["position_apart_tolerance"]
        and others["state_apart_worst"] <= tol["state_apart_tolerance"]
        and others["sum_apart_worst"] <= tol["sum_apart_tolerance"]
        and others["float32_share_least"] >= tol["state_float32_share_floor"]
        and max(deficit, default=0.0) <= float(p["margin"]))
    # what the harness holds the TIMED step's tokens to: the reference
    # over the sequence it decoded (one more pass where the two differ)
    answers = logits[n_pre - 1:] if not left.size else plain.forward_logits(
        weights, cfg, tokens, rows=[n_prompt - 1] + steps, faults=faults)
    return report, within, answers, (taps, states)


def shape_as_run(mcfg) -> dict:
    """The published config's keys that ``harness/shape.py`` does not
    report, as the program was really given them."""
    return {
        "layer_types": list(mcfg.layer_period),
        "phi_rows": mcfg.retention_phi_rows,
        "head_dim": mcfg.head_dim,
        "rms_norm_eps": mcfg.layernorm_epsilon,
        "rope_theta": int(mcfg.rope_theta),
        "qk_norm_per_head": bool(mcfg.qk_norm_per_head),
        "attention_bias": bool(mcfg.add_bias_linear or mcfg.add_qkv_bias),
        "tie_word_embeddings": bool(mcfg.tie_embed_logits),
    }


def file_says(cfg: dict) -> dict:
    """The same keys as the configuration file states them."""
    return {**cfg, "layer_types": ["retention"],
            "phi_rows": cfg["bytes"]["phi_rows"], "qk_norm_per_head": True}


def forward_logits(weights, cfg: dict, tokens, router_margins: list = None,
                   turned: dict = None):
    """``brumby.forward_logits`` at the answer positions, after the
    engine's own logits were held to it at every tapped position and one
    layer's state to ``state_of``: NaN when the engine is apart."""
    if turned:
        raise NotImplementedError("a dense model turns no tie")
    tokens = np.asarray(tokens, np.int32)
    engine = engine_of(weights.p)
    p = settings_as_run(cfg, len(tokens))
    rehearsed = p["prompt_tokens"] != cfg["probe"]["prompt_tokens"]
    as_run = shape_as_run(engine.model.cfg)
    says = file_says(cfg)
    differs = sorted(k for k, v in as_run.items() if says.get(k) != v)
    weights.use({**cfg, **as_run})
    report, within, answers, _ = engine_against_reference(
        engine, weights, weights.cfg, p, tokens)
    # a rehearsal runs tiny widths by design, and is never correct
    within = within and (rehearsed or not differs)
    stats = engine.stats()
    plan = engine._cache
    print(json.dumps({"note": "probe_logits", "positions": len(tokens),
                      "of": "the engine's programs over its state group",
                      "differs_from_the_file": differs,
                      "slots": int(engine.config.num_slots),
                      "paged": plan.paged,
                      "state_bytes_a_slot": plan.state_bytes_per_slot,
                      "paged_kernel": engine.paged_kernel,
                      "prefill_kernel": engine.prefill_kernel,
                      "retention_rows_live": stats.get("retention_rows_live"),
                      "retention_rows_moved":
                          stats.get("retention_rows_moved"),
                      "retention_tokens": stats.get("retention_tokens"),
                      "within": within, **report}),
          flush=True)
    return LastRows(len(tokens), answers if within else answers * jnp.nan)
