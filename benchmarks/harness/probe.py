"""Correctness, outside the window: the program against the plain
reference under ``benchmarks/reference/``.

Serving: one greedy probe request after the window — a prompt of some
hundreds of tokens across several prefill chunks, then a few answer
tokens through the decode step and the paged cache.  The reference runs
the whole sequence at once.  The engine hands out no logits, so the
comparison is the tightest the benchmark's own files can make: at every
answer position the reference's logit of the token the engine chose must
lie within ``margin`` of the reference's largest logit there.  A
configuration with discrete routing has one more case.  The program
rounds its activations to bf16, so where its router's choice is close it
may seat the first rejected expert in the last chosen one's place, and
at three layers of depth that one token's logits then move by whole
units.  So a position beyond the margin is run again through the
reference with that routing choice turned AT THAT POSITION (in every
combination of the routed layers), and stands if the engine's token is
then within the same ``margin``.  At most ``turned_ties_allowed``
positions of a probe may need that, and at most ``unexplained_allowed``
may lie beyond the margin with no turned choice explaining them; the
configuration file gives the numbers read on the chip that these rest on.

Training, two links.  (1) The program's own forward pass — the model
the train step differentiates, on the same sharded weights — gives a
loss at every position of the first micro-batch; the reference gives
its own; they must agree at EVERY position (``position_tolerance``) and
on average (``mean_abs_tolerance``).  A mean over a batch would not do:
over random labels it is near ln V + sigma^2/2 whatever the mask or the
positions.  (2) The first step's logged loss must be the mean of the
program's forward losses over the whole batch (``loss_tolerance``), so
the step is tied to the forward that was checked.
"""

from __future__ import annotations

import importlib.util
import itertools
import os
import time

import numpy as np

from . import spec
from .context import Run, note


def _reference(cfg: dict):
    """(decoder module, weights adapter class) the configuration names."""
    mods = []
    for key, default in (("reference", "reference/decoder.py"),
                         ("reference_weights", "reference/from_program.py")):
        path = os.path.join(spec.BENCH_DIR, cfg.get(key, default))
        s = importlib.util.spec_from_file_location(
            "bench_" + os.path.basename(path)[:-3], path)
        m = importlib.util.module_from_spec(s)
        s.loader.exec_module(m)
        mods.append(m)
    return mods[0], mods[1].ProgramWeights


def reference_cfg(run: Run) -> dict:
    """The configuration as run: the file's published keys, with the
    depth (and in rehearsal every size) the program was really given."""
    cfg = dict(run.cell.config)
    cfg.update(run.model_shape)
    return cfg


def serving_probe(run: Run, engine, sampling_cls, vocab: int) -> None:
    import jax.numpy as jnp

    p = dict(run.cell.config.get("probe", {}))
    if run.rehearsal:
        p.update(p.get("rehearsal", {}))
    n_prompt, n_answer = int(p["prompt_tokens"]), int(p["answer_tokens"])
    margin = float(p["margin"])
    turns_allowed = int(p.get("turned_ties_allowed", 0))
    unexplained_allowed = int(p.get("unexplained_allowed", 0))
    rng = np.random.default_rng(run.seed + 1)
    prompt = rng.integers(1, vocab - 1, size=n_prompt).tolist()
    t0 = time.perf_counter()
    req = engine.submit(prompt, sampling_cls(max_new_tokens=n_answer,
                                             temperature=0.0))
    req.result(timeout=300)
    answer = list(req.out_tokens)
    decoder, weights_cls = _reference(run.cell.config)
    cfg = reference_cfg(run)
    weights = weights_cls(engine.params, cfg)
    chosen_ids = jnp.asarray(answer, jnp.int32)[:, None]

    def reference(turned=None):
        """(deficit at each answer position, the logits there, each
        routed layer's margins there)."""
        routed: list = []
        at = decoder.forward_logits(weights, cfg, prompt + answer[:-1],
                                    router_margins=routed,
                                    turned=turned)[n_prompt - 1:]
        chosen = jnp.take_along_axis(at, chosen_ids, axis=-1)[:, 0]
        return (np.asarray(jnp.max(at, axis=-1) - chosen), at,
                [np.asarray(m)[n_prompt - 1:] for m in routed])

    deficit, at, margins = reference()
    agree = int(np.sum(np.asarray(jnp.argmax(at, axis=-1)) ==
                       np.asarray(answer)))
    # a position beyond the margin stands only if the reference, with its
    # own routing ties AT THAT POSITION turned, has the engine's token
    # within the margin
    turned_ties, unexplained = [], []
    for t in np.flatnonzero(~(deficit <= margin)):
        routed = range(len(margins)) if turns_allowed else ()
        for layers in (c for r in range(1, len(routed) + 1)
                       for c in itertools.combinations(routed, r)):
            again = float(reference(
                {k: [n_prompt - 1 + int(t)] for k in layers})[0][t])
            if again <= margin:
                turned_ties.append({
                    "position": int(t), "deficit": float(deficit[t]),
                    "deficit_with_ties_turned": again,
                    "layers": list(layers),
                    "router_margins": [float(margins[k][t])
                                       for k in layers]})
                break
        else:
            unexplained.append({"position": int(t),
                                "deficit": float(deficit[t]),
                                "router_margins": [float(m[t])
                                                   for m in margins]})
    run.checks["probe_finished"] = (req.finish_reason == "length"
                                    and len(answer) == n_answer)
    run.checks["probe_within_margin_of_reference"] = bool(
        np.all(np.isfinite(deficit))
        and len(unexplained) <= unexplained_allowed
        and len(turned_ties) <= turns_allowed)
    inside = deficit[deficit <= margin]
    note("probe", prompt_tokens=n_prompt, answer_tokens=len(answer),
         chunks=-(-n_prompt // engine.config.prefill_chunk), margin=margin,
         worst_deficit_within_margin=float(inside.max()) if inside.size
         else None,
         turned_ties=turned_ties, unexplained=unexplained,
         turned_ties_allowed=turns_allowed,
         unexplained_allowed=unexplained_allowed,
         same_argmax=agree, seconds=time.perf_counter() - t0,
         logit_std=float(jnp.std(at)))


def training_probe(run: Run, model, params, batch: dict, first_loss: float,
                   sequence_parallel: bool) -> None:
    import jax

    p = dict(run.cell.config.get("probe", {}))
    decoder, weights_cls = _reference(run.cell.config)
    cfg = reference_cfg(run)
    t0 = time.perf_counter()
    seq = batch["tokens"].shape[-1]
    tokens = np.asarray(batch["tokens"]).reshape(
        batch["tokens"].shape[0], -1, seq)          # [micro, rows, seq]
    labels = np.asarray(batch["labels"]).reshape(tokens.shape)

    @jax.jit
    def forward(params, toks, labs):
        out = model(params, toks, labels=labs, rng_key=None, train=False,
                    sequence_parallel=sequence_parallel)
        return (out[0] if isinstance(out, tuple) else out).astype("float32")

    program = np.stack([np.asarray(forward(params, t, lab))
                        for t, lab in zip(tokens, labels)])
    program_mean = float(program.mean())
    # the reference on the first micro-batch, a sequence to a device
    devices = jax.local_devices()
    ref = []
    for k, (toks, labs) in enumerate(zip(tokens[0], labels[0])):
        weights = weights_cls(params, cfg, devices[k % len(devices)])
        ref.append(decoder.position_losses(
            decoder.forward_logits(weights, cfg, toks), labs))
    ref = np.stack([np.asarray(r) for r in ref])
    apart = np.abs(program[0] - ref)
    tol_pos = float(p["position_tolerance"])
    tol_mean = float(p["mean_abs_tolerance"])
    tol_loss = float(p["loss_tolerance"])
    run.checks["forward_matches_reference_at_every_position"] = bool(
        np.all(np.isfinite(apart)) and float(apart.max()) <= tol_pos
        and float(apart.mean()) <= tol_mean)
    run.checks["first_loss_is_the_forwards_mean"] = bool(
        np.isfinite(first_loss) and abs(first_loss - program_mean) <= tol_loss)
    note("probe", positions=int(apart.size), sequences=len(ref),
         worst_position_apart=float(apart.max()),
         p999_position_apart=float(np.quantile(apart, 0.999)),
         mean_abs_apart=float(apart.mean()),
         mean_apart=float((program[0] - ref).mean()),
         position_loss_std=float(ref.std()),
         position_tolerance=tol_pos, mean_abs_tolerance=tol_mean,
         first_loss=first_loss, program_forward_mean=program_mean,
         first_loss_apart=first_loss - program_mean, loss_tolerance=tol_loss,
         seconds=time.perf_counter() - t0)
