"""Controls for the limits of GLM-5's probe: what the readings in
``configs/glm-5-serve.json`` were made with.  Not part of a benchmark
run; the chip, one process a call.  The method is ``keye_controls.py``'s,
in ``kanana_controls.py``'s form.

1. A FAULT IN THE PROGRAM'S PLACE, through the harness, which must come
   out ``correct: false``::

       python3 benchmarks/reference/glm5_controls.py --control dense -- \\
           --workload glm-5-serve.longdoc-64k --seed 2147484001 \\
           --seconds 5 --trace 0

   patches the program in this process and then runs ``benchmarks/run.py``
   on the arguments after ``--``: the cell's own engine, traffic and probe.
   ``dense``: every key a query may see is chosen (``ops/dsa.py::choose``,
   kernels and dense path alike); ``topk_half``: 1,024 latents chosen;
   ``unweighted``: every indexer head weighs the same;
   ``index_query_from_input``: the indexer's queries read the layer's
   normed input (its first ``q_lora_rank`` columns) in the compressed
   query's place; ``no_query_norm``: the norm on the compressed query is
   left out; ``index_no_rope``: nothing of the indexer rotates;
   ``bias_in_gates``: the gates are the scores PLUS the choice bias,
   renormalised and scaled; ``float8_activations``: every normed
   activation of the stack is rounded to float8 (e4m3), the nearest
   precision below the stated bf16 (rounding the WEIGHTS would show
   nothing: the reference reads the engine's weights).

2. FAULTY REFERENCES against the sound engine, position by position::

       python3 benchmarks/reference/glm5_controls.py --readings \\
           --seed 2147484074 2147484003 --faults dense topk_half

   builds the server as the cell does, serves the probe's request and
   prints what ``glm5_probe.py::engine_against_reference`` reads (every
   tapped position's distance, the share of experts that differ, the
   router's slack) and the token deficits ``harness/probe.py`` would
   read, for the sound reference and for each faulty one (``glm5.py``'s
   ``faults``), the engine's experts given to each alike.  ``--faults``
   are read on the first seed, ``--faults_later`` on every later one;
   later seeds reuse the engine with new weights.
"""

from __future__ import annotations

import argparse
import importlib.util
import os
import runpy
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, os.path.join(ROOT, "tools"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

CELL = "glm-5-serve.longdoc-64k"
FAULTS = ("dense", "topk_half", "unweighted", "index_query_from_input",
          "no_query_norm", "index_no_rope", "index_rope_whole",
          "no_latent_norm", "bias_in_gates", "bias_left_out", "no_scale",
          "no_shared", "float8")
CONTROLS = ("dense", "topk_half", "unweighted", "index_query_from_input",
            "no_query_norm", "index_no_rope", "bias_in_gates",
            "float8_activations")

# what is the same for every cell's controls (the server built as the
# cell builds it, its weights made again from another seed, a note's
# line) is keye_controls.py's, loaded as a private copy for this cell
_spec = importlib.util.spec_from_file_location(
    "bench_glm5_shared_controls", os.path.join(HERE, "keye_controls.py"))
_shared = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_shared)
_shared.CELL = CELL
note, build, new_weights = _shared.note, _shared.build, _shared.new_weights


# ---------------------------------------------------------------------------
# 1. a fault in the program's place
# ---------------------------------------------------------------------------

def _indexer(tfm, planted) -> None:
    """``indexer_projections`` with ``planted(sound, x, params, cfg,
    positions, query_input)`` in its place."""
    sound = tfm.indexer_projections

    def faulty(x, params, cfg, positions, query_input=None):
        return planted(sound, x, params, cfg, positions, query_input)

    tfm.indexer_projections = faulty


def plant(control: str) -> None:
    import jax.numpy as jnp
    from megatron_llm_tpu.models import moe
    from megatron_llm_tpu.models import transformer as tfm
    from megatron_llm_tpu.ops import dsa

    if control == "dense":
        dsa.choose = lambda key, valid, pos, topk, pos_bits, count: valid
    elif control == "topk_half":
        def planted(sound, *args):
            iq, ik, iw, topk = sound(*args)
            return iq, ik, iw, max(1, topk // 2)

        _indexer(tfm, planted)
    elif control == "unweighted":
        def planted(sound, x, params, cfg, *rest):
            iq, ik, iw, topk = sound(x, params, cfg, *rest)
            return iq, ik, jnp.full_like(
                iw, cfg.dsa_index_heads ** -0.5
                * cfg.dsa_index_head_dim ** -0.5), topk

        _indexer(tfm, planted)
    elif control == "index_query_from_input":
        _indexer(tfm, lambda sound, x, params, cfg, positions, _: sound(
            x, params, cfg, positions, x[..., :cfg.q_lora_rank]))
    elif control == "index_no_rope":
        def planted(sound, *args):
            turn = tfm.apply_rotary_at
            tfm.apply_rotary_at = lambda x, *a, **kw: x
            try:
                return sound(*args)
            finally:
                tfm.apply_rotary_at = turn

        _indexer(tfm, planted)
    elif control == "no_query_norm":
        # the one RMSNorm of latent_attention whose scale is as wide as
        # the compressed query (the latent's is kv_lora_rank wide)
        norm, latent = tfm.rms_norm, tfm.latent_attention

        def without(x, params, cfg, **kw):
            tfm.rms_norm = lambda y, scale, **k: (
                y if scale.shape[-1] == cfg.q_lora_rank != cfg.kv_lora_rank
                else norm(y, scale, **k))
            try:
                return latent(x, params, cfg, **kw)
            finally:
                tfm.rms_norm = norm

        tfm.latent_attention = without
    elif control == "bias_in_gates":
        route = moe._route

        def biased(x, params, cfg):
            logits, probs, _, idx = route(x, params, cfg)
            gates = jnp.take_along_axis(
                probs + params["router"]["choice_bias"].astype(jnp.float32),
                idx, axis=-1)
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
            return logits, probs, gates * cfg.moe_routed_scale, idx

        moe._route = biased
    elif control == "float8_activations":
        norm = tfm.apply_norm

        def rounded(x, *args, **kwargs):
            y = norm(x, *args, **kwargs)
            return y.astype(jnp.float8_e4m3fn).astype(y.dtype)

        tfm.apply_norm = rounded
    else:
        raise SystemExit(f"no such control: {control}")
    note("control", planted=control)


# ---------------------------------------------------------------------------
# 2. faulty references against the sound engine
# ---------------------------------------------------------------------------

def readings(seeds, faults, faults_later, rehearse: bool,
             more_flags=()) -> None:
    import jax.numpy as jnp
    import numpy as np
    from harness import shape, spec
    from megatron_llm_tpu.serving.request import SamplingParams

    probe = spec.load_module("reference", "glm5_probe")
    weights_cls = spec.load_module("reference",
                                   "glm5_from_program").ProgramWeights
    cell, generator = build(seeds[0], rehearse, more_flags)
    engine = generator.engine
    cfg = dict(cell.config)
    cfg.update(shape.model_shape(engine.model.cfg))
    cfg.update(probe.shape_as_run(engine.model.cfg))
    p = dict(cfg["probe"])
    if rehearse:
        p.update(p["rehearsal"])
    n_prompt, n_answer = int(p["prompt_tokens"]), int(p["answer_tokens"])
    vocab = int(engine.model.cfg.padded_vocab_size)
    for k, seed in enumerate(seeds):
        if k:
            weights = None
            new_weights(generator, seed)
        prompt = np.random.default_rng(seed + 1).integers(
            1, vocab - 1, size=n_prompt).tolist()
        req = engine.submit(prompt, SamplingParams(max_new_tokens=n_answer,
                                                   temperature=0.0))
        req.result(timeout=600)
        answer = list(req.out_tokens)
        tokens = np.asarray(prompt + answer[:-1], np.int32)
        weights = weights_cls(engine.params, cfg)
        taps = None
        for name in ["sound"] + list(faults if k == 0 else faults_later):
            t0 = time.perf_counter()
            report, within, here, _, taps = probe.engine_against_reference(
                engine, weights, cfg, p, tokens, taps=taps,
                faults=frozenset([name]) - {"sound"})
            chosen = jnp.take_along_axis(
                here, jnp.asarray(answer, jnp.int32)[:, None], axis=-1)[:, 0]
            deficit = np.asarray(jnp.max(here, axis=-1) - chosen)
            note("reading", seed=seed, reference=name, within=within,
                 deficit=[float(f"{d:.4g}") for d in deficit],
                 beyond_margin=int(np.sum(~(deficit <= float(p["margin"])))),
                 seconds=time.perf_counter() - t0, **report)
    engine.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=CONTROLS)
    ap.add_argument("--readings", action="store_true")
    ap.add_argument("--seed", type=int, nargs="+", default=[2147484074])
    ap.add_argument("--faults", nargs="*", default=list(FAULTS),
                    choices=FAULTS + ("bf16",))
    ap.add_argument("--faults_later", nargs="*", default=["topk_half"],
                    choices=FAULTS + ("bf16",),
                    help="the faults read on every seed after the first")
    ap.add_argument("--rehearse", action="store_true",
                    help="the readings at the rehearsal's sizes, on the CPU")
    ap.add_argument("--program_flag", action="append", default=[],
                    help="one more flag for the program, e.g. "
                    "--program_flag=--bf16 with --rehearse")
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if args.readings:
        readings(args.seed, args.faults, args.faults_later, args.rehearse,
                 args.program_flag)
        return
    if not args.control:
        raise SystemExit("--control NAME -- <run.py's arguments>, or "
                         "--readings")
    plant(args.control)
    sys.argv = [os.path.join(BENCH, "run.py")] + [
        a for a in args.rest if a != "--"]
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
