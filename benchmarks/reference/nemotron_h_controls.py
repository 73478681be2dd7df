"""Controls for the limits of Nemotron-H's probe: what the readings in
``configs/nemotron-3-nano-30b-a3b-serve.json`` were made with.  Not part
of a benchmark run; the chip, one process a call.  The method is
``keye_controls.py``'s, in ``granite_controls.py``'s form.

1. A FAULT IN THE PROGRAM'S PLACE, through the harness, which must come
   out ``correct: false``::

       python3 benchmarks/reference/nemotron_h_controls.py --control state_bf16 -- \\
           --workload nemotron-3-nano-30b-a3b-serve.reason-4k --seed 2147484001 \\
           --seconds 5 --trace 0

   patches the program in this process and then runs ``benchmarks/run.py``
   on the arguments after ``--``: the cell's own engine, traffic and probe.
   ``state_bf16``: the recurrent state is kept in bf16 in its slot
   (``ops/paged_kv.py::SSM_STATE_DTYPE``: the ASSUMPTION of float32 left
   out; the nearest precision below it); ``norm_whole``: the gated norm
   over the whole inner width (``models/mamba.py::gated_group_norm``
   handed one group); ``expert_relu``: an expert's and the shared MLP's
   ``relu`` with no square; ``no_scale``: ``routed_scaling_factor`` left
   at 1; ``rope_on``: queries and keys rotated at ``rope_theta``
   (``attention`` is handed the config with a rotary embedding);
   ``no_shared``: the shared MLP left out; ``second_norm``: a mixer
   layer's output normed again by the layer's norm before the residual;
   ``float8_activations``: every normed activation of the stack is
   rounded to float8 (e4m3), the nearest precision below the stated bf16
   (rounding the WEIGHTS would show nothing: the reference reads the
   engine's weights).

2. FAULTY REFERENCES against the sound engine, position by position::

       python3 benchmarks/reference/nemotron_h_controls.py --readings \\
           --seed 2147484074 2147484003 --faults state_bf16 norm_whole

   builds the server as the cell does, serves the probe's request and
   prints what ``nemotron_h_probe.py::engine_against_reference`` reads
   (every tapped position's distance, the share of experts that differ,
   the router's slack, each state-space layer's state against the
   reference's) and the token deficits ``harness/probe.py`` would read,
   for the sound reference and for each faulty one
   (``nemotron_h.py``'s ``faults``), the engine's experts given to each
   alike.  ``--faults`` are read on the first seed, ``--faults_later``
   on every later one; later seeds reuse the engine with new weights.
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

CELL = "nemotron-3-nano-30b-a3b-serve.reason-4k"
FAULTS = ("state_bf16", "norm_whole", "group_zero", "expert_swiglu",
          "expert_relu", "bias_in_gates", "no_scale", "rope_on", "no_shared",
          "second_norm", "no_D", "gate_after_norm", "no_conv_bias",
          "state_dropped_at_chunks", "float8")
CONTROLS = ("state_bf16", "norm_whole", "expert_relu", "no_scale", "rope_on",
            "no_shared", "second_norm", "float8_activations")

# what is the same for every cell's controls (the server built as the
# cell builds it, its weights made again from another seed, a note's
# line) is keye_controls.py's, loaded as a private copy for this cell
_spec = importlib.util.spec_from_file_location(
    "bench_nemotron_h_shared_controls", os.path.join(HERE, "keye_controls.py"))
_shared = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_shared)
_shared.CELL = CELL
note, build, new_weights = _shared.note, _shared.build, _shared.new_weights


# ---------------------------------------------------------------------------
# 1. a fault in the program's place
# ---------------------------------------------------------------------------

def plant(control: str) -> None:
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.models import mamba, moe
    from megatron_llm_tpu.models import transformer as tfm

    if control == "state_bf16":
        from megatron_llm_tpu.ops import paged_kv

        paged_kv.SSM_STATE_DTYPE = jnp.bfloat16
    elif control == "norm_whole":
        sound_norm = mamba.gated_group_norm
        mamba.gated_group_norm = lambda y, scale, groups, eps: sound_norm(
            y, scale, 1, eps)
    elif control == "expert_relu":
        moe.apply_mlp_activation = lambda h, cfg: jax.nn.relu(h)
    elif control == "no_scale":
        sound_route = moe._route
        moe._route = lambda x, params, cfg: sound_route(
            x, params, cfg.replace(moe_routed_scale=1.0))
    elif control == "rope_on":
        sound = tfm.attention
        tfm.attention = lambda x, params, cfg, **kw: sound(
            x, params, cfg.replace(position_embedding_type="rotary"), **kw)
    elif control == "no_shared":
        moe._shared_mlp = lambda x, params, cfg: None
    elif control == "second_norm":
        sound_layer = tfm.transformer_layer

        def layer(x, params, cfg, **kw):
            out, cache, aux = sound_layer(x, params, cfg, **kw)
            if kw.get("layer_type") == "moe":
                return out, cache, aux
            again = tfm.apply_norm(
                out - x, params["input_norm"], cfg.normalization,
                eps=cfg.layernorm_epsilon, fp32_compute=cfg.norm_in_fp32)
            return x + again.astype(out.dtype), cache, aux

        tfm.transformer_layer = layer
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

    probe = spec.load_module("reference", "nemotron_h_probe")
    weights_cls = spec.load_module("reference",
                                   "nemotron_h_from_program").ProgramWeights
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
        req.result(timeout=300)
        answer = list(req.out_tokens)
        tokens = np.asarray(prompt + answer[:-1], np.int32)
        weights = weights_cls(engine.params, cfg)
        taps = None
        states = probe.engine_states(engine, tokens, n_prompt)
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
                 state=probe.state_against_reference(engine, p, tokens,
                                                     states),
                 seconds=time.perf_counter() - t0, **report)
    engine.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=CONTROLS)
    ap.add_argument("--readings", action="store_true")
    ap.add_argument("--seed", type=int, nargs="+", default=[2147484074])
    ap.add_argument("--faults", nargs="*", default=list(FAULTS),
                    choices=FAULTS + ("bf16",))
    ap.add_argument("--faults_later", nargs="*", default=["state_bf16"],
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
