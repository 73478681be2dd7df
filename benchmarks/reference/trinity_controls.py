"""Controls for the limits of Trinity's probe: what the readings in
``configs/trinity-mini-serve.json`` were made with.  Not part of a
benchmark run; the chip, one process a call.  The method is
``keye_controls.py``'s, in ``mellum_controls.py``'s form.

1. A FAULT IN THE PROGRAM'S PLACE, through the harness, which must come
   out ``correct: false``::

       python3 benchmarks/reference/trinity_controls.py --control no_gate -- \\
           --workload trinity-mini-serve.agent-16k --seed 2147484001 \\
           --seconds 5 --trace 0

   patches the program in this process and then runs ``benchmarks/run.py``
   on the arguments after ``--``: the cell's own engine, traffic and probe.
   ``no_gate``: the heads' output is not multiplied
   (``models/transformer.py::_split_qkv`` hands back no gate);
   ``full_rotates``: the full layers rotate too
   (``TransformerConfig.rotates`` says yes of every type);
   ``no_output_norms``: two norms a layer (``transformer_layer`` is handed
   the config with ``sublayer_output_norm`` off); ``no_scale``:
   ``route_scale`` 1 (``models/moe.py::_route`` is handed the config with
   ``moe_routed_scale`` 1); ``bias_in_gates``: the gates are the scores
   PLUS the choice bias, renormalised and scaled; ``no_multiplier``: the
   embeddings as they lie (``embedding_forward`` is handed the config with
   no ``embedding_multiplier``); ``all_full``: every layer attends every
   key (``TransformerConfig.attention_of`` hands a sliding layer no
   window, whose pages have gone back to the allocator);
   ``dense_layer_sparse``: the second dense layer runs the first sparse
   layer's MLP in place of its own; ``float8_activations``: every normed
   activation of the stack, the output norms' among them, is rounded to
   float8 (e4m3), the nearest precision below the stated bf16 (rounding
   the WEIGHTS would show nothing: the reference reads the engine's
   weights).  An output norm hides any uniform scale of a sublayer's
   output, so no control of that form is planted.

2. FAULTY REFERENCES against the sound engine, position by position::

       python3 benchmarks/reference/trinity_controls.py --readings \\
           --seed 2147484074 2147484003 --faults no_gate no_scale

   builds the server as the cell does, serves the probe's request and
   prints what ``trinity_probe.py::engine_against_reference`` reads (every
   tapped position's distance, the share of experts that differ, the
   router's slack) and the token deficits ``harness/probe.py`` would
   read, for the sound reference and for each faulty one (``trinity.py``'s
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

CELL = "trinity-mini-serve.agent-16k"
FAULTS = ("no_gate", "full_rotates", "no_output_norms", "no_scale",
          "bias_in_gates", "no_multiplier", "all_full",
          "dense_layer_sparse", "no_shared", "no_qk_norm", "float8")
CONTROLS = ("no_gate", "full_rotates", "no_output_norms", "no_scale",
            "bias_in_gates", "no_multiplier", "all_full",
            "dense_layer_sparse", "float8_activations")

# what is the same for every cell's controls (the server built as the
# cell builds it, its weights made again from another seed, a note's
# line) is keye_controls.py's, loaded as a private copy for this cell
_spec = importlib.util.spec_from_file_location(
    "bench_trinity_shared_controls", os.path.join(HERE, "keye_controls.py"))
_shared = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_shared)
_shared.CELL = CELL
note, build, new_weights = _shared.note, _shared.build, _shared.new_weights


# ---------------------------------------------------------------------------
# 1. a fault in the program's place
# ---------------------------------------------------------------------------

def _dense_layer_sparse(tfm) -> None:
    """The LAST leading dense layer runs the first sparse layer's MLP
    (its router, its shared expert, its experts) under its own norms:
    ``transformer_stack`` notes the sparse layers' MLPs as it is entered,
    and the dense layer takes layer 0 of them."""
    import dataclasses

    import jax

    stack, layer = tfm.transformer_stack, tfm.transformer_layer
    seen = {}

    def noting_stack(x, stack_params, cfg, **kw):
        seen["mlp"] = stack_params["layers"]["mlp"]
        seen["dense_left"] = cfg.moe_first_dense_layers
        return stack(x, stack_params, cfg, **kw)

    def swapping_layer(x, params, cfg, **kw):
        if "experts" in params["mlp"]:
            return layer(x, params, cfg, **kw)
        seen["dense_left"] -= 1
        if seen["dense_left"]:
            return layer(x, params, cfg, **kw)
        mlp = seen["mlp"]
        first = jax.tree_util.tree_map(
            lambda a: a[0], {k: v for k, v in mlp.items() if k != "experts"})
        out, cache, aux = layer(
            x, {**params, "mlp": {**first, "experts": mlp["experts"]}}, cfg,
            **{**kw, "moe_layer": 0})
        if getattr(cache, "moe_counts", None) is not None:
            # a dense layer routes nothing: the engine's routing record
            # keeps a row a sparse layer, as the reference counts them
            cache = dataclasses.replace(cache, moe_counts=None)
        return out, cache, aux

    tfm.transformer_layer = swapping_layer
    # the forward took the stack by name when it was imported
    from megatron_llm_tpu.models import language_model

    language_model.transformer_stack = noting_stack


def plant(control: str) -> None:
    import jax.numpy as jnp
    from megatron_llm_tpu.config import TransformerConfig
    from megatron_llm_tpu.models import language_model, moe
    from megatron_llm_tpu.models import transformer as tfm

    if control == "no_gate":
        split = tfm._split_qkv
        tfm._split_qkv = lambda mixed, cfg: split(mixed, cfg)[:3] + (None,)
    elif control == "full_rotates":
        TransformerConfig.rotates = lambda self, layer_type: True
    elif control == "no_output_norms":
        layer = tfm.transformer_layer
        tfm.transformer_layer = lambda x, params, cfg, **kw: layer(
            x, params, cfg.replace(sublayer_output_norm=False), **kw)
    elif control == "no_scale":
        route = moe._route
        moe._route = lambda x, params, cfg: route(
            x, params, cfg.replace(moe_routed_scale=1.0))
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
    elif control == "no_multiplier":
        embed = language_model.embedding_forward
        language_model.embedding_forward = (
            lambda tokens, position_ids, params, cfg, **kw: embed(
                tokens, position_ids, params,
                cfg.replace(embedding_multiplier=None), **kw))
    elif control == "all_full":
        sound = TransformerConfig.attention_of
        TransformerConfig.attention_of = (
            lambda self, layer_type: (None, sound(self, layer_type)[1]))
    elif control == "dense_layer_sparse":
        _dense_layer_sparse(tfm)
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

    probe = spec.load_module("reference", "trinity_probe")
    weights_cls = spec.load_module("reference",
                                   "trinity_from_program").ProgramWeights
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
                 logit_std=float(jnp.std(here)),
                 seconds=time.perf_counter() - t0, **report)
    engine.stop()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=CONTROLS)
    ap.add_argument("--readings", action="store_true")
    ap.add_argument("--seed", type=int, nargs="+", default=[2147484074])
    ap.add_argument("--faults", nargs="*", default=list(FAULTS),
                    choices=FAULTS + ("bf16",))
    ap.add_argument("--faults_later", nargs="*", default=["no_scale"],
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
