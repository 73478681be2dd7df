"""Controls for the limits of Ouro's probe: what the readings in
``configs/ouro-2.6b-serve.json`` were made with.  Not part of a benchmark
run; the chip, one process a call.  The method is ``keye_controls.py``'s,
in ``glm5_controls.py``'s form.

1. A FAULT IN THE PROGRAM'S PLACE, through the harness, which must come
   out ``correct: false``::

       python3 benchmarks/reference/ouro_controls.py \\
           --control shared_planes -- \\
           --workload ouro-2.6b-serve.reason-2k --seed 2147484001 \\
           --seconds 5 --trace 0

   patches the program in this process and then runs ``benchmarks/run.py``
   on the arguments after ``--``: the cell's own engine, traffic and probe.
   ``shared_planes``: ONE plane a layer shared by the passes (the paper's
   sharing at decode time, and the likeliest bug): every pass of a layer
   is handed the pool the pass before it left, so each overwrites and
   reads the same keys, and every plane of the layer is left as the last
   pass left it; ``previous_plane``: pass t's queries attend the history
   of the plane pass t - 1 wrote (the first pass its own), under its own
   keys of the launch's tokens; ``three_passes``: the last pass's
   layers pass the stream through; ``norm_once``: the final norm after
   the last pass alone; ``no_output_norms``: two norms a layer
   (``transformer_layer`` is handed the config with
   ``sublayer_output_norm`` off); ``theta_1e4``: rotary at theta 10,000;
   ``float8_activations``: every normed activation of the stack, the
   output norms' among them, is rounded to float8 (e4m3), the nearest
   precision below the stated bf16 (rounding the WEIGHTS would show
   nothing: the reference reads the engine's weights).

2. FAULTY REFERENCES against the sound engine, position by position::

       python3 benchmarks/reference/ouro_controls.py --readings \\
           --seed 2147484074 2147484003 --faults shared_planes float8

   builds the server as the cell does, serves the probe's request and
   prints what ``ouro_probe.py::engine_against_reference`` reads (every
   tapped position's distance) and the token deficits
   ``harness/probe.py`` would read, for the sound reference and for each
   faulty one (``ouro.py``'s ``faults``).  ``--faults`` are read on the
   first seed, ``--faults_later`` on every later one; later seeds reuse
   the engine with new weights.
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

CELL = "ouro-2.6b-serve.reason-2k"
FAULTS = ("shared_planes", "previous_plane", "three_passes", "norm_once",
          "no_output_norms", "theta_1e4", "float8")
CONTROLS = ("shared_planes", "previous_plane", "three_passes", "norm_once",
            "no_output_norms", "theta_1e4", "float8_activations")

# what is the same for every cell's controls (the server built as the
# cell builds it, its weights made again from another seed, a note's
# line) is keye_controls.py's, loaded as a private copy for this cell
_spec = importlib.util.spec_from_file_location(
    "bench_ouro_shared_controls", os.path.join(HERE, "keye_controls.py"))
_shared = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_shared)
_shared.CELL = CELL
note, build, new_weights = _shared.note, _shared.build, _shared.new_weights


# ---------------------------------------------------------------------------
# 1. a fault in the program's place
# ---------------------------------------------------------------------------

def _by_pass(tfm, planted) -> None:
    """``transformer_layer`` of the serving loop with ``planted(sound,
    t, i, seen, x, params, cfg, kw)`` in its place: ``t`` and ``i`` the
    pass and the layer of the call (the loop runs the layers of pass 0,
    then of pass 1 ...), ``seen[(t, i)]`` (the cache that call was
    handed, the cache it returned) of the calls before it in this
    program."""
    sound = tfm.transformer_layer
    calls, seen = [0], {}

    def faulty(x, params, cfg, **kw):
        if kw.get("kv_cache") is None:
            return sound(x, params, cfg, **kw)
        n, L = calls[0], cfg.num_layers
        calls[0] = (n + 1) % (L * cfg.loop_steps)
        out = planted(sound, n // L, n % L, seen, x, params, cfg, kw)
        seen[(n // L, n % L)] = (kw["kv_cache"], out[1])
        if not calls[0]:
            seen.clear()
        return out

    tfm.transformer_layer = faulty


def plant(control: str) -> None:
    import dataclasses

    import jax
    from megatron_llm_tpu.models import transformer as tfm
    from megatron_llm_tpu.ops import paged_kv

    if control == "shared_planes":
        def planted(sound, t, i, seen, x, params, cfg, kw):
            if t:
                # the one plane as the pass before left it, at this
                # launch's own positions
                kw = {**kw, "kv_cache": dataclasses.replace(
                    kw["kv_cache"], pool=seen[(t - 1, i)][1].pool)}
            return sound(x, params, cfg, **kw)

        _by_pass(tfm, planted)
        pools_of = paged_kv.pools_of

        def one_plane_a_layer(caches):
            # every plane of a layer as its last pass left the one plane
            pools = pools_of(caches)
            L = len(pools) // 4
            return [pools[len(pools) - L + k % L] for k in range(len(pools))]

        paged_kv.pools_of = one_plane_a_layer
    elif control == "previous_plane":
        attend, reads = paged_kv.PagedKVCache.attend, {}

        def from_the_plane_before(self, q, k, v, *args, **kwargs):
            # the keys are written where they belong, and the queries
            # attend the plane before's history under this pass's own
            # keys of the launch's tokens
            other = reads.pop(id(self), None)
            ctx, new = attend(self, q, k, v, *args, **kwargs)
            if other is not None:
                ctx, _ = attend(other, q, k, v, *args, **kwargs)
            return ctx, new

        paged_kv.PagedKVCache.attend = from_the_plane_before

        def planted(sound, t, i, seen, x, params, cfg, kw):
            if t:
                reads[id(kw["kv_cache"])] = seen[(t - 1, i)][0]
            return sound(x, params, cfg, **kw)

        _by_pass(tfm, planted)
    elif control == "three_passes":
        def planted(sound, t, i, seen, x, params, cfg, kw):
            if t == cfg.loop_steps - 1:
                return x, kw["kv_cache"], None
            return sound(x, params, cfg, **kw)

        _by_pass(tfm, planted)
    elif control == "norm_once":
        stack, norm = tfm.transformer_stack, tfm.apply_norm
        final, seen = [None], [0]

        def stack_noting(x, stack_params, cfg, **kw):
            final[0], seen[0] = stack_params["final_norm"], 0
            return stack(x, stack_params, cfg, **kw)

        def once(x, params, *args, **kwargs):
            if params is final[0]:
                seen[0] += 1
                if seen[0] % 4:
                    return x
            return norm(x, params, *args, **kwargs)

        tfm.transformer_stack, tfm.apply_norm = stack_noting, once
        from megatron_llm_tpu.models import language_model

        language_model.transformer_stack = stack_noting
    elif control == "no_output_norms":
        layer = tfm.transformer_layer
        tfm.transformer_layer = lambda x, params, cfg, **kw: layer(
            x, params, cfg.replace(sublayer_output_norm=False), **kw)
    elif control == "theta_1e4":
        layer = tfm.transformer_layer
        freqs = tfm.rotary_freqs
        from megatron_llm_tpu.models import language_model

        language_model.rotary_freqs = lambda cfg, **kw: freqs(
            cfg.replace(rope_theta=1e4), **kw)
        tfm.transformer_layer = lambda x, params, cfg, **kw: layer(
            x, params, cfg.replace(rope_theta=1e4), **kw)
    elif control == "float8_activations":
        norm = tfm.apply_norm

        def rounded(x, *args, **kwargs):
            # ``lax.reduce_precision``: the TPU's compiler drops most of
            # an ``astype`` there and back (read on the chip, PR 63: the
            # cast pair moved the probe from 0.027 to 0.034-0.045 where
            # the float8 reference stands 0.16-0.23 away)
            y = norm(x, *args, **kwargs)
            return jax.lax.reduce_precision(y, exponent_bits=4,
                                            mantissa_bits=3)

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

    probe = spec.load_module("reference", "ouro_probe")
    weights_cls = spec.load_module("reference",
                                   "ouro_from_program").ProgramWeights
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
    ap.add_argument("--faults_later", nargs="*", default=["float8"],
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
