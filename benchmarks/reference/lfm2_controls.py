"""Controls for the limits of LFM2's probe: what the readings in
``configs/lfm2-8b-a1b-serve.json`` were made with.  Not part of a
benchmark run; the chip, one process a call.  The method is
``keye_controls.py``'s, in ``nemotron_h_controls.py``'s form.

1. A FAULT IN THE PROGRAM'S PLACE, through the harness, which must come
   out ``correct: false``::

       python3 benchmarks/reference/lfm2_controls.py --control taps_reversed -- \\
           --workload lfm2-8b-a1b-serve.sessions-128 --seed 2147484001 \\
           --seconds 5 --trace 0

   patches the program in this process and then runs ``benchmarks/run.py``
   on the arguments after ``--``: the cell's own engine, traffic and probe.
   ``taps_reversed``: a conv layer's taps applied in reverse order;
   ``state_not_handed_on``: a chunk reads zeros for the carried columns
   (``PagedKVCache.read_state`` of a chunk), a decode step its slot's;
   ``bc_swapped``: ``B`` and ``C`` exchanged; ``conv_activation``: a silu
   on the convolution's output; ``bias_in_gates``: the gates are the
   scores PLUS the choice bias, renormalised; ``no_qk_norm``: the heads
   not normed; ``no_rope``: the attention layers do not rotate;
   ``kv_neighbour``: on the kernel path a query head's 64 values are set
   in the OTHER half of the pool's 128-lane row and that half of the
   output kept: it reads its key head's neighbour; ``state_float8``: the
   carried columns are kept in float8 (e4m3) in their slot, the nearest
   dtype below the stated bf16; ``float8_activations``: every normed
   activation of the stack is rounded to float8 (e4m3), the nearest
   precision below the stated bf16 (rounding the WEIGHTS would show
   nothing: the reference reads the engine's weights).

2. FAULTY REFERENCES against the sound engine, position by position::

       python3 benchmarks/reference/lfm2_controls.py --readings \\
           --seed 2147484074 2147484003 --faults taps_reversed float8

   builds the server as the cell does, serves the probe's request and
   prints what ``lfm2_probe.py::engine_against_reference`` reads (every
   tapped position's distance, the share of experts that differ, the
   router's slack, each conv layer's columns against the reference's) and
   the token deficits ``harness/probe.py`` would read, for the sound
   reference and for each faulty one (``lfm2.py``'s ``faults``), the
   engine's experts given to each alike.  ``--faults`` are read on the
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

CELL = "lfm2-8b-a1b-serve.sessions-128"
FAULTS = ("taps_reversed", "state_dropped_at_chunks", "bc_swapped",
          "conv_activation", "bias_in_gates", "no_qk_norm", "no_rope",
          "kv_neighbour", "state_float8", "norm_max", "dense_layer_sparse",
          "float8")
CONTROLS = ("taps_reversed", "state_not_handed_on", "bc_swapped",
            "conv_activation", "bias_in_gates", "no_qk_norm", "no_rope",
            "kv_neighbour", "state_float8", "float8_activations")

# what is the same for every cell's controls (the server built as the
# cell builds it, its weights made again from another seed, a note's
# line) is keye_controls.py's, loaded as a private copy for this cell
_spec = importlib.util.spec_from_file_location(
    "bench_lfm2_shared_controls", os.path.join(HERE, "keye_controls.py"))
_shared = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_shared)
_shared.CELL = CELL
note, build, new_weights = _shared.note, _shared.build, _shared.new_weights


# ---------------------------------------------------------------------------
# 1. a fault in the program's place
# ---------------------------------------------------------------------------

def plant(control: str) -> None:
    import inspect

    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.models import moe, short_conv
    from megatron_llm_tpu.models import transformer as tfm
    from megatron_llm_tpu.ops import paged_kv

    sound_mixer = short_conv.short_conv_mixer

    def with_params(change):
        short_conv.short_conv_mixer = (
            lambda h, params, cfg, **kw: sound_mixer(h, change(params), cfg,
                                                     **kw))

    if control == "taps_reversed":
        with_params(lambda p: {**p, "conv": {
            **p["conv"], "kernel": p["conv"]["kernel"][:, ::-1]}})
    elif control == "bc_swapped":
        def swapped(p):
            k = p["in_proj"]["kernel"]
            h = k.shape[1] // 3
            return {**p, "in_proj": {"kernel": jnp.concatenate(
                [k[:, h:2 * h], k[:, :h], k[:, 2 * h:]], axis=1)}}
        with_params(swapped)
    elif control == "conv_activation":
        source = inspect.getsource(sound_mixer)
        sound_line = "y = Cg * _held(acc, cd)"
        assert sound_line in source
        scope = dict(vars(short_conv))
        exec(source.replace(  # noqa: S102 - a control, not the program
            sound_line, "y = Cg * _held(jax.nn.silu(acc), cd)"), scope)
        short_conv.short_conv_mixer = scope["short_conv_mixer"]
    elif control == "state_not_handed_on":
        sound_read = paged_kv.PagedKVCache.read_state

        def read_state(self):
            held = sound_read(self)
            # a chunk carries its rows' slots; a decode step none
            return held if self.slots is None else jax.tree_util.tree_map(
                jnp.zeros_like, held)

        paged_kv.PagedKVCache.read_state = read_state
    elif control == "state_float8":
        sound_pools = paged_kv.init_pools

        def init_pools(*args, **kwargs):
            return [{"conv_state": p["conv_state"].astype(jnp.float8_e4m3fn)}
                    if paged_kv.is_state(p) else p
                    for p in sound_pools(*args, **kwargs)]

        paged_kv.init_pools = init_pools
    elif control == "bias_in_gates":
        route = moe._route

        def biased(x, params, cfg):
            logits, probs, _, idx = route(x, params, cfg)
            gates = jnp.take_along_axis(
                probs + params["router"]["choice_bias"].astype(jnp.float32),
                idx, axis=-1)
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True)
                             + cfg.moe_gate_norm_eps)
            return logits, probs, gates * cfg.moe_routed_scale, idx

        moe._route = biased
    elif control == "no_qk_norm":
        sound = tfm.attention
        tfm.attention = lambda x, params, cfg, **kw: sound(
            x, params, cfg.replace(qk_norm_per_head=False), **kw)
    elif control == "no_rope":
        sound = tfm.attention
        tfm.attention = lambda x, params, cfg, **kw: sound(
            x, params, cfg.replace(position_embedding_type="none"), **kw)
    elif control == "kv_neighbour":
        into, out_of = paged_kv._in_own_part, paged_kv._own_part

        def other(x, rows, pack):
            # the halves of each row of pack * d exchanged
            parts = x.reshape(x.shape[:-1] + (pack, -1))
            return parts[..., ::-1, :].reshape(x.shape)

        paged_kv._in_own_part = lambda q, rows, pack: other(
            into(q, rows, pack), rows, pack)
        paged_kv._own_part = lambda ctx, rows, pack: out_of(
            other(ctx, rows, pack), rows, pack)
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

    probe = spec.load_module("reference", "lfm2_probe")
    weights_cls = spec.load_module("reference",
                                   "lfm2_from_program").ProgramWeights
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
                 logit_std=float(jnp.std(here)),
                 deficit_worst=float(np.max(deficit)),
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
