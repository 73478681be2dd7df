"""Controls for the limits of Qwen3-Next's probe: what the readings in
``configs/qwen3-next-80b-a3b-serve.json`` were made with.  Not part of a
benchmark run; the chip, one process a call.  The method is
``keye_controls.py``'s, in ``granite_controls.py``'s form.

1. A FAULT IN THE PROGRAM'S PLACE, through the harness, which must come
   out ``correct: false``::

       python3 benchmarks/reference/qwen3_next_controls.py \\
           --control no_delta -- \\
           --workload qwen3-next-80b-a3b-serve.docs-32k --seed 2147484001 \\
           --seconds 5 --trace 0

   patches the program in this process and then runs ``benchmarks/run.py``
   on the arguments after ``--``: the cell's own engine, traffic and probe.
   The delta rule's own (``models/gated_delta.py``,
   ``ops/pallas/delta_step.py``): ``no_delta`` (the ``S'^T k`` term left
   out, a plain additive state) and ``decay_after`` (``d_t`` formed from
   the state before it is decayed): the chunk and the step both run the
   faulty recurrence one token at a time (``faulty_chunk``,
   ``faulty_step``), since the fault is in the algebra the block form
   solves; ``no_beta`` (``beta`` 1 at every real token) and ``no_decay``
   (``g`` 0): the sound chunk and step over those gates; ``no_l2norm``,
   ``no_q_scale``, ``kv_neighbour`` (value head i reading key head ``i
   mod 16``), ``no_z_gate``, ``norm_after_gate``: the mixer's own helpers
   replaced; ``state_not_handed_on`` / ``conv_not_handed_on``: a chunk
   reads zeros in place of the state (the columns) the chunk before it
   left; ``state_bf16``: ``S`` kept in bf16 in its slot
   (``ops/paged_kv.py::SSM_STATE_DTYPE``: the ASSUMPTION of float32 left
   out).  The stack's: ``full_rotary`` (the whole head rotated),
   ``no_attn_gate``, ``no_shared_gate``, ``scale_is_w`` (every stream
   norm's ``1 + w`` read as ``w``), ``nine_experts``,
   ``float8_activations`` (every normed activation of the stack rounded
   to e4m3, the nearest precision below the stated bf16).

2. FAULTY REFERENCES against the sound engine, position by position::

       python3 benchmarks/reference/qwen3_next_controls.py --readings \\
           --seed 2147484074 2147484003 --faults no_delta state_bf16

   builds the server as the cell does, serves the probe's request and
   prints what ``qwen3_next_probe.py::engine_against_reference`` reads
   (every tapped position's distance, the share of experts that differ,
   the router's slack, each delta layer's state against the reference's)
   and the token deficits ``harness/probe.py`` would read, for the sound
   reference and for each faulty one (``qwen3_next.py``'s ``faults``),
   the engine's experts given to each alike.  ``--faults`` are read on
   the first seed, ``--faults_later`` on every later one; later seeds
   reuse the engine with new weights.
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

CELL = "qwen3-next-80b-a3b-serve.docs-32k"
FAULTS = ("no_delta", "no_beta", "no_decay", "decay_after", "no_l2norm",
          "no_q_scale", "kv_neighbour", "state_dropped_at_chunks",
          "conv_dropped_at_chunks", "no_z_gate", "norm_after_gate",
          "state_bf16", "full_rotary", "no_attn_gate", "no_shared_gate",
          "scale_is_w", "nine_experts", "float8")
CONTROLS = ("no_delta", "no_beta", "no_decay", "decay_after", "no_l2norm",
            "no_q_scale", "kv_neighbour", "state_not_handed_on",
            "conv_not_handed_on", "no_z_gate", "norm_after_gate",
            "state_bf16", "full_rotary", "no_attn_gate", "no_shared_gate",
            "scale_is_w", "nine_experts", "float8_activations")

# what is the same for every cell's controls (the server built as the
# cell builds it, its weights made again from another seed, a note's
# line) is keye_controls.py's, loaded as a private copy for this cell
_spec = importlib.util.spec_from_file_location(
    "bench_qwen3_next_shared_controls",
    os.path.join(HERE, "keye_controls.py"))
_shared = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_shared)
_shared.CELL = CELL
note, build, new_weights = _shared.note, _shared.build, _shared.new_weights


# ---------------------------------------------------------------------------
# 1. a fault in the program's place
# ---------------------------------------------------------------------------

def faulty_step(S, q, k, v, g, beta, fault: str):
    """``dense_gated_delta_step`` with ``fault`` in its algebra."""
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.ops.pallas import delta_step as ds

    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    r = S.shape[1] // q.shape[1]
    q = ds.for_value_heads(q.astype(f32), r)
    k = ds.for_value_heads(k.astype(f32), r)
    decayed = jnp.exp(g)[..., None, None] * S
    read = S if fault == "decay_after" else decayed
    answered = (0.0 if fault == "no_delta" else jnp.einsum(
        "bhkv,bhk->bhv", read, k, precision=hi))
    d = beta[..., None] * (v.astype(f32) - answered)
    S = decayed + k[..., :, None] * d[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", S, q, precision=hi), S


def faulty_chunk(q, k, v, g, beta, S, cdtype, fault: str):
    """``gated_delta_chunk``'s contract by the faulty recurrence, one
    token at a time."""
    import jax
    import jax.numpy as jnp

    def step(S, xs):
        o, S = faulty_step(S, *xs, fault)
        return S, o

    S, o = jax.lax.scan(step, S.astype(jnp.float32), tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def plant(control: str) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.models import gated_delta as gd
    from megatron_llm_tpu.models import moe
    from megatron_llm_tpu.models import transformer as tfm
    from megatron_llm_tpu.ops import paged_kv
    from megatron_llm_tpu.ops.pallas import delta_step as ds
    from megatron_llm_tpu.ops.paged_kv import PagedKVCache

    def over_gates(change):
        """The sound chunk and step over gates ``change(g, beta)``."""
        chunk, step = gd.gated_delta_chunk, PagedKVCache.step_delta
        gd.gated_delta_chunk = lambda q, k, v, g, beta, S, cd: chunk(
            q, k, v, *change(g, beta), S, cd)
        PagedKVCache.step_delta = lambda self, q, k, v, g, beta: step(
            self, q, k, v, *change(g, beta))

    if control in ("no_delta", "decay_after"):
        gd.gated_delta_chunk = lambda *a: faulty_chunk(*a, control)

        def step_delta(self, q, k, v, g, beta):
            pool = self.pool["delta_state"]
            live, fresh = self.valid_lens > 0, self.context_lens == 0
            o, new = faulty_step(self._rows(pool, fresh), q, k, v, g, beta,
                                 control)
            return o, dataclasses.replace(self, pool={
                **self.pool, "delta_state": self._put(pool, new, live)})

        PagedKVCache.step_delta = step_delta
    elif control == "no_beta":
        # a token that is not real keeps its beta of 0
        over_gates(lambda g, beta: (g, (beta > 0).astype(beta.dtype)))
    elif control == "no_decay":
        over_gates(lambda g, beta: (jnp.zeros_like(g), beta))
    elif control == "no_l2norm":
        gd.l2norm = lambda x: x
    elif control == "no_q_scale":
        gd.query_scale = lambda d_key: 1.0
    elif control == "kv_neighbour":
        def tiled(x, r, axis=1):
            return jnp.concatenate([x] * r, axis=axis)

        ds.for_value_heads = gd.for_value_heads = tiled
    elif control == "no_z_gate":
        gd.gated_norm = lambda o, z, scale, eps: gd.rms_norm(
            o, scale.astype(jnp.float32), eps=eps).reshape(z.shape)
    elif control == "norm_after_gate":
        gd.gated_norm = lambda o, z, scale, eps: gd.rms_norm(
            o * jax.nn.silu(z.astype(jnp.float32)).reshape(o.shape),
            scale.astype(jnp.float32), eps=eps).reshape(z.shape)
    elif control in ("state_not_handed_on", "conv_not_handed_on"):
        sound_read = PagedKVCache.read_state

        def read_state(self):
            held = sound_read(self)
            if self.slots is None or len(held) != 2:
                return held         # a decode step, or no delta layer's
            conv, S = held
            return ((conv, jnp.zeros_like(S))
                    if control == "state_not_handed_on"
                    else (jnp.zeros_like(conv), S))

        PagedKVCache.read_state = read_state
    elif control == "state_bf16":
        paged_kv.SSM_STATE_DTYPE = jnp.bfloat16
    elif control == "full_rotary":
        sound = tfm.qkv_heads
        tfm.qkv_heads = lambda x, params, cfg, **kw: sound(
            x, params, cfg.replace(rotary_percent=1.0), **kw)
    elif control == "no_attn_gate":
        sound = tfm._split_qkv
        tfm._split_qkv = lambda mixed, cfg: sound(mixed, cfg)[:3] + (None,)
    elif control == "no_shared_gate":
        sound = moe._shared_mlp
        moe._shared_mlp = lambda x, params, cfg: sound(
            x, params, cfg.replace(moe_shared_expert_gate=False))
    elif control == "scale_is_w":
        norm = tfm.apply_norm
        tfm.apply_norm = lambda x, p, *a, **kw: norm(
            x, {**p, "scale": p["scale"] - jnp.ones((), p["scale"].dtype)},
            *a, **kw)
    elif control == "nine_experts":
        sound = moe.moe_mlp_dropless
        dropless = lambda x, params, cfg, *a, **kw: sound(  # noqa: E731
            x, params, cfg.replace(moe_top_k=cfg.moe_top_k - 1), *a, **kw)
        moe.moe_mlp_dropless = tfm.moe_mlp_dropless = dropless
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

    probe = spec.load_module("reference", "qwen3_next_probe")
    weights_cls = spec.load_module("reference",
                                   "qwen3_next_from_program").ProgramWeights
    cell, generator = build(seeds[0], rehearse, more_flags)
    engine = generator.engine
    cfg = dict(cell.config)
    cfg.update(shape.model_shape(engine.model.cfg))
    cfg.update(probe.shape_as_run(engine.model.cfg))
    p = dict(cfg["probe"])
    if rehearse:
        p.update(p["rehearsal"])
    probe.TAPPED["chunks"] = tuple(p["tapped_chunks"])
    p["prefill_rows"] = len(p["tapped_chunks"])
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
