"""Controls for the limits of Brumby's probe: what the readings in
``configs/brumby-14b-serve.json`` were made with.  Not part of a
benchmark run; the chip, one process a call.  The method is
``keye_controls.py``'s, in ``lfm2_controls.py``'s form.

1. A FAULT IN THE PROGRAM'S PLACE, through the harness, which must come
   out ``correct: false``::

       python3 benchmarks/reference/brumby_controls.py --control state_bf16 -- \\
           --workload brumby-14b-serve.continue-16k --seed 2147484001 \\
           --seconds 5 --trace 0

   patches the program in this process and then runs ``benchmarks/run.py``
   on the arguments after ``--``: the cell's own engine, traffic and probe.
   ``state_bf16``: ``S`` and ``z`` are rounded to bf16 whenever a chunk
   or a step writes them (the nearest precision below the stated
   float32; the pool stays float32 so that the step's kernel is the
   timed one); ``step_products_bf16``: in the step's kernel ``phi(k)
   v^T`` is rounded to bf16 before it is summed INTO the state;
   ``state_not_handed_on``: a chunk reads zeros for the carried state
   (``PagedKVCache.read_state`` of a chunk), a decode step its slot's;
   ``no_sqrt2``: ``phi``'s cross terms at weight 1 in every form (the
   chunk's blocks keep ``(q . k)^2`` inside them);
   ``kv_neighbour``: a query head reads its neighbour's key-value head's
   state; ``no_gate``: ``a = 0``; ``sum_not_decayed``: ``z`` is not
   decayed with ``S``; ``own_term_decayed``: the gate applied to a
   token's own term too; ``no_normaliser``: the numerator alone;
   ``degree_one``: ``q . k`` to the first power (``phi`` the identity in
   its first rotation); ``no_rope``, ``no_qk_norm``;
   ``float8_activations``: every normed activation of the stack rounded
   to float8 (e4m3).

2. FAULTY REFERENCES against the sound engine, position by position, ALL
   IN ONE PROCESS (a fault in the program's place costs a start each)::

       python3 benchmarks/reference/brumby_controls.py --readings \\
           --seed 2147484074 2147484003 --faults degree_one no_gate

   builds the server as the cell does, serves the probe's request and
   prints what ``brumby_probe.py::engine_against_reference`` reads
   (every tapped position's distance, one layer's state against the
   reference's) and the token deficits ``harness/probe.py`` would read,
   for the sound reference and for each faulty one (``brumby.py``'s
   ``faults``).  ``--faults`` are read on the first seed,
   ``--faults_later`` on every later one; later seeds reuse the engine
   with new weights.
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

CELL = "brumby-14b-serve.continue-16k"
FAULTS = ("degree_one", "no_normaliser", "no_sqrt2", "no_gate",
          "own_term_decayed", "sum_not_decayed", "state_dropped_at_chunks",
          "kv_neighbour", "no_rope", "no_qk_norm", "float8")
CONTROLS = ("state_bf16", "step_products_bf16", "state_not_handed_on",
            "no_sqrt2", "kv_neighbour", "no_gate", "sum_not_decayed",
            "own_term_decayed", "no_normaliser", "degree_one", "no_rope",
            "no_qk_norm", "float8_activations")

_spec = importlib.util.spec_from_file_location(
    "bench_brumby_shared_controls", os.path.join(HERE, "keye_controls.py"))
_shared = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_shared)
_shared.CELL = CELL
note, build, new_weights = _shared.note, _shared.build, _shared.new_weights


# ---------------------------------------------------------------------------
# 1. a fault in the program's place
# ---------------------------------------------------------------------------

def _rewritten(module, name: str, changes) -> None:
    """``module.name`` defined again from its own source with each
    ``(sound line, faulty line)`` of ``changes`` exchanged: a control,
    not the program."""
    import inspect

    fn = getattr(module, name)
    source = inspect.getsource(getattr(fn, "__wrapped__", fn))
    for sound, faulty in changes:
        assert sound in source, (name, sound)
        source = source.replace(sound, faulty)
    scope = dict(vars(module))
    exec(source, scope)  # noqa: S102 - a control, not the program
    setattr(module, name, scope[name])


def plant(control: str) -> None:
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.models import retention
    from megatron_llm_tpu.models import transformer as tfm
    from megatron_llm_tpu.ops import paged_kv
    from megatron_llm_tpu.ops.pallas import retention_step as rs

    def bf16(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def bf16_in_kernel(x):
        # Mosaic has no reduce_precision and does the casts it is given
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    step_line = "S = decay * S + v_col * ph[r:r + 1, :]"
    dense_lines = ("    S = (jnp.exp(a)[..., None, None, None] * S\n"
                   "         + v[..., None, :, None] * phi(k)"
                   "[..., :, None, :])")
    if control == "state_bf16":
        vars(rs).update(bf16=bf16, bf16_in_kernel=bf16_in_kernel)
        # dense_sum_step first: what is defined again keeps the module as
        # it stood then
        _rewritten(rs, "dense_sum_step", [(
            "    return jnp.einsum(", "    z = bf16(z)\n    return jnp.einsum(")])
        _rewritten(rs, "_body", [("            state[t] = S\n",
                                  "            S = bf16_in_kernel(S)\n"
                                  "            state[t] = S\n")])
        _rewritten(rs, "dense_retention_step", [(
            "    num = jnp.einsum(", "    S = bf16(S)\n    num = jnp.einsum(")])
        sound_write = paged_kv.PagedKVCache.write_state
        paged_kv.PagedKVCache.write_state = lambda self, *arrays: sound_write(
            self, *(bf16(a) for a in arrays))
    elif control == "step_products_bf16":
        vars(rs).update(bf16_in_kernel=bf16_in_kernel)
        _rewritten(rs, "_body", [(
            step_line,
            "S = decay * S + bf16_in_kernel(v_col * ph[r:r + 1, :])")])
    elif control == "state_not_handed_on":
        sound_read = paged_kv.PagedKVCache.read_state

        def read_state(self):
            held = sound_read(self)
            # a chunk carries its rows' slots; a decode step none
            return held if self.slots is None else jax.tree_util.tree_map(
                jnp.zeros_like, held)

        paged_kv.PagedKVCache.read_state = read_state
    elif control == "no_sqrt2":
        rs.phi_weights = lambda d: (1.0,) * rs.rotations(d)
    elif control == "kv_neighbour":
        _rewritten(retention, "retention_mixer", [(
            "    q = q.reshape(b, n, g, r, d)\n",
            "    q = jnp.roll(q.reshape(b, n, g, r, d), 1, axis=2)\n")])
    elif control == "no_gate":
        _rewritten(retention, "retention_mixer", [(
            "    q = q.reshape(b, n, g, r, d)\n",
            "    q = q.reshape(b, n, g, r, d)\n    a = jnp.zeros_like(a)\n")])
    elif control == "sum_not_decayed":
        _rewritten(rs, "dense_sum_step", [(
            "z = jnp.exp(a)[..., None, None] * z + phi(k)", "z = z + phi(k)")])
        _rewritten(retention, "retention_chunk", [(
            "z = kept[..., None, None] * z + pk.astype(f32).sum(axis=1)",
            "z = z + _phi_held(kb, cdtype).astype(f32).sum(axis=1)"), (
            "den = den + before * jnp.einsum(", "den = den + jnp.einsum(")])
    elif control == "own_term_decayed":
        _rewritten(rs, "dense_sum_step", [(
            "z = jnp.exp(a)[..., None, None] * z + phi(k)",
            "z = jnp.exp(a)[..., None, None] * (z + phi(k))")])
        _rewritten(rs, "_body", [(step_line,
                                  "S = decay * (S + v_col * ph[r:r + 1, :])")])
        _rewritten(rs, "dense_retention_step", [(dense_lines, (
            "    S = jnp.exp(a)[..., None, None, None] * (S\n"
            "         + v[..., None, :, None] * phi(k)[..., :, None, :])"))])
        _rewritten(retention, "retention_chunk", [
            ("seg = A[:, :, None, :] - A[:, None, :, :]",
             "seg = A[:, :, None, :] - A[:, None, :, :] + ab[:, None, :, :]"),
            ("to_end = jnp.exp(A[:, -1:, :] - A)",
             "to_end = jnp.exp(A[:, -1:, :] - A + ab)")])
    elif control == "no_normaliser":
        _rewritten(retention, "retention_mixer", [
            ("out = num / jnp.where(live, den, 1.0)[..., None]", "out = num"),
            ("out = num / jnp.where(live[..., None], den, 1.0)[..., None]",
             "out = num")])
    elif control == "degree_one":
        def first_power(x):
            x = x.astype(jnp.float32)
            O = rs.rotations(x.shape[-1])
            return jnp.stack([x] + [jnp.zeros_like(x)] * (O - 1), axis=-2)

        def first_held(x, cdtype, scale=None):
            x = x.astype(jnp.float32)
            return retention._held(first_power(
                x if scale is None else x * scale[..., None]), cdtype)

        rs.phi, retention._phi_held = first_power, first_held
        _rewritten(rs, "_body", [(
            "tab[o] = weights[o] * pltpu.roll(x, (d - o) % d, axis=1)",
            "tab[o] = jnp.full_like(x, float(o == 0))")])
        _rewritten(retention, "retention_chunk", [("jnp.square(qk)", "qk")])
    elif control in ("no_rope", "no_qk_norm"):
        sound = tfm.qkv_heads
        change = (dict(position_embedding_type="none")
                  if control == "no_rope" else dict(qk_norm_per_head=False))
        tfm.qkv_heads = lambda x, params, cfg, **kw: sound(
            x, params, cfg.replace(**change), **kw)
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

    probe = spec.load_module("reference", "brumby_probe")
    weights_cls = spec.load_module("reference",
                                   "brumby_from_program").ProgramWeights
    cell, generator = build(seeds[0], rehearse, more_flags)
    engine = generator.engine
    cfg = dict(cell.config)
    cfg.update(shape.model_shape(engine.model.cfg))
    cfg.update(probe.shape_as_run(engine.model.cfg))
    p = dict(cfg["probe"])
    if rehearse:
        p.update(p["rehearsal"])
        cfg["fault_chunk"] = int(engine.config.prefill_chunk)
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
        run = None
        for name in ["sound"] + list(faults if k == 0 else faults_later):
            t0 = time.perf_counter()
            report, within, here, run = probe.engine_against_reference(
                engine, weights, cfg, p, tokens, run=run,
                faults=frozenset([name]) - {"sound"})
            chosen = jnp.take_along_axis(
                here, jnp.asarray(answer, jnp.int32)[:, None], axis=-1)[:, 0]
            deficit = np.asarray(jnp.max(here, axis=-1) - chosen)
            note("reading", seed=seed, reference=name, within=within,
                 deficit_worst=float(np.max(deficit)),
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
