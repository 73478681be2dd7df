#!/usr/bin/env python
"""Compile-time evidence for the fused flash backward (VERDICT r4 #8).

The real libtpu compiler is local and needs no chip: this tool
AOT-compiles the bench-config
train step (llama-650M: L10 h2048 d128, the shape `bench.py` measures)
for a single virtual v5e chip at seq 2048/4096/8192, with the fused
single-pass flash backward ON vs OFF, and records what the compiler
itself reports — `cost_analysis()` FLOPs / bytes-accessed,
`memory_analysis()` temp/total HBM, and optimized-HLO op counts
(fusions, custom-calls = pallas kernels, while loops).

These are COMPILE-TIME numbers, not MFU: they show the fused path's
effect on compiled HBM traffic and kernel count.  A chip run
supersedes them.

Same one-process-per-compile structure as tools/aot_memcheck.py (the
local libtpu accepts one client at a time — /tmp/libtpu_lockfile).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

GB = 1 << 30

# (label, seq, micro_batch, fused_backward) — shapes mirror the
# tools/mfu_sweep.py `fusedbwd` trial group so on-chip numbers, when
# they land, are directly comparable.
TRIALS = [
    ("seq2048-twokernel", 2048, 4, False),
    ("seq2048-fused", 2048, 4, True),
    ("seq4096-twokernel", 4096, 2, False),
    ("seq4096-fused", 4096, 2, True),
    ("seq8192-twokernel", 8192, 1, False),
    ("seq8192-fused", 8192, 1, True),
    # fused-CE flip-point insurance (VERDICT r4 #7 is chip-gated; these
    # record the compiler-visible memory/traffic effect at 128k vocab).
    # Smaller body (L4 h1024): at the full bench shape the 128k-vocab
    # model's fp32 optimizer state alone nears the 16 GB HBM and both
    # variants OOM at compile, drowning the CE difference.
    # (label, seq, mb, fused_bwd, vocab, fused_ce, shape)
    ("vocab128k-plainCE", 2048, 4, True, 131072, False, "small"),
    ("vocab128k-fusedCE", 2048, 4, True, 131072, True, "small"),
]

SHAPES = {
    # the bench.py llama-650M shape (docs/perf_tpu.md)
    "bench": dict(num_layers=10, hidden_size=2048, num_attention_heads=16,
                  ffn_hidden_size=5632),
    # d=128 kept (MXU alignment), small body for memory-edge trials
    "small": dict(num_layers=4, hidden_size=1024, num_attention_heads=8,
                  ffn_hidden_size=2816),
}


def run_trial(label: str, seq: int, mb: int, fused: bool,
              vocab: int = 32000, fused_ce: bool = False,
              shape: str = "bench") -> dict:
    # off-GCP the metadata server 403s and libtpu retries each variable
    # 30x with backoff before the topology init can proceed — skip it
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    import megatron_llm_tpu.ops.pallas.flash_attention as fa
    from megatron_llm_tpu.config import ParallelConfig, TrainConfig
    from megatron_llm_tpu.models.llama import LlamaModel, llama_config
    from megatron_llm_tpu.optimizer import MegatronOptimizer
    from megatron_llm_tpu.training import build_train_step

    fa.FUSED_BACKWARD = fused

    # smallest expressible v5e topology is one 2x2 host; the program is
    # compiled single-device on its first chip (no collectives), so the
    # memory/cost analysis is the 1-chip bench-config story
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = topo.devices[0]

    cfg = llama_config(
        "tiny", **SHAPES[shape], padded_vocab_size=vocab, seq_length=seq,
        max_position_embeddings=seq, params_dtype="bf16",
        compute_dtype="bf16", recompute_granularity="selective",
        use_flash_attn=True, use_fused_rmsnorm=True,
        fused_lm_cross_entropy=fused_ce)
    model = LlamaModel(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n_params = sum(int(x.size)
                   for x in jax.tree_util.tree_leaves(params_shape))

    tc = TrainConfig(micro_batch_size=mb, global_batch_size=mb,
                     train_iters=0, lr=1e-4, optimizer="adam", bf16=True,
                     clip_grad=1.0)
    opt = MegatronOptimizer(tc, params_dtype=jnp.bfloat16)
    opt_shape = jax.eval_shape(opt.init, params_shape)
    step = build_train_step(model, opt, ParallelConfig(), 1)

    batch = {
        "tokens": jax.ShapeDtypeStruct((1, mb, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((1, mb, seq), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((1, mb, seq), jnp.float32),
    }
    print(f"[{label}] lowering ({n_params/1e6:.0f}M params, "
          f"{dev.device_kind})...", file=sys.stderr, flush=True)
    # donate params/opt_state like the real bench jit (build_train_step's
    # inner donation doesn't survive the outer device-pinning jit), so
    # memory_analysis aliases them instead of double-counting
    lowered = jax.jit(step, device=dev, donate_argnums=(0, 1)).lower(
        params_shape, opt_shape, batch,
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32))
    print(f"[{label}] compiling...", file=sys.stderr, flush=True)
    compiled = lowered.compile()

    ma = compiled.memory_analysis()
    mem = {
        "temp_gb": round(int(ma.temp_size_in_bytes) / GB, 3),
        "total_gb": round(
            (int(ma.argument_size_in_bytes) + int(ma.output_size_in_bytes)
             + int(ma.temp_size_in_bytes) - int(ma.alias_size_in_bytes))
            / GB, 3),
    }
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        for k in ("flops", "bytes accessed", "transcendentals"):
            if k in ca:
                cost[k.replace(" ", "_")] = float(ca[k])
    except Exception as e:
        cost = {"error": str(e)[:100]}

    ops = {}
    try:
        txt = compiled.as_text()
        ops = {
            "custom_calls": txt.count(" custom-call("),
            "fusions": txt.count(" fusion("),
            "while_loops": txt.count(" while("),
        }
    except Exception as e:
        ops = {"error": str(e)[:100]}

    rec = {"trial": label, "seq": seq, "micro_batch": mb, "fused": fused,
           "vocab": vocab, "fused_ce": fused_ce,
           "memory": mem, "cost": cost, "hlo_ops": ops}
    print(json.dumps(rec), flush=True)
    return rec


def main(argv):
    if argv and argv[0] == "--child":
        label = argv[1]
        t = next(t for t in TRIALS if t[0] == label)
        run_trial(*t)
        return 0

    wanted = [t for t in TRIALS if not argv or t[0] in argv]
    env = dict(os.environ)
    env.pop("JAX_PLATFORM_NAME", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    env["TPU_ACCELERATOR_TYPE"] = "v5litepod-4"
    # AOT children lower for a TPU topology with a CPU default backend;
    # without this the kernels silently compile as their XLA fallbacks
    env["MLT_FORCE_PALLAS"] = "1"
    rc = 0
    rows = []
    for t in wanted:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", t[0]],
            env=env, cwd=REPO, capture_output=True, text=True)
        sys.stderr.write(r.stderr[-2000:])
        for line in r.stdout.splitlines():
            if line.startswith("{"):
                rows.append(json.loads(line))
                print(line, flush=True)
        rc |= r.returncode
    if rows:
        print(f"\n{'trial':24} {'temp GB':>8} {'total GB':>9} "
              f"{'GFLOP':>10} {'GB accessed':>12} {'kernels':>8}")
        for r in rows:
            c = r["cost"]
            print(f"{r['trial']:24} {r['memory']['temp_gb']:8.3f} "
                  f"{r['memory']['total_gb']:9.3f} "
                  f"{c.get('flops', 0)/1e9:10.1f} "
                  f"{c.get('bytes_accessed', 0)/GB:12.2f} "
                  f"{r['hlo_ops'].get('custom_calls', -1):8d}")
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
