#!/usr/bin/env python
"""AOT scale-proof for the milestone configs of docs/scale_aot.md
(VERDICT r3 #3).

The 16-GB single v5e cannot *run* a 7B+ training step, but JAX + libtpu
can AOT-compile one against a **virtual TPU topology**
(``jax.experimental.topologies``) with no hardware attached, and the
compiled executable reports per-device memory
(``compiled.memory_analysis()``).  This tool compiles the TRUE shapes of
milestone configs 2-5 — Llama-2-7B TP=8, Mistral-7B TP=8 (GQA + sliding
window), Falcon-40B TP8xPP4, Llama-2-70B 3D on a v5p-256 slice — and
asserts the per-device bytes fit HBM (16 GB v5e / 95 GB v5p), recording
compiled collective counts.

Reference scaling recipes being proven: the SC21 suite
(/root/reference/examples/sc21/run_table_1.sh:14-127) and the 7B/70B
training configs in /root/reference/docs/guide/getting_started.md.

Usage:
  python tools/aot_memcheck.py [config ...]     # default: all
  python tools/aot_memcheck.py --list
  python tools/aot_memcheck.py --hlo_dir=DIR config   # + DIR/<config>.hlo.txt,
      # the step's instruction table (role | mesh edge | opcode) on stderr
      # and collectives_by_edge in the record

Each config runs in a forced-CPU subprocess (AOT needs only the local
libtpu compiler, no chip).
Prints one JSON line per config and a summary table.
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

# name -> spec.  'topology' is the libtpu topology string; devices are
# chips (v5p-256 in pod-slice naming = 256 cores = 128 megacore chips).
CONFIGS = {
    # milestone 2: Llama-2-7B TP=8 on a v5e-8 slice (16 GB HBM/chip)
    "llama2-7b-tp8": dict(
        family="llama2", size="7B", topology="v5e:2x4", accel="v5litepod-8",
        hbm_gb=16, tp=8, pp=1, vpp=None, seq=4096, micro_batch=1,
        num_micro=1, zero1=False, recompute="selective",
    ),
    # milestone 3: Mistral-7B GQA + sliding-window flash, TP=8.  Full
    # recompute: selective leaves 16.61 GB/chip (0.61 over budget); full
    # drops temp 5.16 -> 2.79 GB -> 14.41 GB/chip (measured via this tool)
    "mistral-7b-tp8": dict(
        family="mistral", size="7B", topology="v5e:2x4", accel="v5litepod-8",
        hbm_gb=16, tp=8, pp=1, vpp=None, seq=4096, micro_batch=1,
        num_micro=1, zero1=False, recompute="full",
    ),
    # trainable-batch 7B on v5e (VERDICT r4 #6): the tp8/mb1/M1 row above
    # is an existence proof with 0.17 GB headroom; this one is a config
    # you could actually train — v5e-16, tp=8 x dp=2, ZeRO-1 over dp,
    # M=8 microbatches (16 seqs/step at seq 4096), full recompute
    "llama2-7b-v5e16-m8": dict(
        family="llama2", size="7B", topology="v5e:4x4", accel="v5litepod-16",
        hbm_gb=16, tp=8, pp=1, vpp=None, seq=4096, micro_batch=1,
        num_micro=8, zero1=True, recompute="full",
    ),
    # the benchmark's training cell (mistral-7b-train-tp2dp2.pretrain-4k):
    # 5 of 32 layers on the four chips of one v5e host, tp 2 (sequence
    # parallel) x dp 2, 4 accumulated micro-batches; with --hlo_dir its
    # text is what docs/guide/collective_placement.md reads
    "mistral-7b-5l-tp2dp2": dict(
        family="mistral", size="7B", layers=5, topology="v5e:2x2",
        accel="v5litepod-4", hbm_gb=16, tp=2, pp=1, vpp=None, seq=4096,
        micro_batch=1, num_micro=4, zero1=False, recompute="selective",
    ),
    # milestone 4: Falcon-40B TP=8 x PP=4 (32 x v5p, 95 GB HBM/chip)
    "falcon-40b-tp8pp4": dict(
        family="falcon", size="40B", topology="v5p:4x4x2", accel="v5p-64",
        hbm_gb=95, tp=8, pp=4, vpp=None, seq=2048, micro_batch=1,
        num_micro=8, zero1=False,
    ),
    # milestone 5 / north star: Llama-2-70B full 3D on a v5p-256 slice
    # (128 chips): tp=8 x pp=4 x dp=4, ZeRO-1 over dp
    "llama2-70b-3d-v5p256": dict(
        family="llama2", size="70B", topology="v5p:8x4x4", accel="v5p-256",
        hbm_gb=95, tp=8, pp=4, vpp=None, seq=4096, micro_batch=1,
        num_micro=8, zero1=True,
    ),
    # Llama-3-8B (GQA 8kv, 128k vocab, theta 5e5) at seq 8192 on v5e-16:
    # the 128k-vocab head is exactly where fused CE pays (scale_aot
    # notes), so this row compiles with fused_lm_cross_entropy on
    "llama3-8b-v5e16": dict(
        family="llama3", size="llama3-8B", topology="v5e:4x4",
        accel="v5litepod-16", hbm_gb=16, tp=8, pp=1, vpp=None, seq=8192,
        micro_batch=1, num_micro=4, zero1=True, recompute="full",
        fused_ce=True,
    ),
    # beyond-reference families at scale: Qwen2-7B and Gemma-7B
    "qwen2-7b-tp8": dict(
        family="qwen2", size="7B", topology="v5p:2x2x2", accel="v5p-16",
        hbm_gb=95, tp=8, pp=1, vpp=None, seq=4096, micro_batch=1,
        num_micro=1, zero1=False,
    ),
    "gemma-7b-tp8": dict(
        family="gemma", size="7B", topology="v5p:2x2x2", accel="v5p-16",
        hbm_gb=95, tp=8, pp=1, vpp=None, seq=4096, micro_batch=1,
        num_micro=1, zero1=False,
    ),
    # SC21 weak-scaling suite rows (reference examples/sc21/run_table_1.sh
    # + arXiv 2104.04473 Table 1) mapped onto v5p topologies — GPT-2
    # architecture, seq 2048, same tp/pp split, dp fills the slice
    "sc21-1.7b": dict(
        family="gpt", shape=dict(num_layers=24, hidden_size=2304,
                                 num_attention_heads=24),
        topology="v5p:2x2x1", accel="v5p-8", hbm_gb=95, tp=1, pp=1,
        vpp=None, seq=2048, micro_batch=4, num_micro=2, zero1=True,
    ),
    "sc21-18b": dict(
        family="gpt", shape=dict(num_layers=40, hidden_size=6144,
                                 num_attention_heads=48),
        topology="v5p:4x2x2", accel="v5p-32", hbm_gb=95, tp=8, pp=1,
        vpp=None, seq=2048, micro_batch=1, num_micro=4, zero1=True,
    ),
    "sc21-175b": dict(
        family="gpt", shape=dict(num_layers=96, hidden_size=12288,
                                 num_attention_heads=96),
        topology="v5p:8x4x8", accel="v5p-512", hbm_gb=95, tp=8, pp=16,
        vpp=None, seq=2048, micro_batch=1, num_micro=32, zero1=True,
    ),
}


def _model_for(spec):
    import jax.numpy as jnp

    common = dict(
        seq_length=spec["seq"], max_position_embeddings=spec["seq"],
        params_dtype="bf16", compute_dtype="bf16",
        recompute_granularity=spec.get("recompute", "selective"),
        use_flash_attn=True,
        use_fused_rmsnorm=False,
        fused_lm_cross_entropy=spec.get("fused_ce", False),
    )
    if "layers" in spec:
        common["num_layers"] = spec["layers"]
    if spec["family"] == "gpt":
        from megatron_llm_tpu.models.gpt import GPTModel
        from megatron_llm_tpu.models.gpt2 import gpt2_config

        common.pop("use_fused_rmsnorm", None)
        return GPTModel(gpt2_config(
            "tiny", **spec["shape"], padded_vocab_size=51200,
            hidden_dropout=0.0, attention_dropout=0.0, **common))
    if spec["family"] == "qwen2":
        from megatron_llm_tpu.models.qwen2 import Qwen2Model, qwen2_config

        return Qwen2Model(qwen2_config(spec["size"], **common))
    if spec["family"] == "gemma":
        from megatron_llm_tpu.models.gemma import GemmaModel, gemma_config

        return GemmaModel(gemma_config(spec["size"], **common))
    if spec["family"] in ("llama2", "llama3"):
        from megatron_llm_tpu.models.llama import LlamaModel, llama_config

        return LlamaModel(llama_config(spec["size"], **common))
    if spec["family"] == "mistral":
        from megatron_llm_tpu.models.mistral import (
            MistralModel,
            mistral_config,
        )

        return MistralModel(mistral_config(spec["size"], **common))
    if spec["family"] == "falcon":
        from megatron_llm_tpu.models.falcon import FalconModel, falcon_config

        common.pop("use_fused_rmsnorm", None)
        return FalconModel(falcon_config(spec["size"], **common))
    raise ValueError(spec["family"])


def _abstract_with_shardings(tree, specs, mesh):
    """eval_shape pytree + logical specs -> ShapeDtypeStructs carrying
    NamedShardings (what jit.lower needs for AOT)."""
    import jax
    from jax.sharding import NamedSharding

    from megatron_llm_tpu.parallel.sharding import logical_to_mesh

    def one(x, s):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, logical_to_mesh(tuple(s))))

    return jax.tree_util.tree_map(
        one, tree, specs, is_leaf=lambda s: isinstance(s, tuple))


def run_config(name: str, hlo_dir: str = "") -> dict:
    spec = CONFIGS[name]
    # off-GCP the metadata server 403s and libtpu retries each variable
    # 30x with backoff before the topology init can proceed — skip it
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from megatron_llm_tpu import topology
    from megatron_llm_tpu.config import ParallelConfig, TrainConfig
    from megatron_llm_tpu.optimizer import MegatronOptimizer

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name=spec["topology"])
    devs = topo.devices
    tp, pp = spec["tp"], spec["pp"]
    dp = len(devs) // (tp * pp)
    mesh = topology.initialize_model_parallel(
        tensor_model_parallel_size=tp, pipeline_model_parallel_size=pp,
        virtual_pipeline_model_parallel_size=spec["vpp"], devices=devs)

    model = _model_for(spec)
    cfg = model.cfg
    key = jax.random.PRNGKey(0)
    # the tree in the form finetune.py trains it in
    from megatron_llm_tpu.parallel import glu_pairs
    params_shape = jax.eval_shape(
        lambda k: glu_pairs.for_trainer(model.init(k)), key)
    n_params = sum(
        int(np_.size) for np_ in jax.tree_util.tree_leaves(params_shape))
    pspecs = model.param_specs(params_shape)
    params_abs = _abstract_with_shardings(params_shape, pspecs, mesh)

    M, mb = spec["num_micro"], spec["micro_batch"]
    tc = TrainConfig(micro_batch_size=mb, global_batch_size=M * mb * dp,
                     train_iters=0, lr=1e-4, optimizer="adam", bf16=True,
                     clip_grad=1.0)
    pc = ParallelConfig(
        tensor_model_parallel_size=tp, pipeline_model_parallel_size=pp,
        data_parallel_size=dp,
        virtual_pipeline_model_parallel_size=spec["vpp"],
        sequence_parallel=tp > 1,
        use_distributed_optimizer=spec["zero1"],
    )
    opt = MegatronOptimizer(tc, params_dtype=jnp.bfloat16)
    opt_shape = jax.eval_shape(opt.init, params_shape)
    ospecs = opt.state_specs(pspecs, params_shape,
                             zero1=spec["zero1"] and dp > 1, dp_size=dp)
    import jax.tree_util as jtu

    def replicated(tree):
        return jtu.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, P())),
            tree)

    opt_abs = opt_shape._replace(
        step=replicated(opt_shape.step),
        grad_scaler=replicated(opt_shape.grad_scaler),
        exp_avg=_abstract_with_shardings(
            opt_shape.exp_avg, ospecs.exp_avg, mesh),
        exp_avg_sq=(
            _abstract_with_shardings(
                opt_shape.exp_avg_sq, ospecs.exp_avg_sq, mesh)
            if opt_shape.exp_avg_sq is not None else None),
        master_params=(
            _abstract_with_shardings(
                opt_shape.master_params, ospecs.master_params, mesh)
            if opt_shape.master_params is not None else None),
    )

    seq = spec["seq"]
    dsh = NamedSharding(mesh, P(None, "dp", None))
    batch = {
        "tokens": jax.ShapeDtypeStruct((M, mb * dp, seq), jnp.int32,
                                       sharding=dsh),
        "labels": jax.ShapeDtypeStruct((M, mb * dp, seq), jnp.int32,
                                       sharding=dsh),
        "loss_mask": jax.ShapeDtypeStruct((M, mb * dp, seq), jnp.float32,
                                          sharding=dsh),
    }
    key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32)
    lr_abs = jax.ShapeDtypeStruct((), jnp.float32)
    wd_abs = jax.ShapeDtypeStruct((), jnp.float32)

    if pp > 1:
        from megatron_llm_tpu.parallel.pipeline import (
            build_pipeline_train_step,
        )

        step = build_pipeline_train_step(model, opt, pc, M)
    else:
        from megatron_llm_tpu.training import build_train_step

        step = build_train_step(model, opt, pc, M)

    print(f"[{name}] lowering: {n_params/1e9:.2f}B params, "
          f"{len(devs)} x {devs[0].device_kind}, tp={tp} pp={pp} dp={dp} "
          f"seq={seq} M={M}", file=sys.stderr, flush=True)
    lowered = step.lower(params_abs, opt_abs, batch, key_abs, lr_abs, wd_abs)
    print(f"[{name}] compiling...", file=sys.stderr, flush=True)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    arg_b = int(ma.argument_size_in_bytes)
    out_b = int(ma.output_size_in_bytes)
    tmp_b = int(ma.temp_size_in_bytes)
    alias_b = int(ma.alias_size_in_bytes)
    total = arg_b + out_b + tmp_b - alias_b
    hbm = spec["hbm_gb"] * GB

    colls = {}
    by_edge = None
    try:
        txt = compiled.as_text()
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(hlo_dir, name + ".hlo.txt"), "w") as f:
                f.write(txt)
            # the step's instruction table as the trainer registers it
            # (training.py::_ReadStep): instructions by role, mesh edge
            # and opcode, and every collective's calls and bytes a step
            # under the axes its replica groups run over
            from megatron_llm_tpu import hlo_collectives
            table = hlo_collectives.ProgramTable(
                name, hlo_collectives.instructions(txt),
                mesh_shape=dict(mesh.shape))
            print(table.table(), file=sys.stderr, flush=True)
            by_edge = table.collectives_by_edge()
        if txt and len(txt) < 400 << 20:
            for op in ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all"):
                n = txt.count(f" {op}(") + txt.count(f" {op}-start(")
                if n:
                    colls[op] = n
    except Exception as e:
        colls = {"error": str(e)[:100]}

    rec = {
        "config": name, "n_params": n_params, "devices": len(devs),
        "device_kind": devs[0].device_kind, "tp": tp, "pp": pp, "dp": dp,
        "seq": seq, "num_micro": M, "zero1": spec["zero1"],
        "hbm_gb": spec["hbm_gb"],
        "per_device_bytes": {
            "arguments": arg_b, "outputs": out_b, "temp": tmp_b,
            "aliased": alias_b, "total": total,
        },
        "per_device_gb": round(total / GB, 2),
        "fits": total <= hbm,
        "headroom_gb": round((hbm - total) / GB, 2),
        "collectives": colls,
        **({"collectives_by_edge": by_edge} if by_edge is not None else {}),
    }
    print(json.dumps(rec), flush=True)
    return rec


def main(argv):
    if "--list" in argv:
        print("\n".join(CONFIGS))
        return 0
    hlo_dir = next((os.path.abspath(a.split("=", 1)[1]) for a in argv
                    if a.startswith("--hlo_dir=")), "")
    argv = [a for a in argv if not a.startswith("--hlo_dir=")]
    if argv and argv[0] == "--child":
        return 0 if run_config(argv[1], hlo_dir).get("fits") else 1

    names = [a for a in argv if not a.startswith("-")] or list(CONFIGS)
    env = dict(os.environ)
    env.pop("JAX_PLATFORM_NAME", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    # AOT children lower for a TPU topology with a CPU default backend;
    # without this the pallas kernels silently compile as XLA fallbacks
    # (discovered round 5 — rows recorded before then were XLA-attention
    # compiles)
    env["MLT_FORCE_PALLAS"] = "1"
    rc = 0
    for name in names:
        e = dict(env)
        e["TPU_ACCELERATOR_TYPE"] = CONFIGS[name]["accel"]
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", name]
            + ([f"--hlo_dir={hlo_dir}"] if hlo_dir else []),
            env=e, cwd=REPO)
        rc |= r.returncode
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
