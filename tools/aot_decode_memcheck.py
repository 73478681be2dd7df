#!/usr/bin/env python
"""AOT serving-scale proof: long-context decode memory, bf16 vs int8.

Training scale is proven by tools/aot_memcheck.py; this is the DECODE
side.  The claim under test: **int8 weights + the int8 KV cache make a
64k-token-context Llama-3-8B serveable on ONE 16-GB v5e chip, where
bf16 cannot fit** (bf16: ~16 GB weights + ~8 GB KV ≈ 24+ GB; int8:
~8 GB + ~4 GB ≈ 13 GB).  The decode-step function (one token through
the full-length cache — the loop body whose residency dominates
serving memory) is AOT-compiled against a virtual v5e through the real
libtpu compiler, and ``memory_analysis()`` reports per-chip bytes.

Usage:
  python tools/aot_decode_memcheck.py            # the 8B/64k headline rows
  python tools/aot_decode_memcheck.py tiny       # CI-sized smoke rows

Each row runs in a forced-CPU subprocess (AOT needs only the local
libtpu compiler, no chip).  One JSON line per row.
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

# llama-3-8b true shape: 32 L, h 4096, 32 q / 8 kv heads, ffn 14336,
# vocab 128256 (the 128k vocab also exercises the fused-CE-free decode
# head).  ctx = prompt + generation budget the cache must hold.
# hbm_gb is libtpu's USABLE v5e budget (its own refusal message says
# "of 15.75G hbm"), not the 16-GB nameplate — a total in (15.75, 16]
# must be a NO
ROWS = {
    "l3-8b-64k-bf16": dict(L=32, h=4096, heads=32, kv=8, ffn=14336,
                           vocab=128256, ctx=65536, wq=False, kvq=False,
                           hbm_gb=15.75),
    "l3-8b-64k-int8": dict(L=32, h=4096, heads=32, kv=8, ffn=14336,
                           vocab=128256, ctx=65536, wq=True, kvq=True,
                           hbm_gb=15.75),
    # speculative decode step: the loop body is the [b, K+1] verify
    # window (serving/engine.py with --serve_speculative), so the
    # residency claim must hold for THAT shape too — K extra query
    # positions and K extra logits rows on top of the int8 row
    "l3-8b-64k-int8-spec4": dict(L=32, h=4096, heads=32, kv=8,
                                 ffn=14336, vocab=128256, ctx=65536,
                                 wq=True, kvq=True, spec_k=4,
                                 hbm_gb=15.75),
    # CI-sized smoke (same code path, minutes -> seconds)
    "tiny-bf16": dict(L=2, h=256, heads=4, kv=2, ffn=704, vocab=512,
                      ctx=512, wq=False, kvq=False, hbm_gb=15.75),
    "tiny-int8": dict(L=2, h=256, heads=4, kv=2, ffn=704, vocab=512,
                      ctx=512, wq=True, kvq=True, hbm_gb=15.75),
    "tiny-int8-spec4": dict(L=2, h=256, heads=4, kv=2, ffn=704,
                            vocab=512, ctx=512, wq=True, kvq=True,
                            spec_k=4, hbm_gb=15.75),
}


def run_row(name: str) -> dict:
    spec = ROWS[name]
    # off-GCP the metadata server 403s and libtpu retries each variable
    # 30x with backoff before the topology init can proceed — skip it
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from megatron_llm_tpu.models.llama import LlamaModel, llama_config
    from megatron_llm_tpu.quantization import quantize_linear_weights_int8
    from megatron_llm_tpu.text_generation.generation import (
        _forward_with_cache,
        init_kv_caches,
    )

    topo = topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2")
    dev = topo.devices[0]

    cfg = llama_config(
        "tiny", num_layers=spec["L"], hidden_size=spec["h"],
        num_attention_heads=spec["heads"],
        num_attention_heads_kv=spec["kv"],
        ffn_hidden_size=spec["ffn"], padded_vocab_size=spec["vocab"],
        seq_length=spec["ctx"], max_position_embeddings=spec["ctx"],
        params_dtype="bf16", compute_dtype="bf16",
        # flash never engages in decode (kv_cache forwards use the
        # masked XLA path); keep it off so the row is decode-honest
        use_flash_attn=False, use_fused_rmsnorm=False,
        rope_theta=500000.0,
    )
    model = LlamaModel(cfg)
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if spec["wq"]:
        params_shape = jax.eval_shape(quantize_linear_weights_int8,
                                      params_shape)
    n_params = sum(int(x.size)
                   for x in jax.tree_util.tree_leaves(params_shape))

    b = 1
    caches_shape = jax.eval_shape(
        lambda: init_kv_caches(cfg, b, spec["ctx"],
                               quantized=spec["kvq"]))

    # spec_k > 0 rows prove the speculative-decoding loop body instead:
    # the engine's fixed-shape [b, K+1] verify window at the last cache
    # positions (K draft tokens + the bonus row)
    k1 = int(spec.get("spec_k", 0)) + 1

    def decode_step(params, caches, tok):
        # one decoded token (or the K+1 verify window) at the LAST
        # cache positions: the steady-state loop body (cache fully
        # resident, weights read once)
        logits, caches = _forward_with_cache(
            model, params, tok, caches, spec["ctx"] - k1)
        return jnp.argmax(logits, axis=-1), caches

    tok = jax.ShapeDtypeStruct((b, k1), jnp.int32)
    print(f"[{name}] lowering ({n_params/1e9:.2f}B params, "
          f"ctx {spec['ctx']})...", file=sys.stderr, flush=True)
    lowered = jax.jit(decode_step, device=dev,
                      donate_argnums=(1,)).lower(
        params_shape, caches_shape, tok)
    print(f"[{name}] compiling...", file=sys.stderr, flush=True)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    arg = int(ma.argument_size_in_bytes)
    out = int(ma.output_size_in_bytes)
    tmp = int(ma.temp_size_in_bytes)
    alias = int(ma.alias_size_in_bytes)
    total = (arg + out + tmp - alias) / GB
    rec = {
        "row": name, "n_params": n_params, "ctx": spec["ctx"],
        "int8_weights": spec["wq"], "int8_kv": spec["kvq"],
        "spec_k": spec.get("spec_k", 0),
        "arg_gb": round(arg / GB, 3), "temp_gb": round(tmp / GB, 3),
        "total_gb": round(total, 3), "hbm_gb": spec["hbm_gb"],
        "fits": total <= spec["hbm_gb"],
    }
    print(json.dumps(rec), flush=True)
    return rec


def main(argv):
    if argv and argv[0] == "--list":
        print("\n".join(ROWS))
        return 0
    if argv and argv[0] == "tiny":
        names = [n for n in ROWS if n.startswith("tiny")]
    elif argv:
        names = argv
    else:
        names = [n for n in ROWS if n.startswith("l3-")]
    results = []
    rc = 0
    for name in names:
        # same as aot_memcheck.py: keep/seed the libtpu init vars
        env = dict(os.environ)
        env.pop("JAX_PLATFORM_NAME", None)
        env.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
        env.update(JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""))
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", name],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=3600)
        sys.stderr.write(r.stderr)
        line = [l for l in r.stdout.splitlines() if l.startswith("{")]
        if r.returncode != 0 or not line:
            # a compiler RESOURCE_EXHAUSTED is a first-class verdict:
            # the config does NOT fit, and libtpu says by how much
            import re as _re
            m = _re.search(r"Used ([0-9.]+)G of ([0-9.]+)G hbm",
                           r.stderr or "")
            if m:
                rec = {"row": name, "ctx": ROWS[name]["ctx"],
                       "int8_weights": ROWS[name]["wq"],
                       "int8_kv": ROWS[name]["kvq"],
                       "spec_k": ROWS[name].get("spec_k", 0),
                       "total_gb": float(m.group(1)),
                       "hbm_gb": ROWS[name]["hbm_gb"], "fits": False,
                       "compiler_verdict": "RESOURCE_EXHAUSTED",
                       "n_params": None}
                results.append(rec)
                print(json.dumps(rec), flush=True)
            else:
                print(json.dumps({"row": name, "error":
                                  (r.stderr or "no output")[-300:]}))
                rc = 1
            continue
        results.append(json.loads(line[-1]))
        print(line[-1], flush=True)
    if results:
        print(f"\n{'row':22s} {'params':>8s} {'ctx':>7s} "
              f"{'total GB':>9s} fits")
        for r in results:
            npb = (f"{r['n_params']/1e9:7.2f}B" if r["n_params"]
                   else "      —")
            verdict = "YES" if r["fits"] else \
                "NO (compiler: RESOURCE_EXHAUSTED)"
            print(f"{r['row']:22s} {npb} "
                  f"{r['ctx']:7d} {r['total_gb']:9.2f} {verdict}")
    return rc


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        run_row(sys.argv[2])
        sys.exit(0)
    sys.exit(main(sys.argv[1:]))
