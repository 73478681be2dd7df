"""finetune.py --lora_rank end to end: adapters train over a frozen
base, and the saved checkpoint is a standard MERGED one that a plain
(non-LoRA) run can load."""

import os
import re
import subprocess
import sys

import jax
import numpy as np

from megatron_llm_tpu import checkpointing
from megatron_llm_tpu.lora import DEFAULT_TARGETS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    return env


def _run(extra):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "finetune.py"),
         "--model_name=llama2", "--num_layers=2", "--hidden_size=64",
         "--num_attention_heads=4", "--seq_length=32",
         "--max_position_embeddings=32", "--micro_batch_size=2",
         "--global_batch_size=16", "--lr=1e-2", "--vocab_size=128",
         "--log_interval=1", "--lr_decay_style=constant"] + extra,
        cwd=ROOT, env=_env(), capture_output=True, text=True,
        timeout=1200)


def _leaves(ckpt_dir):
    params = checkpointing.load_checkpoint(ckpt_dir)[0]
    return {jax.tree_util.keystr(path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(params)[0]}


def test_lora_cli_train_and_merged_checkpoint(tmp_path):
    base, ck = str(tmp_path / "base"), str(tmp_path / "ck")
    r0 = _run(["--train_iters=1", f"--save={base}", "--save_interval=1",
               "--seed=3"])
    assert r0.returncode == 0, r0.stderr[-3000:]

    r = _run(["--train_iters=8", "--lora_rank=2", "--lora_alpha=8",
              f"--load={base}", "--finetune", f"--save={ck}",
              "--save_interval=8", "--seed=3"])
    assert r.returncode == 0, r.stderr[-3000:]
    assert "LoRA rank 2" in r.stdout
    losses = [float(m) for m in re.findall(r"lm loss: ([0-9.E+-]+)",
                                           r.stdout)]
    # the CLI's synthetic tokens are drawn anew every step and their
    # labels are uniform, so the loss sits at ln(vocab) and has no trend
    # to assert; the trend over a fixed batch is test_lora.py's
    # test_train_step_updates_only_adapters
    assert len(losses) == 8 and np.all(np.isfinite(losses)), losses

    # what LoRA guarantees, read off the two checkpoints: outside the
    # targeted kernels the base is bit for bit what it was, and each
    # targeted kernel moved by scale * A @ B: not zero (B left its zero
    # init) and of rank 2 at most
    before = _leaves(base)
    after = _leaves(ck)
    assert before.keys() == after.keys()
    targeted = [k for k in before if k.endswith("['kernel']")
                and any(f"['{t}']" in k for t in DEFAULT_TARGETS)]
    assert len(targeted) == 2, targeted   # one stacked leaf per target
    for k in before:
        if k not in targeted:
            np.testing.assert_array_equal(after[k], before[k], err_msg=k)
            continue
        delta = (after[k] - before[k]).astype(np.float64)
        for layer in delta.reshape((-1,) + delta.shape[-2:]):
            sv = np.linalg.svd(layer, compute_uv=False)
            assert sv[0] > 0, k
            assert sv[2] < 1e-4 * sv[0], (k, sv[:4])

    # the exported checkpoint is MERGED: a plain non-LoRA run loads it
    r2 = _run(["--train_iters=2", f"--load={ck}", "--finetune",
               "--seed=4"])
    assert r2.returncode == 0, r2.stderr[-3000:]
    assert "loaded checkpoint" in r2.stdout
