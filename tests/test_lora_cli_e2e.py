"""finetune.py --lora_rank end to end: adapters train over a frozen
base, and the saved checkpoint is a standard MERGED one that a plain
(non-LoRA) run can load."""

import os
import re
import subprocess
import sys

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


def test_lora_cli_train_and_merged_checkpoint(tmp_path):
    ck = str(tmp_path / "ck")
    r = _run(["--train_iters=8", "--lora_rank=2", "--lora_alpha=8",
              f"--save={ck}", "--save_interval=8", "--seed=3"])
    assert r.returncode == 0, r.stderr[-3000:]
    assert "LoRA rank 2" in r.stdout
    losses = [float(m) for m in re.findall(r"lm loss: ([0-9.E+-]+)",
                                           r.stdout)]
    assert len(losses) >= 8, losses
    # 8 iters of a rank-2 adapter moves the loss by ~1e-2 — comparable to
    # per-step noise, so last-vs-first flakes.  Compare window means: the
    # trend survives the noise.
    assert (sum(losses[-4:]) / 4) < (sum(losses[:4]) / 4), losses

    # the exported checkpoint is MERGED: a plain non-LoRA run loads it
    r2 = _run(["--train_iters=2", f"--load={ck}", "--finetune",
               "--seed=4"])
    assert r2.returncode == 0, r2.stderr[-3000:]
    assert "loaded checkpoint" in r2.stdout
