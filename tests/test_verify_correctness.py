"""End-to-end run of the verify_correctness harness (reference:
verify_correctness.py + tests/test_llama_weights.py): HF golden model ->
converted release checkpoint -> CLI comparison passes within tolerance."""

import os
import subprocess
import sys

import numpy as np
import pytest

torch = pytest.importorskip("torch")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_verify_correctness_cli(tmp_path):
    from transformers import LlamaConfig, LlamaForCausalLM

    from megatron_llm_tpu import checkpointing
    from weights_conversion.hf_to_megatron import convert_llama_family

    torch.manual_seed(0)
    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=176,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    hf = LlamaForCausalLM(hf_cfg).eval()
    hf_dir = tmp_path / "hf"
    hf.save_pretrained(str(hf_dir))

    params, config = convert_llama_family(hf)
    config["model_name"] = "llama2"
    ck_dir = tmp_path / "ck"
    checkpointing.save_checkpoint(str(ck_dir), 0, params, args=config,
                                  release=True)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "verify_correctness.py"),
         "--model_name=llama2", f"--load={ck_dir}",
         f"--huggingface_path={hf_dir}", "--iters=2", "--batch=1",
         "--seq_length=16"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert " OK" in proc.stdout
    # the harness actually measured something
    assert "mean max-abs logits error" in proc.stdout
