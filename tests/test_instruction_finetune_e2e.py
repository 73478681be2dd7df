"""Instruction-tuning CLI end to end (VERDICT r4 #3): tiny chat jsonl
-> ``tools/preprocess_instruct_data.py`` -> ``finetune.py
--data_type=instruction`` must (a) run the real train loop with the
assistant-masked loss falling, and (b) produce text/role datasets whose
collated loss mask is 1.0 exactly on assistant tokens, the
``--scalar_loss_mask`` value on system/user tokens, and 0 on padding —
the reference's marquee workflow (finetune.py:155-166 +
instruction_dataset.py:321-355), proven here at the CLI level the way
``test_glue_finetune_e2e.py`` proves GLUE."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORDS = ["yes", "no", "maybe", "dogs", "cats", "run", "sleep", "fast",
         "slow", "happy", "you", "are", "helpful", "what", "do", "like",
         "tell", "me", "about", "animals"]

ANSWER = "dogs run fast yes"


def _cpu_env(n_devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    return env


@pytest.fixture(scope="module")
def instruct_run(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("instr")
    vocab = tmp_path / "vocab.txt"
    vocab.write_text("\n".join(
        ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + WORDS) + "\n")

    chat = tmp_path / "chat.jsonl"
    with open(chat, "w") as f:
        for i in range(32):
            f.write(json.dumps({"conversations": [
                {"role": "system", "content": "you are helpful"},
                {"role": "user",
                 "content": f"tell me about {WORDS[5 + i % 10]} animals"},
                {"role": "assistant", "content": ANSWER},
            ]}) + "\n")

    prefix = str(tmp_path / "instr")
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "preprocess_instruct_data.py"),
         "--input", str(chat), "--output_prefix", prefix,
         "--tokenizer_type", "BertWordPieceLowerCase",
         "--vocab_file", str(vocab), "--append_eod"],
        env=_cpu_env(), cwd=REPO, capture_output=True, text=True,
        timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "finetune.py"),
         "--model_name=llama2",
         "--num_layers=2", "--hidden_size=64", "--num_attention_heads=4",
         "--ffn_hidden_size=128", "--seq_length=32",
         "--max_position_embeddings=32",
         "--micro_batch_size=4", "--global_batch_size=4",
         "--train_iters=25", "--lr=1e-2", "--lr_decay_style=constant",
         "--log_interval=1",
         "--data_type=instruction", "--data_path", prefix,
         "--scalar_loss_mask", "0.1",
         "--tokenizer_type", "BertWordPieceLowerCase",
         "--vocab_file", str(vocab), "--seed", "42"],
        env=_cpu_env(), cwd=REPO, capture_output=True, text=True,
        timeout=900)
    return proc, prefix, vocab


def test_assistant_masked_loss_falls(instruct_run):
    proc, _, _ = instruct_run
    assert proc.returncode == 0, proc.stderr[-3000:]
    losses = [float(m) for m in re.findall(
        r"lm loss: ([0-9.E+-]+)", proc.stdout)]
    assert len(losses) == 25, proc.stdout[-2000:]
    # The assistant reply is constant: the masked LM objective must
    # collapse far below the initial ~log(vocab) loss.
    assert losses[-1] < 0.5 * losses[0], f"first {losses[0]}, last {losses[-1]}"
    assert losses[-1] < 1.0, f"final loss {losses[-1]}"


def test_loss_mask_role_semantics(instruct_run):
    """The CLI-built -text/-role datasets collate into the documented
    loss mask: 1.0 on assistant label positions, --scalar_loss_mask on
    system/user, 0.0 on pad."""
    _, prefix, vocab = instruct_run
    from megatron_llm_tpu.data.instruction_dataset import (
        ROLES,
        InstructionDataset,
        build_instruction_collator,
    )

    ds = InstructionDataset(prefix, shuffle=False)
    assert len(ds) == 32
    sample = ds[0]
    assert len(sample["text"]) == len(sample["role"])
    # the conversation layout survives the round trip: a system span,
    # then user, then assistant (plus the appended eod as assistant)
    roles = sample["role"]
    assert roles[0] == ROLES["system"]
    assert roles[-1] == ROLES["assistant"]
    assert set(np.unique(roles)) == {ROLES["system"], ROLES["user"],
                                     ROLES["assistant"]}

    seq = 32
    collate = build_instruction_collator(seq, pad_token_id=0,
                                         scalar_loss_mask=0.1)
    batch = collate([[ds[0], ds[1]]])
    mask = batch["loss_mask"][0]      # [batch, seq]
    label_roles = np.full_like(batch["labels"][0], ROLES["pad"])
    for r in range(2):
        t = ds[r]["role"][:seq + 1]
        label_roles[r, : len(t) - 1] = t[1:]
    np.testing.assert_array_equal(mask == 1.0,
                                  label_roles == ROLES["assistant"])
    np.testing.assert_array_equal(mask == 0.0, label_roles == ROLES["pad"])
    scalar = (label_roles == ROLES["system"]) | (label_roles == ROLES["user"])
    np.testing.assert_allclose(mask[scalar], 0.1)
    assert scalar.any() and (mask == 1.0).any() and (mask == 0.0).any()
