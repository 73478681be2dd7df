"""Mini GLUE finetune end to end (VERDICT r3 #7): tasks/main.py --task
MNLI on a tiny separable corpus must (a) run the REAL
train_step/optimizer/scheduler path, (b) improve dev accuracy over
random init, (c) report per-split accuracy for two dev files, and
(d) dump per-sample predictions + a best/ checkpoint."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORDS = ["yes", "no", "maybe", "dogs", "cats", "run", "sleep", "fast",
         "slow", "happy"]


def _write_vocab(path):
    toks = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + WORDS
    path.write_text("\n".join(toks) + "\n")


def _write_mnli_tsv(path, n, seed):
    """Separable toy MNLI: label fully determined by the first word of
    the hypothesis (yes->entailment, no->contradiction, maybe->neutral).
    11-column TSV, premise col 8, hypothesis col 9, label last."""
    import numpy as np

    rng = np.random.RandomState(seed)
    first = {"entailment": "yes", "contradiction": "no", "neutral": "maybe"}
    lines = ["\t".join(f"c{i}" for i in range(11))]
    for uid in range(n):
        label = ["contradiction", "entailment", "neutral"][uid % 3]
        filler = " ".join(rng.choice(WORDS[3:], 3))
        premise = f"dogs {filler}"
        hyp = f"{first[label]} {filler}"
        row = [str(uid)] + ["x"] * 7 + [premise, hyp, label]
        lines.append("\t".join(row))
    path.write_text("\n".join(lines) + "\n")


@pytest.fixture(scope="module")
def finetune_run(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("glue")
    vocab = tmp_path / "vocab.txt"
    _write_vocab(vocab)
    train = tmp_path / "train.tsv"
    _write_mnli_tsv(train, 96, seed=0)
    dev_m = tmp_path / "dev_matched.tsv"
    _write_mnli_tsv(dev_m, 24, seed=1)
    dev_mm = tmp_path / "dev_mismatched.tsv"
    _write_mnli_tsv(dev_mm, 24, seed=2)
    save = tmp_path / "out"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tasks", "main.py"),
         "--task", "MNLI",
         "--train_data", str(train),
         "--valid_data", str(dev_m), str(dev_mm),
         "--tokenizer_type", "BertWordPieceLowerCase",
         "--vocab_file", str(vocab),
         "--num_layers", "2", "--hidden_size", "32",
         "--num_attention_heads", "4", "--ffn_hidden_size", "64",
         "--seq_length", "16", "--max_position_embeddings", "16",
         "--micro_batch_size", "8", "--lr", "5e-3",
         "--lr_warmup_fraction", "0.1",
         "--epochs", "6", "--log_interval", "10",
         "--save", str(save), "--save_interval", "1000",
         "--seed", "42"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    return proc, save


def test_finetune_improves_dev_accuracy(finetune_run):
    proc, _ = finetune_run
    assert proc.returncode == 0, proc.stderr[-3000:]
    accs = [float(m) for m in re.findall(
        r"validation accuracy ([0-9.]+)%", proc.stdout)]
    assert accs, proc.stdout[-2000:]
    # 3-class random init ~33%; the toy task is linearly separable on
    # the first hypothesis token, so training must clearly beat chance
    assert max(accs) > 60.0, f"accuracies {accs}"


def test_per_split_accuracy_reported(finetune_run):
    proc, _ = finetune_run
    assert "metrics for dev_matched" in proc.stdout
    assert "metrics for dev_mismatched" in proc.stdout
    assert re.search(r">> \|epoch: \d+\| overall: correct / total",
                     proc.stdout)


def test_predictions_dumped_and_best_checkpoint(finetune_run):
    proc, save = finetune_run
    dumps = sorted(p for p in os.listdir(save)
                   if p.startswith("predictions_epoch"))
    assert dumps, os.listdir(save)
    with open(os.path.join(save, dumps[-1])) as f:
        preds = json.load(f)
    assert set(preds) == {"dev_matched", "dev_mismatched"}
    p = preds["dev_matched"]
    assert len(p["softmaxes"]) == 24 and len(p["labels"]) == 24
    assert len(p["softmaxes"][0]) == 3  # 3-class distribution
    assert abs(sum(p["softmaxes"][0]) - 1.0) < 1e-3
    assert len(set(p["ids"])) == 24  # uids, not positions
    # checkpoint-best exists and records an iteration
    best = os.path.join(save, "best")
    assert os.path.isdir(best), os.listdir(save)
    assert os.path.exists(
        os.path.join(best, "latest_checkpointed_iteration.txt"))
