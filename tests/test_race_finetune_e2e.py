"""Mini RACE finetune end to end: tasks/main.py --task RACE on a tiny
separable 4-way multiple-choice corpus through the real
train_step/optimizer/scheduler path, with per-split reporting and
prediction dumps (same contract as the MNLI e2e test)."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORDS = ["good", "bad", "where", "what", "city", "food", "blue", "red",
         "big", "small", "answer", "choose"]


def _write_vocab(path):
    toks = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + WORDS
    path.write_text("\n".join(toks) + "\n")


def _write_race_dir(d, n_articles, seed):
    """Separable toy RACE: the correct option always contains the word
    'good'; distractors contain 'bad'."""
    import numpy as np

    rng = np.random.RandomState(seed)
    d.mkdir(parents=True, exist_ok=True)
    recs = []
    for i in range(n_articles):
        correct = int(rng.randint(4))
        opts = []
        for c in range(4):
            filler = " ".join(rng.choice(WORDS[4:10], 2))
            opts.append(("good " if c == correct else "bad ") + filler)
        recs.append({
            "article": "the city food " + " ".join(rng.choice(WORDS[4:], 4)),
            "questions": ["what to choose _"],
            "options": [opts],
            "answers": [chr(ord("A") + correct)],
        })
    (d / "part.txt").write_text(
        "\n".join(json.dumps(r) for r in recs) + "\n")


@pytest.fixture(scope="module")
def race_run(tmp_path_factory):
    tmp_path = tmp_path_factory.mktemp("race")
    vocab = tmp_path / "vocab.txt"
    _write_vocab(vocab)
    train = tmp_path / "train"
    _write_race_dir(train, 48, seed=0)
    dev = tmp_path / "dev"
    _write_race_dir(dev, 16, seed=1)
    save = tmp_path / "out"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tasks", "main.py"),
         "--task", "RACE",
         "--train_data", str(train),
         "--valid_data", str(dev),
         "--tokenizer_type", "BertWordPieceLowerCase",
         "--vocab_file", str(vocab),
         "--num_layers", "2", "--hidden_size", "32",
         "--num_attention_heads", "4", "--ffn_hidden_size", "64",
         "--seq_length", "32", "--max_position_embeddings", "32",
         "--micro_batch_size", "8", "--lr", "5e-3",
         "--epochs", "6", "--log_interval", "10",
         "--save", str(save), "--save_interval", "1000",
         "--seed", "42"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)
    return proc, save


def test_race_finetune_beats_chance(race_run):
    proc, _ = race_run
    assert proc.returncode == 0, proc.stderr[-3000:]
    accs = [float(m) for m in re.findall(
        r"validation accuracy ([0-9.]+)%", proc.stdout)]
    assert accs, proc.stdout[-2000:]
    # 4-way chance is 25%; 'good'-marked answers are fully separable
    assert max(accs) > 50.0, f"accuracies {accs}"


def test_race_predictions_dumped(race_run):
    proc, save = race_run
    dumps = [p for p in os.listdir(save) if p.startswith("predictions_")]
    assert dumps, os.listdir(save)
    with open(os.path.join(save, sorted(dumps)[-1])) as f:
        preds = json.load(f)
    (split,) = preds
    assert len(preds[split]["softmaxes"][0]) == 4  # 4-way distribution
