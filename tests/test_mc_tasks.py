"""Multiple-choice zero-shot tasks (PIQA/HellaSwag/ARC/BoolQ/Winogrande
— beyond-reference): parser formats, loglikelihood-ranking math with a
rigged scorer, and the tasks/main.py route end to end."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tasks.zeroshot_gpt.mc_tasks import (
    LENGTH_NORMALIZED,
    load_mc_samples,
    score_choices,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORDS = ["the", "cat", "sat", "good", "bad", "yes", "no", "big", "dog"]


class _Tok:
    pad = 0

    def tokenize(self, text):
        return [5 + WORDS.index(w) for w in text.lower().split()
                if w in WORDS]


def test_parsers(tmp_path):
    cases = {
        "PIQA": ({"goal": "g", "sol1": "a", "sol2": "b", "label": 1}, 2, 1),
        "HELLASWAG": ({"ctx": "c", "endings": ["x", "y", "z", "w"],
                       "label": 2}, 4, 2),
        "ARC-EASY": ({"question": "q",
                      "choices": {"text": ["a", "b", "c"],
                                  "label": ["A", "B", "C"]},
                      "answerKey": "B"}, 3, 1),
        "BOOLQ": ({"passage": "p", "question": "q", "answer": True}, 2, 1),
        "WINOGRANDE": ({"sentence": "the _ sat", "option1": "cat",
                        "option2": "dog", "answer": "2"}, 2, 1),
    }
    partial = {
        "WINOGRANDE": {"sentence": "the _ sat", "option1": "cat",
                       "option2": "dog", "answer": "2"},
    }
    for task, (rec, n_choices, gold) in cases.items():
        p = tmp_path / f"{task}.jsonl"
        p.write_text(json.dumps(rec) + "\n")
        (s,) = load_mc_samples(task, str(p))
        assert len(s["choices"]) == n_choices, task
        assert s["gold"] == gold, task
    # winogrande partial evaluation: per-choice contexts carry the
    # substituted option; the scored continuation is the shared suffix
    p = tmp_path / "wg.jsonl"
    p.write_text(json.dumps(partial["WINOGRANDE"]) + "\n")
    (s,) = load_mc_samples("WINOGRANDE", str(p))
    assert s["contexts"] == ["the cat", "the dog"]
    assert s["choices"] == [" sat", " sat"]
    assert "HELLASWAG" in LENGTH_NORMALIZED


class _RiggedModel:
    """Assigns high prob to one 'good' token id; everything else uniform
    low — makes the loglikelihood argmax analytically known."""

    class cfg:
        num_experts = 0

    def __init__(self, vocab=32, good_id=8):
        self.vocab, self.good_id = vocab, good_id

    def __call__(self, params, tokens, **kw):
        import jax.numpy as jnp

        b, s = tokens.shape
        logits = jnp.zeros((b, s, self.vocab))
        return logits.at[:, :, self.good_id].set(5.0)


def test_score_choices_picks_higher_likelihood():
    """The choice made of the rigged 'good' token must win."""
    model = _RiggedModel(good_id=5 + WORDS.index("good"))
    samples = [
        {"context": "the cat", "choices": [" good good", " bad bad"],
         "gold": 0},
        {"context": "the dog", "choices": [" bad", " good"], "gold": 1},
    ]
    acc, scores = score_choices(model, None, _Tok(), samples, seq_len=8,
                                batch_size=4)
    assert acc == 1.0
    assert scores[0, 0] > scores[0, 1] and scores[1, 1] > scores[1, 0]


def test_length_normalization_changes_ranking():
    """Unnormalized scoring penalizes long continuations; acc_norm does
    not: a 3x-long all-'good' continuation beats a short one only under
    normalization... and ties per-token otherwise."""
    model = _RiggedModel(good_id=5 + WORDS.index("good"))
    samples = [{"context": "the cat",
                "choices": [" good good good", " bad"], "gold": 0}]
    acc_raw, s_raw = score_choices(model, None, _Tok(), samples, seq_len=8,
                                   batch_size=2, length_normalize=False)
    acc_norm, s_norm = score_choices(model, None, _Tok(), samples,
                                     seq_len=8, batch_size=2,
                                     length_normalize=True)
    # raw: 3 good tokens still sum higher than 1 bad token here, but the
    # normalized margin per token must be >= the raw margin / 3
    assert acc_raw == 1.0 and acc_norm == 1.0
    assert s_norm[0, 0] == pytest.approx(s_raw[0, 0] / 3, rel=1e-5)


def test_mc_task_via_tasks_main(tmp_path):
    vocab = tmp_path / "vocab.txt"
    vocab.write_text("\n".join(
        ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + WORDS) + "\n")
    data = tmp_path / "piqa.jsonl"
    recs = [{"goal": "the cat", "sol1": "good", "sol2": "bad", "label": 0},
            {"goal": "the dog", "sol1": "bad", "sol2": "good", "label": 1}]
    data.write_text("\n".join(json.dumps(r) for r in recs) + "\n")

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tasks", "main.py"),
         "--task", "PIQA", "--valid_data", str(data),
         "--tokenizer_type", "BertWordPieceLowerCase",
         "--vocab_file", str(vocab),
         "--num_layers", "2", "--hidden_size", "32",
         "--num_attention_heads", "4", "--ffn_hidden_size", "64",
         "--seq_length", "16", "--max_position_embeddings", "16",
         "--micro_batch_size", "2"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "PIQA: acc =" in proc.stdout, proc.stdout[-1000:]
