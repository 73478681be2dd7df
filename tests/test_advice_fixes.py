"""Regression tests for the round-1 advisor findings (ADVICE.md r1):

1. tasks finetune built the optimizer *before* the fp16/bf16 cast, so
   half-precision params silently lost fp32 master weights.
2. ORQA answer lists were parsed with ``eval`` (arbitrary code execution
   from a data file).
3. ``data/helpers.py`` rebuilt libhelpers.so in place with no lock —
   a concurrent loader could dlopen a half-written file.
4. WordPiece bos/eos aliased CLS/SEP/eod instead of the reference's
   dedicated [BOS]/[EOS] tokens.
5. LambadaDataset produced ragged rows for passages longer than seq_len.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# 1. finetune optimizer must be constructed from the post-cast param dtype
# ---------------------------------------------------------------------------

def test_finetune_optimizer_sees_post_cast_dtype(monkeypatch):
    import jax
    import jax.numpy as jnp

    import tasks.finetune_utils as fu
    from megatron_llm_tpu.arguments import parse_args, validate_args
    from megatron_llm_tpu.models.bert import bert_config
    from megatron_llm_tpu.models.classification import ClassificationModel
    from megatron_llm_tpu.optimizer import MegatronOptimizer

    captured = {}

    class SpyOptimizer(MegatronOptimizer):
        def __init__(self, tc, params_dtype=jnp.float32, **kw):
            captured["params_dtype"] = params_dtype
            super().__init__(tc, params_dtype=params_dtype, **kw)

    monkeypatch.setattr(fu, "MegatronOptimizer", SpyOptimizer)

    from megatron_llm_tpu import topology
    topology.initialize_model_parallel(1, 1)
    args = parse_args(args_list=[
        "--bf16", "--micro_batch_size=1",
        "--global_batch_size=8", "--lr=1e-4", "--seq_length=8",
        "--max_position_embeddings=8",
    ])
    validate_args(args)
    args.epochs = 0  # task-harness flag (tasks/main.py); none needed here
    cfg = bert_config(num_layers=1, hidden_size=32, num_attention_heads=4,
                      ffn_hidden_size=64, padded_vocab_size=64,
                      seq_length=8, max_position_embeddings=8)
    model = ClassificationModel(cfg, num_classes=2)
    fu.finetune(args, model, train_dataset=[], valid_dataset=None)

    # the regression: optimizer used to be built before the cast with the
    # default fp32 params_dtype, so no fp32 masters were kept for bf16 runs
    assert captured["params_dtype"] == jnp.bfloat16


def test_low_precision_optimizer_keeps_fp32_masters():
    import jax
    import jax.numpy as jnp

    from megatron_llm_tpu.config import TrainConfig
    from megatron_llm_tpu.optimizer import MegatronOptimizer

    tc = TrainConfig(micro_batch_size=1, global_batch_size=1, train_iters=0,
                     lr=1e-4, optimizer="adam", bf16=True)
    opt = MegatronOptimizer(tc, params_dtype=jnp.bfloat16)
    params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
    state = opt.init(params)
    masters = [l for l in jax.tree_util.tree_leaves(state)
               if hasattr(l, "dtype") and l.dtype == jnp.float32]
    assert masters, "bf16 params must produce fp32 optimizer state"


# ---------------------------------------------------------------------------
# 2. ORQA answers: literal_eval only, no code execution
# ---------------------------------------------------------------------------

def test_orqa_load_qa_pairs_no_eval(tmp_path):
    from tasks.orqa.evaluate_orqa import load_qa_pairs

    canary = tmp_path / "pwned"
    p = tmp_path / "qa.tsv"
    with open(p, "w") as f:
        f.write("who?\t['Paris', 'paris']\n")
        # a hostile "answer" that eval would have executed
        f.write(f"evil?\topen({str(canary)!r}, 'w').close()\n")
        f.write("plain?\tjust a plain string\n")
    pairs = load_qa_pairs(str(p))
    assert pairs[0] == ("who?", ["Paris", "paris"])
    assert pairs[1][1] == ["open(" + repr(str(canary)) + ", 'w').close()"]
    assert pairs[2][1] == ["just a plain string"]
    assert not canary.exists(), "data file expression must never execute"


# ---------------------------------------------------------------------------
# 3. libhelpers.so: concurrent builds never expose a half-written file
# ---------------------------------------------------------------------------

def test_helpers_concurrent_build():
    from megatron_llm_tpu.data import helpers

    so = helpers._so_path()
    if os.path.exists(so):
        os.unlink(so)
    code = ("from megatron_llm_tpu.data import helpers; "
            "import sys; sys.exit(0 if helpers._load() is not None else 1)")
    procs = [subprocess.Popen([sys.executable, "-c", code], cwd=REPO)
             for _ in range(3)]
    rcs = [p.wait(timeout=300) for p in procs]
    assert rcs == [0, 0, 0]
    assert os.path.exists(so)
    leftovers = [f for f in os.listdir(os.path.dirname(so))
                 if ".so.tmp." in f]
    assert leftovers == []


# ---------------------------------------------------------------------------
# 4. WordPiece [BOS]/[EOS] are dedicated tokens, not CLS/SEP aliases
# ---------------------------------------------------------------------------

def _write_vocab(path):
    toks = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]",
            "the", "cat", "sat", "##s", "a", "b", "c"]
    with open(path, "w") as f:
        f.write("\n".join(toks) + "\n")


def test_wordpiece_bos_eos_dedicated(tmp_path):
    from megatron_llm_tpu.tokenizer.tokenizer import _BertWordPieceTokenizer

    vf = tmp_path / "vocab.txt"
    _write_vocab(vf)
    tok = _BertWordPieceTokenizer(str(vf))
    assert tok.bos_token_id is not None and tok.eos_token_id is not None
    # the reference adds [BOS]/[EOS] as their own ids (tokenizer.py:156-200);
    # they must not collide with cls/sep/eod
    assert tok.bos_token_id != tok.cls
    assert tok.eos_token_id != tok.sep
    assert tok.eos_token_id != tok.eod
    assert tok.bos_token_id != tok.eos_token_id
    assert tok.vocab_size > 12  # grew by the added special tokens


# ---------------------------------------------------------------------------
# 5. LAMBADA: over-long passages are left-truncated, never ragged
# ---------------------------------------------------------------------------

class IntTok:
    cls, sep, pad, mask, eod = 1, 2, 0, 3, 2

    def tokenize(self, text):
        return [int(t) % 400 + 5 for t in text.split()]


def test_lambada_long_passage_truncated(tmp_path):
    from tasks.zeroshot_gpt.datasets import LambadaDataset

    seq_len = 16
    long_text = " ".join(str(i) for i in range(50))   # 50 tokens > 17
    short_text = "10 11 12 13 14"
    p = tmp_path / "l.jsonl"
    with open(p, "w") as f:
        f.write(json.dumps({"text": long_text}) + "\n")
        f.write(json.dumps({"text": short_text}) + "\n")
    ds = LambadaDataset(str(p), pad_idx=0, tokenizer=IntTok(),
                        seq_len=seq_len)
    rows = [ds[i] for i in range(len(ds))]
    for s in rows:
        assert s["text"].shape == (seq_len + 1,)
        assert s["pad_mask"].shape == (seq_len,)
        assert s["pad_mask"].sum() == 1
    # the long row keeps the *suffix* of the prefix plus the label token
    toks = IntTok().tokenize(long_text)
    assert rows[0]["text"][-1] == toks[-1]
    assert rows[0]["text"][0] == toks[len(toks) - (seq_len + 1)]
    # batch assembly must not be ragged
    np.stack([s["text"] for s in rows])
