"""ORQA/REALM evidence pipeline (VERDICT r3 #5): DPR wiki TSV ->
OpenRetrievalEvidenceDataset -> EvidenceIndexBuilder embedding run ->
RETRIEVER-EVAL recall@k, end to end through tasks/main.py.

Reference behavior: megatron/data/orqa_wiki_dataset.py:1-193 +
megatron/data/biencoder_dataset_utils.py:1-209 + tasks RETRIEVER-EVAL.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORDS = ["paris", "capital", "france", "rome", "italy", "berlin",
         "germany", "cat", "dog", "moon", "cheese", "king"]


def _write_vocab(path):
    toks = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + WORDS
    path.write_text("\n".join(toks) + "\n")


def _write_evidence(path):
    rows = [
        (1, "paris is the capital of france", "france"),
        (2, "rome is the capital of italy", "italy"),
        (3, "berlin is the capital of germany", "germany"),
        (4, "the cat chased the dog", "animals"),
        (5, "the moon is not made of cheese", "moon"),
        (6, "the king lives in the capital", "royalty"),
    ]
    with open(path, "w") as f:
        f.write("id\ttext\ttitle\n")
        for doc_id, text, title in rows:
            f.write(f"{doc_id}\t{text}\t{title}\n")
    return rows


class _Tok:
    """Whitespace tokenizer over the fixture vocab (cls=2, sep=3, pad=0)."""
    cls, sep, pad, mask = 2, 3, 0, 4

    def tokenize(self, text):
        base = 5
        return [base + WORDS.index(w) for w in text.lower().split()
                if w in WORDS]

    def detokenize(self, ids):
        return " ".join(WORDS[i - 5] for i in ids if 5 <= i < 5 + len(WORDS))


def test_evidence_dataset_rows(tmp_path):
    from megatron_llm_tpu.data.orqa_wiki_dataset import (
        OpenRetrievalEvidenceDataset,
        evidence_batches,
    )

    tsv = tmp_path / "wiki.tsv"
    rows = _write_evidence(tsv)
    ds = OpenRetrievalEvidenceDataset(str(tsv), _Tok(), max_seq_length=12)
    assert len(ds) == len(rows)
    assert ds.id2text[1] == ("paris is the capital of france", "france")

    s = ds[0]
    assert s["row_id"] == 1
    # [CLS] title [SEP] text... [SEP] then pad
    assert s["context"][0] == _Tok.cls
    assert _Tok.sep in s["context"].tolist()
    assert s["context"].shape == (12,)
    n_real = int(s["context_pad_mask"].sum())
    assert (s["context"][n_real:] == _Tok.pad).all()

    batches = list(evidence_batches(ds, batch_size=4))
    assert [b["context"].shape[0] for b in batches] == [4, 2]
    assert batches[0]["row_id"].tolist() == [1, 2, 3, 4]


def test_trim_overlong_context():
    from megatron_llm_tpu.data.orqa_wiki_dataset import (
        build_tokens_types_paddings_from_ids,
    )

    ids, types, mask = build_tokens_types_paddings_from_ids(
        list(range(5, 25)), 8, cls_id=2, sep_id=3, pad_id=0)
    assert len(ids) == 8 and ids[0] == 2 and ids[-1] == 3
    assert mask.sum() == 8


def test_evidence_index_builder_roundtrip(tmp_path):
    import jax

    from megatron_llm_tpu.data.orqa_wiki_dataset import (
        OpenRetrievalEvidenceDataset,
    )
    from megatron_llm_tpu.data.realm_index import (
        BruteForceMIPSIndex,
        OpenRetrievalDataStore,
    )
    from megatron_llm_tpu.indexer import EvidenceIndexBuilder
    from megatron_llm_tpu.models.bert import bert_config
    from megatron_llm_tpu.models.biencoder import BiEncoderModel

    tsv = tmp_path / "wiki.tsv"
    _write_evidence(tsv)
    ds = OpenRetrievalEvidenceDataset(str(tsv), _Tok(), max_seq_length=12)

    cfg = bert_config(num_layers=1, hidden_size=32, num_attention_heads=4,
                      ffn_hidden_size=64, padded_vocab_size=32,
                      seq_length=12, max_position_embeddings=12)
    model = BiEncoderModel(cfg, projection_dim=8)
    params = model.init(jax.random.PRNGKey(0))

    emb_path = str(tmp_path / "emb.pkl")
    EvidenceIndexBuilder(model, params, ds, emb_path,
                         batch_size=4).build_and_save_index()

    store = OpenRetrievalDataStore(emb_path)
    assert set(store.embed_data) == {1, 2, 3, 4, 5, 6}
    # the stored embedding must be exactly the context-tower output for
    # the same row (the builder embedded what the dataset produced)
    want = np.asarray(model.embed_context(
        params,
        np.stack([ds[0]["context"]]).astype(np.int32),
        np.stack([ds[0]["context_pad_mask"]]).astype(np.int32)))[0]
    # the store quantizes to fp16 (realm_index.add_block_data, matching
    # the reference's hashed-index memory format)
    np.testing.assert_allclose(
        np.asarray(store.embed_data[1], np.float32), want, atol=2e-3)
    # and MIPS over the store returns valid doc ids
    index = BruteForceMIPSIndex(8, store)
    _, top = index.search_mips_index(want[None], top_k=6)
    assert set(int(i) for i in top[0]) == {1, 2, 3, 4, 5, 6}


def test_retriever_eval_end_to_end_via_tasks_main(tmp_path):
    """tasks/main.py --task RETRIEVER-EVAL on a tiny wiki TSV: builds the
    evidence embedding store, retrieves, and reports NONZERO recall@k
    (answers present in the corpus; k = corpus size makes recall@k = 1
    even for a random retriever — the assertion is the pipeline, not the
    model quality)."""
    tsv = tmp_path / "wiki.tsv"
    _write_evidence(tsv)
    vocab = tmp_path / "vocab.txt"
    _write_vocab(vocab)
    qa = tmp_path / "qa.jsonl"
    qa.write_text(
        json.dumps({"question": "capital of france", "answers": ["paris"]})
        + "\n"
        + json.dumps({"question": "capital of italy", "answers": ["rome"]})
        + "\n")
    emb = tmp_path / "emb.pkl"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tasks", "main.py"),
         "--task", "RETRIEVER-EVAL",
         "--evidence_data_path", str(tsv),
         "--embedding_path", str(emb),
         "--qa_data_dev", str(qa),
         "--tokenizer_type", "BertWordPieceLowerCase",
         "--vocab_file", str(vocab),
         "--num_layers", "1", "--hidden_size", "32",
         "--num_attention_heads", "4", "--ffn_hidden_size", "64",
         "--seq_length", "16", "--max_position_embeddings", "16",
         "--micro_batch_size", "1",
         "--biencoder_projection_dim", "8",
         "--retriever_report_topk_accuracies", "1", "6"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert os.path.exists(emb), "embedding store was not built"
    out = proc.stdout
    assert "recall@6" in out, out[-2000:]
    import re

    m = re.search(r"recall@6: ([0-9.]+)%", out)
    assert m, out[-2000:]
    assert float(m.group(1)) > 0.0, "recall@6 must be nonzero"
