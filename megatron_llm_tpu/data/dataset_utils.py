"""Shared machinery for the masked-LM dataset family (BERT / T5 / ICT).

Capability parity with the reference's ``megatron/data/dataset_utils.py``:
segment pairing (:95-171), n-gram masked-LM prediction building (:187-386),
sample-mapping construction + on-disk cache (:643-729), and the
train/valid/test dispatcher (:421-592).  Fresh TPU-side implementation: no
torch, plain numpy; the mapping itself comes from the native C helper
(``helpers.build_mapping``) with a numpy fallback.
"""

from __future__ import annotations

import collections
import os
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from megatron_llm_tpu.data import helpers
from megatron_llm_tpu.data.blendable_dataset import BlendableDataset
from megatron_llm_tpu.data.gpt_dataset import get_train_valid_test_split_
from megatron_llm_tpu.data.indexed_dataset import make_dataset

DSET_TYPE_BERT = "standard_bert"
DSET_TYPE_ICT = "ict"
DSET_TYPE_T5 = "t5"

MaskedLmInstance = collections.namedtuple("MaskedLmInstance",
                                          ["index", "label"])


# --------------------------------------------------------------------------
# segments
# --------------------------------------------------------------------------

def get_a_and_b_segments(sample: Sequence[np.ndarray], np_rng):
    """Split a multi-sentence sample into segments A and B; with p=0.5 swap
    them and mark ``is_next_random`` (reference: dataset_utils.py:95-124)."""
    n = len(sample)
    assert n > 1, "need at least two sentences for a segment pair"
    a_end = 1 if n < 3 else int(np_rng.randint(1, n))
    tokens_a: List[int] = []
    for j in range(a_end):
        tokens_a.extend(sample[j])
    tokens_b: List[int] = []
    for j in range(a_end, n):
        tokens_b.extend(sample[j])
    is_next_random = False
    if np_rng.random() < 0.5:
        is_next_random = True
        tokens_a, tokens_b = tokens_b, tokens_a
    return tokens_a, tokens_b, is_next_random


def truncate_segments(tokens_a, tokens_b, len_a, len_b, max_num_tokens,
                      np_rng) -> bool:
    """Trim the longer segment one token at a time, randomly front or back
    (reference: dataset_utils.py:127-144).  Returns True if truncated."""
    assert len_a > 0
    if len_a + len_b <= max_num_tokens:
        return False
    while len_a + len_b > max_num_tokens:
        if len_a > len_b:
            len_a -= 1
            toks = tokens_a
        else:
            len_b -= 1
            toks = tokens_b
        if np_rng.random() < 0.5:
            del toks[0]
        else:
            toks.pop()
    return True


def create_tokens_and_tokentypes(tokens_a, tokens_b, cls_id, sep_id):
    """[CLS] A [SEP] (B [SEP]) with 0/1 token types (reference:
    dataset_utils.py:147-171)."""
    tokens = [cls_id] + list(tokens_a) + [sep_id]
    tokentypes = [0] * (len(tokens_a) + 2)
    if tokens_b:
        tokens += list(tokens_b) + [sep_id]
        tokentypes += [1] * (len(tokens_b) + 1)
    return tokens, tokentypes


# --------------------------------------------------------------------------
# masking
# --------------------------------------------------------------------------

def is_start_piece(piece: str) -> bool:
    """WordPiece continuation tokens start with '##'."""
    return not piece.startswith("##")


def create_masked_lm_predictions(tokens,
                                 vocab_id_list,
                                 vocab_id_to_token_dict,
                                 masked_lm_prob,
                                 cls_id, sep_id, mask_id,
                                 max_predictions_per_seq,
                                 np_rng,
                                 max_ngrams: int = 3,
                                 do_whole_word_mask: bool = True,
                                 favor_longer_ngram: bool = False,
                                 geometric_dist: bool = False,
                                 masking_style: str = "bert"):
    """N-gram span masking over whole words (reference:
    dataset_utils.py:187-386, the ALBERT-style n-gram scheme).

    Returns (output_tokens, masked_positions, masked_labels, token_boundary,
    masked_spans); spans are consumed by the T5 sentinel construction.
    ``masking_style``: 'bert' = 80/10/10 mask/keep/random; 't5' = always the
    mask sentinel placeholder.
    """
    # group wordpieces into whole-word candidates
    cand_indexes: List[List[int]] = []
    token_boundary = [0] * len(tokens)
    for i, tok in enumerate(tokens):
        if tok == cls_id or tok == sep_id:
            token_boundary[i] = 1
            continue
        piece = vocab_id_to_token_dict.get(tok, "") \
            if isinstance(vocab_id_to_token_dict, dict) \
            else vocab_id_to_token_dict[tok]
        if (do_whole_word_mask and cand_indexes
                and not is_start_piece(piece)):
            cand_indexes[-1].append(i)
        else:
            cand_indexes.append([i])
            if is_start_piece(piece):
                token_boundary[i] = 1

    output_tokens = list(tokens)
    if masked_lm_prob == 0:
        return output_tokens, [], [], token_boundary, []

    num_to_predict = min(int(max_predictions_per_seq),
                         max(1, int(round(len(tokens) * masked_lm_prob))))

    ngrams = np.arange(1, max_ngrams + 1, dtype=np.int64)
    pvals = 1.0 / np.arange(1, max_ngrams + 1)
    pvals /= pvals.sum()
    if favor_longer_ngram:
        pvals = pvals[::-1]

    # candidate n-gram windows anchored at each whole-word position
    anchors = list(range(len(cand_indexes)))
    np_rng.shuffle(anchors)

    masked_lms: List[MaskedLmInstance] = []
    masked_spans: List[MaskedLmInstance] = []
    covered = set()
    for a in anchors:
        if len(masked_lms) >= num_to_predict:
            break
        avail = len(cand_indexes) - a  # whole words available from anchor
        if avail <= 0:
            continue
        if geometric_dist:
            # SpanBERT/T5: n ~ Geometric(0.2) clipped to max_ngrams
            n = min(int(np_rng.geometric(0.2)), max_ngrams)
        else:
            k = min(max_ngrams, avail)
            p = pvals[:k] / pvals[:k].sum()
            n = int(np_rng.choice(ngrams[:k], p=p))
        n = min(n, avail)
        # shrink the span until it fits the prediction budget
        index_set: List[int] = []
        while n > 0:
            index_set = [i for w in cand_indexes[a:a + n] for i in w]
            if len(masked_lms) + len(index_set) <= num_to_predict:
                break
            n -= 1
        if n == 0 or not index_set:
            continue
        if any(i in covered for i in index_set):
            continue
        for i in index_set:
            covered.add(i)
            if masking_style == "bert":
                if np_rng.random() < 0.8:
                    new_tok = mask_id
                elif np_rng.random() < 0.5:
                    new_tok = tokens[i]
                else:
                    new_tok = vocab_id_list[
                        int(np_rng.randint(0, len(vocab_id_list)))]
            elif masking_style == "t5":
                new_tok = mask_id
            else:
                raise ValueError(f"invalid masking style {masking_style!r}")
            output_tokens[i] = new_tok
            masked_lms.append(MaskedLmInstance(index=i, label=tokens[i]))
        masked_spans.append(MaskedLmInstance(
            index=index_set, label=[tokens[i] for i in index_set]))

    assert len(masked_lms) <= num_to_predict
    masked_lms.sort(key=lambda x: x.index)
    masked_spans.sort(key=lambda x: x.index[0])
    masked_positions = [p.index for p in masked_lms]
    masked_labels = [p.label for p in masked_lms]
    return (output_tokens, masked_positions, masked_labels, token_boundary,
            masked_spans)


def pad_and_convert_to_numpy(tokens, tokentypes, masked_positions,
                             masked_labels, pad_id, max_seq_length):
    """Pad to max_seq_length; labels -1 outside masked positions
    (reference: dataset_utils.py:389-418)."""
    num_tokens = len(tokens)
    padding = max_seq_length - num_tokens
    assert padding >= 0, (num_tokens, max_seq_length)
    assert len(tokentypes) == num_tokens
    assert len(masked_positions) == len(masked_labels)

    tokens_np = np.array(tokens + [pad_id] * padding, np.int64)
    tokentypes_np = np.array(tokentypes + [pad_id] * padding, np.int64)
    padding_mask_np = np.array([1] * num_tokens + [0] * padding, np.int64)
    labels_np = np.full(max_seq_length, -1, np.int64)
    loss_mask_np = np.zeros(max_seq_length, np.int64)
    for pos, lab in zip(masked_positions, masked_labels):
        assert pos < num_tokens
        labels_np[pos] = lab
        loss_mask_np[pos] = 1
    return tokens_np, tokentypes_np, labels_np, padding_mask_np, loss_mask_np


# --------------------------------------------------------------------------
# samples mapping (cached)
# --------------------------------------------------------------------------

def get_samples_mapping(indexed_dataset,
                        data_prefix: str,
                        num_epochs: Optional[int],
                        max_num_samples: Optional[int],
                        max_seq_length: int,
                        short_seq_prob: float,
                        seed: int,
                        name: str,
                        binary_head: bool) -> np.ndarray:
    """Build (or load the cached) [n,3] sentence-span map (reference:
    dataset_utils.py:643-729).  Only the first host process builds; the cache
    file makes re-runs instant."""
    if not num_epochs:
        if not max_num_samples:
            raise ValueError("need max_num_samples or num_epochs")
        num_epochs = np.iinfo(np.int32).max - 1
    if not max_num_samples:
        max_num_samples = np.iinfo(np.int64).max - 1

    # the doc window distinguishes train/valid/test views of the same prefix
    lo = getattr(indexed_dataset, "doc_lo", 0)
    hi = getattr(indexed_dataset, "doc_hi",
                 len(indexed_dataset.doc_idx) - 1)
    fname = (f"{data_prefix}_{name}_indexmap"
             f"_{num_epochs}ep_{max_num_samples}mns_{max_seq_length}msl"
             f"_{short_seq_prob:0.2f}ssp_{seed}s"
             f"_{2 if binary_head else 1}msn_d{lo}-{hi}.npy")

    def build():
        start = time.time()
        mapping = helpers.build_mapping(
            indexed_dataset.doc_idx,
            indexed_dataset.sizes,
            num_epochs,
            max_num_samples,
            max_seq_length,
            short_seq_prob,
            seed,
            2 if binary_head else 1,
        )
        if mapping.shape[0] == 0:
            raise RuntimeError(
                f"samples mapping for {data_prefix!r} ({name}) is empty: no "
                f"document is eligible (need >= {2 if binary_head else 1} "
                f"sentences per doc, every sentence <= 512 tokens)")
        print(f" > built samples mapping in {time.time() - start:.2f}s",
              flush=True)
        return mapping

    return _cached_mapping(fname, build)


def _cached_mapping(fname: str, build_fn) -> np.ndarray:
    """Build-once / load-many cache with multi-host safety: only host 0
    writes (atomically, via rename); other hosts poll for the file.  Falls
    back to in-memory on read-only data directories."""
    if os.path.isfile(fname):
        return np.load(fname, allow_pickle=True, mmap_mode="r")
    # host identity from the bootstrap env, NOT jax.process_index(): calling
    # into jax here would initialize the backend from a data worker, and
    # the chip belongs to one process
    proc = int(os.environ.get("JAX_PROCESS_ID",
                              os.environ.get("RANK", "0")))
    nproc = int(os.environ.get("JAX_NUM_PROCESSES",
                               os.environ.get("WORLD_SIZE", "1")))
    writable = os.access(os.path.dirname(os.path.abspath(fname)) or ".",
                         os.W_OK)
    if not writable or proc == 0 or nproc == 1:
        # read-only data dir: every host builds locally (can't publish a
        # cache file for the others to poll)
        mapping = build_fn()
        if not writable:
            return mapping
        try:
            tmp = f"{fname}.tmp.{os.getpid()}"
            np.save(tmp, mapping, allow_pickle=True)
            os.replace(tmp + (".npy" if not tmp.endswith(".npy") else ""),
                       fname)
        except OSError:
            return mapping
        del mapping
    else:
        deadline = time.time() + 3600
        while not os.path.isfile(fname):
            if time.time() > deadline:
                raise TimeoutError(f"waited 1h for host 0 to build {fname}")
            time.sleep(5)
        time.sleep(1)  # let the rename settle on networked filesystems
    return np.load(fname, allow_pickle=True, mmap_mode="r")


# --------------------------------------------------------------------------
# dispatcher
# --------------------------------------------------------------------------

def get_indexed_dataset_(data_prefix, data_impl="mmap", skip_warmup=True):
    ds = make_dataset(data_prefix, data_impl, skip_warmup)
    assert ds.sizes.shape[0] == ds.doc_idx[-1]
    return ds


class _DocSlice:
    """A view of an indexed dataset restricted to a doc_idx window, so each
    split samples only its own documents (the reference mutates doc_idx in
    place, dataset_utils.py:533-585; a view is safer)."""

    def __init__(self, inner, doc_lo: int, doc_hi: int):
        self._inner = inner
        self.doc_lo = doc_lo  # global index of this view's first document
        self.doc_hi = doc_hi
        self.doc_idx = inner.doc_idx[doc_lo:doc_hi + 1]
        self.sizes = inner.sizes

    def __getitem__(self, idx):
        return self._inner[idx]

    def get(self, idx, offset=0, length=None):
        return self._inner.get(idx, offset, length)


def build_train_valid_test_datasets_core(
        data_prefix,
        splits_string: str,
        train_valid_test_num_samples,
        max_seq_length: int,
        masked_lm_prob: float,
        short_seq_prob: float,
        seed: int,
        dataset_type: str,
        tokenizer,
        binary_head: bool = False,
        max_seq_length_dec: Optional[int] = None,
        data_impl: str = "mmap",
        **extra):
    """Split documents, then build one dataset per split (reference:
    dataset_utils.py:421-592).  ``data_prefix`` may be a single prefix or a
    [w1, p1, w2, p2, ...] blend specification."""
    prefixes = [data_prefix] if isinstance(data_prefix, str) else data_prefix
    if len(prefixes) == 1:
        return _build_single(prefixes[0], splits_string,
                             train_valid_test_num_samples, max_seq_length,
                             masked_lm_prob, short_seq_prob, seed,
                             dataset_type, tokenizer, binary_head,
                             max_seq_length_dec, data_impl, **extra)
    # blended: weight-1 prefix-1 weight-2 prefix-2 ...
    assert len(prefixes) % 2 == 0
    weights = np.array([float(prefixes[2 * i])
                        for i in range(len(prefixes) // 2)])
    weights /= weights.sum()
    names = [prefixes[2 * i + 1] for i in range(len(prefixes) // 2)]
    per = [[int(np.ceil(n * w * 1.005))
            for n in train_valid_test_num_samples] for w in weights]
    # keep (dataset, weight) pairs aligned even when a prefix yields no
    # dataset for a given split
    parts = {0: [], 1: [], 2: []}
    for prefix, w, counts in zip(names, weights, per):
        built = _build_single(prefix, splits_string, counts, max_seq_length,
                              masked_lm_prob, short_seq_prob, seed,
                              dataset_type, tokenizer, binary_head,
                              max_seq_length_dec, data_impl, **extra)
        for i, ds in enumerate(built):
            if ds is not None:
                parts[i].append((ds, w))

    def mk(pairs, size):
        if not pairs or not size:
            return None
        ds, ws = zip(*pairs)
        return BlendableDataset(list(ds), list(ws), size)

    return (mk(parts[0], train_valid_test_num_samples[0]),
            mk(parts[1], train_valid_test_num_samples[1]),
            mk(parts[2], train_valid_test_num_samples[2]))


def _build_single(data_prefix, splits_string, train_valid_test_num_samples,
                  max_seq_length, masked_lm_prob, short_seq_prob, seed,
                  dataset_type, tokenizer, binary_head, max_seq_length_dec,
                  data_impl, **extra):
    from megatron_llm_tpu.data.bert_dataset import BertDataset
    from megatron_llm_tpu.data.ict_dataset import ICTDataset
    from megatron_llm_tpu.data.t5_dataset import T5Dataset

    indexed = get_indexed_dataset_(data_prefix, data_impl)
    total_docs = indexed.doc_idx.shape[0] - 1
    splits = get_train_valid_test_split_(splits_string, total_docs)

    def build(i, name):
        if splits[i + 1] <= splits[i]:
            return None
        if not train_valid_test_num_samples[i]:
            return None  # split present but 0 samples requested
        view = _DocSlice(indexed, splits[i], splits[i + 1])
        kwargs = dict(
            name=name, data_prefix=data_prefix, num_epochs=None,
            max_num_samples=train_valid_test_num_samples[i],
            max_seq_length=max_seq_length, seed=seed, tokenizer=tokenizer,
        )
        if dataset_type == DSET_TYPE_BERT:
            return BertDataset(indexed_dataset=view,
                               masked_lm_prob=masked_lm_prob,
                               short_seq_prob=short_seq_prob,
                               binary_head=binary_head, **kwargs)
        if dataset_type == DSET_TYPE_T5:
            return T5Dataset(indexed_dataset=view,
                             masked_lm_prob=masked_lm_prob,
                             max_seq_length_dec=max_seq_length_dec,
                             short_seq_prob=short_seq_prob, **kwargs)
        if dataset_type == DSET_TYPE_ICT:
            return ICTDataset(block_dataset=view, **kwargs, **extra)
        raise ValueError(f"invalid dataset_type {dataset_type!r}")

    return build(0, "train"), build(1, "valid"), build(2, "test")
