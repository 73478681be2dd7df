"""``reference/brumby.py``'s weights, read out of the program's parameter
tree (``megatron_llm_tpu/models/transformer.py``).  What this file alone
knows:

* THE STACKS.  The two norms and the MLP of every layer are stacked under
  ``layers``; the MIXERS are stacked apart by kind under
  ``layers['retention']`` (one kind, so model layer i is entry i).
* THE FUSED PROJECTION in Megatron's grouped layout: for each key-value
  group its query heads, its key head, its value head.  The gate's
  projection ``gate`` ``[h, kv]`` is its own leaf.
* THE ROTARY RELABELLING.  The program rotates interleaved pairs of a
  head's columns (2i, 2i+1); the reference, like the published trunk,
  rotates (i, i + d/2).  So within each head the reference's column i is
  the program's column 2i and its column i + d/2 the program's 2i + 1:
  one fixed permutation of the columns of W_q and W_k, and of the
  entries of the two per-head norm scales with them (the norm's mean is
  blind to the order, a query-key product too).  ``state_columns`` is
  the same permutation for a STATE: the program's ``phi`` is over its
  own order of a key's columns.
* THE UNTIED HEAD: ``lm_head.weight``'s rows ``[vocab, h]``.

Everything is copied to one device and to float32 a layer at a time, the
MLP a block of columns at a time, the embedding and the head a few rows
at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rotate_half_columns(heads: int, d: int) -> np.ndarray:
    """For each column of the reference's (rotate-half) projection, the
    program's (interleaved) column that holds it."""
    within = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    return (np.arange(heads)[:, None] * d + within[None, :]).reshape(-1)


def state_columns(d: int) -> np.ndarray:
    """For each column of a key as the PROGRAM holds it, the reference's
    column that holds it (the inverse of ``rotate_half_columns``)."""
    return np.argsort(rotate_half_columns(1, d))


class ProgramWeights:
    def __init__(self, params, cfg: dict, device=None):
        self.p = params
        self.device = device or jax.devices()[0]
        self.use(cfg)

    def use(self, cfg: dict) -> None:
        self.cfg = cfg
        self.nh = int(cfg["num_attention_heads"])
        self.ng = int(cfg["num_key_value_heads"])

    def _f32(self, x):
        return jax.device_put(x, self.device).astype(jnp.float32)

    def embedding_rows(self, tokens):
        table = self.p["embedding"]["word"]["embedding"]
        return self._f32(table[jnp.asarray(np.asarray(tokens, np.int32))])

    def output_rows(self, first: int, last: int):
        return self._f32(self.p["lm_head"]["weight"][first:last])

    def final_norm(self):
        return self._f32(self.p["transformer"]["final_norm"]["scale"])

    def layer(self, i: int) -> dict:
        stack = self.p["transformer"]["layers"]
        mixer = stack["retention"]
        qkv = self._f32(mixer["query_key_value"]["kernel"][i])
        h = qkv.shape[0]
        qpg = self.nh // self.ng
        d = qkv.shape[1] // (self.ng * (qpg + 2))
        grouped = qkv.reshape(h, self.ng, qpg + 2, d)
        within = rotate_half_columns(1, d)
        return {
            "input_norm": self._f32(stack["input_norm"]["scale"][i]),
            "post_norm": self._f32(stack["post_attention_norm"]["scale"][i]),
            "wq": grouped[:, :, :qpg, :].reshape(h, self.nh * d)[
                :, rotate_half_columns(self.nh, d)],
            "wk": grouped[:, :, qpg, :].reshape(h, self.ng * d)[
                :, rotate_half_columns(self.ng, d)],
            "wv": grouped[:, :, qpg + 1, :].reshape(h, self.ng * d),
            "wg": self._f32(mixer["gate"]["kernel"][i]),
            "q_norm": self._f32(mixer["q_norm"]["scale"][i])[within],
            "k_norm": self._f32(mixer["k_norm"]["scale"][i])[within],
            "wo": self._f32(mixer["dense"]["kernel"][i])}

    def mlp_block(self, i: int, lo: int, hi: int):
        """(w1, w3, w2) of columns ``lo .. hi`` of layer i's MLP."""
        mlp = self.p["transformer"]["layers"]["mlp"]
        w_in = mlp["dense_h_to_4h"]["kernel"]
        f = w_in.shape[2] // 2
        return (self._f32(w_in[i, :, lo:hi]),
                self._f32(w_in[i, :, f + lo:f + hi]),
                self._f32(mlp["dense_4h_to_h"]["kernel"][i, lo:hi]))
