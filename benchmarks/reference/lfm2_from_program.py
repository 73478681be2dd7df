"""``reference/lfm2.py``'s weights, read out of the program's parameter
tree (``megatron_llm_tpu/models/transformer.py``).  What this file alone
knows:

* TWO WAYS OF STACKING, ONE WAY OF COUNTING.  The two norms and the MLP
  of the leading dense layers are stacked under ``dense_layers`` and the
  sparse layers' under ``layers`` (model layer i is sparse layer ``i -
  num_dense_layers`` there); the MIXERS are stacked apart by kind under
  ``layers['conv']`` and ``layers['attention']``, and model layer i's is
  entry ``kind_index[i]`` of its kind's stack, counted over the WHOLE
  depth: a dense layer's mixer is a member of its kind's stack like any
  other layer's.
* THE CONVOLUTION.  ``conv.in_proj`` is ``[h, 3h]`` as ``[B | C | X]``,
  ``conv.conv.kernel`` ``[h, taps]`` (tap j multiplies the column ``taps
  - 1 - j`` tokens back), ``conv.out_proj`` ``[h, h]``.
* THE FUSED PROJECTION in Megatron's grouped layout: for each key-value
  group its query heads, its key head, its value head.
* THE ROTARY RELABELLING.  The program rotates interleaved pairs of a
  head's columns (2i, 2i+1); the reference, like the published model,
  rotates (i, i + d/2).  So within each head the reference's column i is
  the program's column 2i and its column i + d/2 the program's 2i + 1:
  one fixed permutation of the columns of W_q and W_k, and of the
  entries of the two per-head norm scales with them (the norm's mean is
  blind to the order, a query-key product too).
* THE TIED HEAD: the output rows are the embedding's.

Everything is copied to one device and to float32 a layer (or an expert)
at a time, the embedding a few rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"conv": "conv", "full_attention": "attention"}


def rotate_half_columns(heads: int, d: int) -> np.ndarray:
    """For each column of the reference's (rotate-half) projection, the
    program's (interleaved) column that holds it."""
    within = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    return (np.arange(heads)[:, None] * d + within[None, :]).reshape(-1)


class ProgramWeights:
    def __init__(self, params, cfg: dict, device=None):
        self.p = params
        self.device = device or jax.devices()[0]
        self.use(cfg)

    def use(self, cfg: dict) -> None:
        """Read the tree by ``cfg``: the harness builds this adapter from
        the FILE's keys, and the probe hands it the pattern and the dense
        layers the program was really given (a rehearsal's differ)."""
        self.cfg = cfg
        self.nh = int(cfg["num_attention_heads"])
        self.ng = int(cfg["num_key_value_heads"])
        self.dense = int(cfg["num_dense_layers"])
        self.kinds = [KINDS[t] for t in cfg["layer_types"]][
            :int(cfg["num_hidden_layers"])]
        self.kind_index = [self.kinds[:i].count(k)
                           for i, k in enumerate(self.kinds)]

    def _f32(self, x):
        return jax.device_put(x, self.device).astype(jnp.float32)

    def embedding_rows(self, tokens):
        table = self.p["embedding"]["word"]["embedding"]
        return self._f32(table[jnp.asarray(np.asarray(tokens, np.int32))])

    def output_rows(self, first: int, last: int):
        return self._f32(self.p["embedding"]["word"]["embedding"][first:last])

    def final_norm(self):
        return self._f32(self.p["transformer"]["final_norm"]["scale"])

    def layer(self, i: int) -> dict:
        sparse = i >= self.dense
        stack = self.p["transformer"]["layers" if sparse else "dense_layers"]
        j = i - self.dense if sparse else i
        kind, at = self.kinds[i], self.kind_index[i]
        mixer = self.p["transformer"]["layers"][kind]
        w = {"operator_norm": self._f32(stack["input_norm"]["scale"][j]),
             "ffn_norm": self._f32(stack["post_attention_norm"]["scale"][j])}
        if kind == "conv":
            w.update({
                "in_proj": self._f32(mixer["in_proj"]["kernel"][at]),
                "conv_kernel": self._f32(mixer["conv"]["kernel"][at]),
                "out_proj": self._f32(mixer["out_proj"]["kernel"][at])})
        else:
            qkv = self._f32(mixer["query_key_value"]["kernel"][at])
            h = qkv.shape[0]
            qpg = self.nh // self.ng
            d = qkv.shape[1] // (self.ng * (qpg + 2))
            grouped = qkv.reshape(h, self.ng, qpg + 2, d)
            within = rotate_half_columns(1, d)
            w.update({
                "wq": grouped[:, :, :qpg, :].reshape(h, self.nh * d)[
                    :, rotate_half_columns(self.nh, d)],
                "wk": grouped[:, :, qpg, :].reshape(h, self.ng * d)[
                    :, rotate_half_columns(self.ng, d)],
                "wv": grouped[:, :, qpg + 1, :].reshape(h, self.ng * d),
                "q_norm": self._f32(mixer["q_norm"]["scale"][at])[within],
                "k_norm": self._f32(mixer["k_norm"]["scale"][at])[within],
                "wo": self._f32(mixer["dense"]["kernel"][at])})
        mlp = stack["mlp"]
        if sparse:
            w["gate"] = self._f32(mlp["router"]["kernel"][j])
            w["choice_bias"] = self._f32(mlp["router"]["choice_bias"][j])
        else:
            w_in = self._f32(mlp["dense_h_to_4h"]["kernel"][j])
            f = w_in.shape[1] // 2
            w.update({"w1": w_in[:, :f], "w3": w_in[:, f:],
                      "w2": self._f32(mlp["dense_4h_to_h"]["kernel"][j])})
        return w

    def expert(self, i: int, e: int) -> dict:
        """Expert ``e`` of model layer i (a sparse layer)."""
        ex = self.p["transformer"]["layers"]["mlp"]["experts"]
        w_in = self._f32(ex["w_in"][i - self.dense, e])
        f = w_in.shape[1] // 2
        return {"w1": w_in[:, :f], "w3": w_in[:, f:],
                "w2": self._f32(ex["w_out"][i - self.dense, e])}
