"""``reference/trinity.py``'s weights, read out of the program's parameter
tree (``megatron_llm_tpu/models/transformer.py``): the leading dense
layers stacked under ``dense_layers`` and the sparse ones under
``layers`` (model layer i is sparse layer ``i - num_dense_layers``
there).  What this file alone knows:

* THE FUSED PROJECTION.  ``query_key_value`` holds, for each key-value
  group, its query heads, its key head, its value head and then its
  query heads' GATES (``2 qpg + 2`` heads of ``d`` a group; the gate is
  the published ``gate_proj``, fused so that the normed input is read
  once).
* THE ROTARY RELABELLING.  The program rotates interleaved pairs of a
  head's columns (2i, 2i+1); the reference, like the published model,
  rotates (i, i + d/2).  So within each head the reference's column i is
  the program's column 2i and its column i + d/2 the program's 2i + 1:
  one fixed permutation of the columns of W_q and W_k, and of the
  entries of the two per-head norm scales with them (the norm's mean is
  blind to the order, a query-key product too).  The layers that do not
  rotate are relabelled alike, which changes nothing there.
* THE FOUR NORMS.  ``input_norm`` (before attention),
  ``attention_output_norm`` (HF: ``post_attention_layernorm``),
  ``post_attention_norm`` (before the MLP; HF: ``pre_mlp_layernorm``),
  ``mlp_output_norm`` (HF: ``post_mlp_layernorm``).
* THE SHARE.  The router's ``kernel`` and ``choice_bias`` are over ALL
  the experts it scores; ``experts['w_in']`` [L_sparse, held, H, 2F] /
  ``w_out`` [L_sparse, held, F, H] hold the program's share, where held
  expert j is the router's expert ``experts_first + j``.

Everything is copied to one device and to float32 a layer (or an expert)
at a time, the embedding and the head a few rows at a time (at 200,192
rows a float32 copy of either is 1.6 GB beside the program).
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


class ProgramWeights:
    def __init__(self, params, cfg: dict, device=None):
        self.p = params
        self.device = device or jax.devices()[0]
        self.use(cfg)

    def use(self, cfg: dict) -> None:
        """Read the tree by ``cfg``: the harness builds this adapter from
        the FILE's keys, and the probe hands it the share of experts and
        the dense layers the program was really given."""
        self.cfg = cfg
        self.nh = int(cfg["num_attention_heads"])
        self.ng = int(cfg["num_key_value_heads"])
        self.dense = int(cfg["num_dense_layers"])
        self.first = int(cfg.get("experts_first", 0))

    def _f32(self, x):
        return jax.device_put(x, self.device).astype(jnp.float32)

    def embedding_rows(self, tokens):
        table = self.p["embedding"]["word"]["embedding"]
        return self._f32(table[jnp.asarray(np.asarray(tokens, np.int32))])

    def output_rows(self, first: int, last: int):
        return self._f32(self.p["lm_head"]["weight"][first:last])

    def final_norm(self):
        return self._f32(self.p["transformer"]["final_norm"]["scale"])

    def _swiglu(self, mlp, j: int, prefix: str = "") -> dict:
        w_in = self._f32(mlp["dense_h_to_4h"]["kernel"][j])
        f = w_in.shape[1] // 2
        return {prefix + "w1": w_in[:, :f], prefix + "w3": w_in[:, f:],
                prefix + "w2": self._f32(mlp["dense_4h_to_h"]["kernel"][j])}

    def layer(self, i: int) -> dict:
        sparse = i >= self.dense
        stack = self.p["transformer"]["layers" if sparse else "dense_layers"]
        j = i - self.dense if sparse else i
        a = stack["attention"]
        qkv = self._f32(a["query_key_value"]["kernel"][j])
        h = qkv.shape[0]
        qpg = self.nh // self.ng
        d = qkv.shape[1] // (self.ng * (2 * qpg + 2))
        grouped = qkv.reshape(h, self.ng, 2 * qpg + 2, d)
        within = rotate_half_columns(1, d)
        w = {
            "wq": grouped[:, :, :qpg, :].reshape(h, self.nh * d)[
                :, rotate_half_columns(self.nh, d)],
            "wk": grouped[:, :, qpg, :].reshape(h, self.ng * d)[
                :, rotate_half_columns(self.ng, d)],
            "wv": grouped[:, :, qpg + 1, :].reshape(h, self.ng * d),
            "wg": grouped[:, :, qpg + 2:, :].reshape(h, self.nh * d),
            "q_norm": self._f32(a["q_norm"]["scale"][j])[within],
            "k_norm": self._f32(a["k_norm"]["scale"][j])[within],
            "wo": self._f32(a["dense"]["kernel"][j]),
            "attention_norm": self._f32(stack["input_norm"]["scale"][j]),
            "attention_out_norm": self._f32(
                stack["attention_output_norm"]["scale"][j]),
            "ffn_norm": self._f32(stack["post_attention_norm"]["scale"][j]),
            "mlp_out_norm": self._f32(stack["mlp_output_norm"]["scale"][j]),
        }
        mlp = stack["mlp"]
        if not sparse:
            return {**w, **self._swiglu(mlp, j)}
        w["gate"] = self._f32(mlp["router"]["kernel"][j])
        w["choice_bias"] = self._f32(mlp["router"]["choice_bias"][j])
        return {**w, **self._swiglu(mlp["shared"], j, "shared_")}

    def expert(self, i: int, e: int) -> dict:
        """The ROUTER's expert ``e`` of model layer i (a sparse layer),
        which the program holds as its expert ``e - experts_first``."""
        ex = self.p["transformer"]["layers"]["mlp"]["experts"]
        w_in = self._f32(ex["w_in"][i - self.dense, e - self.first])
        f = w_in.shape[1] // 2
        return {"w1": w_in[:, :f], "w3": w_in[:, f:],
                "w2": self._f32(ex["w_out"][i - self.dense, e - self.first])}
