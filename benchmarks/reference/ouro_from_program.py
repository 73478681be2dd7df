"""``reference/ouro.py``'s weights, read out of the program's parameter
tree (``megatron_llm_tpu/models/transformer.py``): ONE stack of
``num_hidden_layers`` layers under ``layers``, whatever the number of
passes (the passes share it), ``final_norm`` and the exit gate
(``exit_gate``: ``kernel`` [hidden] and ``bias``) beside it.  What this
file alone knows:

* THE FUSED PROJECTION.  ``query_key_value`` holds, for each key-value
  group (here a group is one query head), its query head, its key head
  and its value head (3 heads of ``d`` a group).
* THE ROTARY RELABELLING.  The program rotates interleaved pairs of a
  head's columns (2i, 2i+1); the reference, like the published model,
  rotates (i, i + d/2).  So within each head the reference's column i is
  the program's column 2i and its column i + d/2 the program's 2i + 1:
  one fixed permutation of the columns of W_q and W_k (a query-key
  product is blind to the order), as ``qwen3_next_from_program.py`` and
  ``trinity_from_program.py`` say of theirs.
* THE FOUR NORMS.  ``input_norm`` (before attention; published
  ``input_layernorm``), ``attention_output_norm`` (``input_layernorm_2``:
  the attention's OUTPUT before the residual adds it),
  ``post_attention_norm`` (before the MLP; ``post_attention_layernorm``),
  ``mlp_output_norm`` (``post_attention_layernorm_2``).

Everything is copied to one device and to float32 a layer at a time, the
embedding and the head a few rows at a time.
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
        self.cfg = cfg
        self.device = device or jax.devices()[0]
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

    def exit_gate(self):
        gate = self.p["transformer"]["exit_gate"]
        return self._f32(gate["kernel"]), self._f32(gate["bias"])

    def layer(self, j: int) -> dict:
        stack = self.p["transformer"]["layers"]
        qkv = self._f32(stack["attention"]["query_key_value"]["kernel"][j])
        h = qkv.shape[0]
        qpg = self.nh // self.ng
        d = qkv.shape[1] // (self.ng * (qpg + 2))
        grouped = qkv.reshape(h, self.ng, qpg + 2, d)
        w_in = self._f32(stack["mlp"]["dense_h_to_4h"]["kernel"][j])
        f = w_in.shape[1] // 2
        return {
            "wq": grouped[:, :, :qpg, :].reshape(h, self.nh * d)[
                :, rotate_half_columns(self.nh, d)],
            "wk": grouped[:, :, qpg, :].reshape(h, self.ng * d)[
                :, rotate_half_columns(self.ng, d)],
            "wv": grouped[:, :, qpg + 1, :].reshape(h, self.ng * d),
            "wo": self._f32(stack["attention"]["dense"]["kernel"][j]),
            "attention_norm": self._f32(stack["input_norm"]["scale"][j]),
            "attention_out_norm": self._f32(
                stack["attention_output_norm"]["scale"][j]),
            "ffn_norm": self._f32(stack["post_attention_norm"]["scale"][j]),
            "mlp_out_norm": self._f32(stack["mlp_output_norm"]["scale"][j]),
            "w1": w_in[:, :f], "w3": w_in[:, f:],
            "w2": self._f32(stack["mlp"]["dense_4h_to_h"]["kernel"][j]),
        }
