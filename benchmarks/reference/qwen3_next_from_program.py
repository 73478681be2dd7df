"""``reference/qwen3_next.py``'s weights, read out of the program's
parameter tree (``megatron_llm_tpu/models/transformer.py``): the norms,
the router, the shared expert and the experts stacked over ALL layers
under ``layers``, the two mixer kinds stacked apart under
``layers['gated_delta']`` and ``layers['attention']`` (model layer i is
layer ``kind_index[i]`` of its kind).  What this file alone knows:

* THE NORMS' SCALE.  The reference computes ``x_hat * (1 + w)``, the
  program ``x_hat * s``: ``w = s - 1`` for every norm of the stream, the
  per-head query and key norms and the final norm.  The gated norm
  inside a delta layer is ``w`` itself on both sides.
* THE FUSED PROJECTION.  ``query_key_value`` holds, for each key-value
  group, its query heads, its key head, its value head and then its
  query heads' GATES (``2 qpg + 2`` heads of ``d`` a group).  The
  reference's ``wq`` is the published ``q_proj``: a head's ``d`` query
  and ``d`` gate columns side by side.
* THE ROTARY RELABELLING, PARTIAL.  The program rotates interleaved
  pairs (2i, 2i + 1) of a head's FIRST ``rot`` columns; the reference,
  like the published model, rotates halves (i, i + rot / 2) of them.  So
  within each head the reference's column i < rot / 2 is the program's
  2i and its column i + rot / 2 the program's 2i + 1, and the columns
  from ``rot`` on stay where they are: one fixed permutation of the
  columns of W_q and W_k, and of the entries of the two per-head norm
  scales with them (the norm's mean is blind to the order, a query-key
  product too).  The gate's and the value's columns are not relabelled.
* THE SHARE.  The router's ``kernel`` is over ALL the experts it
  scores; ``experts['w_in']`` [L, held, H, 2F] / ``w_out`` [L, held, F,
  H] hold the program's share, where held expert j is the router's
  expert ``experts_first + j``.

Everything is copied to one device and to float32 a layer (or an expert)
at a time, the embedding and the head a few rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def partial_rotate_half_columns(heads: int, d: int, rot: int) -> np.ndarray:
    """For each column of the reference's projection (halves of a head's
    first ``rot`` dimensions rotate), the program's column (interleaved
    pairs of them) that holds it."""
    within = np.concatenate([np.arange(0, rot, 2), np.arange(1, rot, 2),
                             np.arange(rot, d)])
    return (np.arange(heads)[:, None] * d + within[None, :]).reshape(-1)


class ProgramWeights:
    def __init__(self, params, cfg: dict, device=None):
        self.p = params
        self.device = device or jax.devices()[0]
        self.use(cfg)

    def use(self, cfg: dict) -> None:
        """Read the tree by ``cfg``: the harness builds this adapter from
        the FILE's keys, and the probe hands it the share of experts the
        program was really given (a rehearsal's differs)."""
        self.cfg = cfg
        self.nh = int(cfg["num_attention_heads"])
        self.ng = int(cfg["num_key_value_heads"])
        self.d = int(cfg["head_dim"])
        self.rot = int(self.d * float(cfg["partial_rotary_factor"]))
        self.first = int(cfg.get("experts_first", 0))
        every = int(cfg["full_attention_interval"])
        full = [(i + 1) % every == 0
                for i in range(int(cfg["num_hidden_layers"]))]
        self.full = full
        self.kind_index = [full[:i].count(f) for i, f in enumerate(full)]

    def _f32(self, x):
        return jax.device_put(x, self.device).astype(jnp.float32)

    def _w(self, scale):
        """A norm's ``w`` of the program's scale ``1 + w``."""
        return self._f32(scale) - 1.0

    def embedding_rows(self, tokens):
        table = self.p["embedding"]["word"]["embedding"]
        return self._f32(table[jnp.asarray(np.asarray(tokens, np.int32))])

    def output_rows(self, first: int, last: int):
        return self._f32(self.p["lm_head"]["weight"][first:last])

    def final_norm(self):
        return self._w(self.p["transformer"]["final_norm"]["scale"])

    def _swiglu(self, mlp, j: int, prefix: str = "") -> dict:
        w_in = self._f32(mlp["dense_h_to_4h"]["kernel"][j])
        f = w_in.shape[1] // 2
        return {prefix + "w1": w_in[:, :f], prefix + "w3": w_in[:, f:],
                prefix + "w2": self._f32(mlp["dense_4h_to_h"]["kernel"][j])}

    def layer(self, i: int) -> dict:
        layers = self.p["transformer"]["layers"]
        j = self.kind_index[i]
        shared = layers["mlp"]["shared"]
        w = {"mixer_norm": self._w(layers["input_norm"]["scale"][i]),
             "ffn_norm": self._w(layers["post_attention_norm"]["scale"][i]),
             "gate": self._f32(layers["mlp"]["router"]["kernel"][i]),
             "shared_gate": self._f32(shared["gate"]["kernel"][i]),
             **self._swiglu(shared, i, "shared_")}
        if not self.full[i]:
            m = layers["gated_delta"]
            w.update({
                "in_proj": self._f32(m["in_proj"]["kernel"][j]),
                "ba_proj": self._f32(m["ba_proj"]["kernel"][j]),
                "conv_kernel": self._f32(m["conv"]["kernel"][j]),
                "dt_bias": self._f32(m["dt_bias"][j]),
                "A_log": self._f32(m["A_log"][j]),
                "gate_norm": self._f32(m["norm"]["scale"][j]),
                "out_proj": self._f32(m["out_proj"]["kernel"][j])})
            return w
        a = layers["attention"]
        qkv = self._f32(a["query_key_value"]["kernel"][j])
        h, d = qkv.shape[0], self.d
        qpg = self.nh // self.ng
        grouped = qkv.reshape(h, self.ng, 2 * qpg + 2, d)
        within = partial_rotate_half_columns(1, d, self.rot)
        q = grouped[:, :, :qpg, :].reshape(h, self.nh, d)[:, :, within]
        g = grouped[:, :, qpg + 2:, :].reshape(h, self.nh, d)
        w.update({
            "wq": jnp.concatenate([q, g], axis=-1).reshape(
                h, self.nh * 2 * d),
            "wk": grouped[:, :, qpg, :].reshape(h, self.ng * d)[
                :, partial_rotate_half_columns(self.ng, d, self.rot)],
            "wv": grouped[:, :, qpg + 1, :].reshape(h, self.ng * d),
            "q_norm": self._w(a["q_norm"]["scale"][j])[within],
            "k_norm": self._w(a["k_norm"]["scale"][j])[within],
            "wo": self._f32(a["dense"]["kernel"][j])})
        return w

    def expert(self, i: int, e: int) -> dict:
        """The ROUTER's expert ``e`` of layer i, which the program holds
        as its expert ``e - experts_first``."""
        ex = self.p["transformer"]["layers"]["mlp"]["experts"]
        w_in = self._f32(ex["w_in"][i, e - self.first])
        f = w_in.shape[1] // 2
        return {"w1": w_in[:, :f], "w3": w_in[:, f:],
                "w2": self._f32(ex["w_out"][i, e - self.first])}
