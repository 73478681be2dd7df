"""``reference/granite.py``'s weights, read out of the program's parameter
tree (``megatron_llm_tpu/models/transformer.py``): the norms, the router,
the shared MLP and the experts stacked over ALL layers under ``layers``,
the two mixer kinds stacked apart under ``layers['mamba']`` and
``layers['attention']`` (model layer i is layer ``kind_index[i]`` of its
kind); the fused QKV kernel in Megatron's grouped layout (for each KV
group its query heads, its key head, its value head; nothing rotates, so
no relabelling); a Mamba mixer's ``in_proj`` as [z | xBC | dt], its
convolution ``[channels, taps]``; the fused SwiGLU kernels as
[silu'd half | other half]; the experts' ``w_in`` [L, held, H, 2F] /
``w_out`` [L, held, F, H], where held expert j of the program is the
router's expert ``experts_first + j``.  The head is the embedding (tied).
Everything is copied to one device and to float32 a layer (or an expert)
at a time, the embedding a few rows at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class ProgramWeights:
    def __init__(self, params, cfg: dict, device=None):
        self.p = params
        self.device = device or jax.devices()[0]
        self.use(cfg)

    def use(self, cfg: dict) -> None:
        """Read the tree by ``cfg``: the harness builds this adapter from
        the FILE's keys, and the probe hands it the layer types and the
        share of experts the program was really given (a rehearsal's
        differ)."""
        self.cfg = cfg
        self.nh = int(cfg["num_attention_heads"])
        self.ng = int(cfg["num_key_value_heads"])
        self.first = int(cfg.get("experts_first", 0))
        kinds = list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]
        self.kind_index = [kinds[:i].count(k) for i, k in enumerate(kinds)]

    def _f32(self, x):
        return jax.device_put(x, self.device).astype(jnp.float32)

    def embedding_rows(self, tokens):
        table = self.p["embedding"]["word"]["embedding"]
        return self._f32(table[jnp.asarray(np.asarray(tokens, np.int32))])

    def output_rows(self, first: int, last: int):
        return self._f32(self.p["embedding"]["word"]["embedding"][first:last])

    def final_norm(self):
        return self._f32(self.p["transformer"]["final_norm"]["scale"])

    def _swiglu(self, mlp, j: int, prefix: str = "") -> dict:
        w_in = self._f32(mlp["dense_h_to_4h"]["kernel"][j])
        f = w_in.shape[1] // 2
        return {prefix + "w1": w_in[:, :f], prefix + "w3": w_in[:, f:],
                prefix + "w2": self._f32(mlp["dense_4h_to_h"]["kernel"][j])}

    def layer(self, i: int) -> dict:
        layers = self.p["transformer"]["layers"]
        kind, j = self.cfg["layer_types"][i], self.kind_index[i]
        w = {"mixer_norm": self._f32(layers["input_norm"]["scale"][i]),
             "ffn_norm": self._f32(layers["post_attention_norm"]["scale"][i]),
             "gate": self._f32(layers["mlp"]["router"]["kernel"][i]),
             **self._swiglu(layers["mlp"]["shared"], i, "shared_")}
        if kind == "mamba":
            m = layers["mamba"]
            w.update({
                "in_proj": self._f32(m["in_proj"]["kernel"][j]),
                "conv_kernel": self._f32(m["conv"]["kernel"][j]),
                "conv_bias": self._f32(m["conv"]["bias"][j]),
                "dt_bias": self._f32(m["dt_bias"][j]),
                "A_log": self._f32(m["A_log"][j]),
                "D": self._f32(m["D"][j]),
                "gate_norm": self._f32(m["norm"]["scale"][j]),
                "out_proj": self._f32(m["out_proj"]["kernel"][j])})
            return w
        a = layers["attention"]
        qkv = self._f32(a["query_key_value"]["kernel"][j])
        h = qkv.shape[0]
        qpg = self.nh // self.ng
        d = qkv.shape[1] // (self.ng * (qpg + 2))
        grouped = qkv.reshape(h, self.ng, qpg + 2, d)
        w.update({
            "wq": grouped[:, :, :qpg, :].reshape(h, self.nh * d),
            "wk": grouped[:, :, qpg, :].reshape(h, self.ng * d),
            "wv": grouped[:, :, qpg + 1, :].reshape(h, self.ng * d),
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
