"""The reference's weights, read out of the program's parameter tree.

This is the only file of the reference that knows the program's layout
(``megatron_llm_tpu/models/transformer.py``): layers stacked on a leading
axis, the fused QKV kernel in Megatron's grouped layout (for each KV
group: its query heads, then its key head, then its value head), the
fused SwiGLU kernel as [gate | up], and for the experts ``w_in``
[E, H, 2F] / ``w_out`` [E, F, H].  Everything is copied to one device
and to float32 a layer (or an expert) at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class ProgramWeights:
    def __init__(self, params, cfg: dict, device=None):
        self.p = params
        self.cfg = cfg
        self.device = device or jax.devices()[0]
        self.nh = int(cfg["num_attention_heads"])
        self.ng = int(cfg["num_key_value_heads"])

    def _f32(self, x):
        return jax.device_put(x, self.device).astype(jnp.float32)

    def embedding(self):
        return self._f32(self.p["embedding"]["word"]["embedding"])

    def output(self):
        return self._f32(self.p["lm_head"]["weight"])

    def final_norm(self):
        return self._f32(self.p["transformer"]["final_norm"]["scale"])

    def layer(self, i: int) -> dict:
        layers = self.p["transformer"]["layers"]
        qkv = self._f32(layers["attention"]["query_key_value"]["kernel"][i])
        h = qkv.shape[0]
        qpg = self.nh // self.ng
        d = qkv.shape[1] // (self.ng * (qpg + 2))
        grouped = qkv.reshape(h, self.ng, qpg + 2, d)
        w = {
            "wq": grouped[:, :, :qpg, :].reshape(h, self.nh * d),
            "wk": grouped[:, :, qpg, :].reshape(h, self.ng * d),
            "wv": grouped[:, :, qpg + 1, :].reshape(h, self.ng * d),
            "wo": self._f32(layers["attention"]["dense"]["kernel"][i]),
            "attention_norm": self._f32(layers["input_norm"]["scale"][i]),
            "ffn_norm": self._f32(layers["post_attention_norm"]["scale"][i]),
        }
        mlp = layers["mlp"]
        if "router" in mlp:
            w["gate"] = self._f32(mlp["router"]["kernel"][i])
        else:
            w_in = self._f32(mlp["dense_h_to_4h"]["kernel"][i])
            f = w_in.shape[1] // 2
            w["w1"], w["w3"] = w_in[:, :f], w_in[:, f:]
            w["w2"] = self._f32(mlp["dense_4h_to_h"]["kernel"][i])
        return w

    def expert(self, i: int, e: int) -> dict:
        ex = self.p["transformer"]["layers"]["mlp"]["experts"]
        w_in = self._f32(ex["w_in"][i, e])
        f = w_in.shape[1] // 2
        return {"w1": w_in[:, :f], "w3": w_in[:, f:],
                "w2": self._f32(ex["w_out"][i, e])}
