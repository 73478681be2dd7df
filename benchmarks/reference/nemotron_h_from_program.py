"""``reference/nemotron_h.py``'s weights, read out of the program's
parameter tree (``megatron_llm_tpu/models/transformer.py``): ONE norm a
layer stacked over ALL layers under ``layers['input_norm']``, the three
kinds of layer stacked apart under ``layers['mamba']``,
``layers['attention']`` and ``layers['moe']`` (model layer i is layer
``kind_index[i]`` of its kind); the fused QKV kernel in Megatron's
grouped layout (for each KV group its query heads, its key head, its
value head; nothing rotates, so no relabelling); a Mamba mixer's
``in_proj`` as [z | xBC | dt], its convolution ``[channels, taps]``; the
router's ``kernel`` and ``choice_bias``; the UNGATED shared MLP and
experts, two matrices each: ``w_in`` [L_moe, held, H, F laid out at
whole lanes] / ``w_out`` [L_moe, held, F, H], where held expert j of the
program is the router's expert ``experts_first + j``.  THE LAYOUT IS
UNDONE HERE: the program lays ``w_in``'s 1856 columns out at 1920
(``models/moe.py::laid_width``) and drops what the columns past the
width give before ``w_out``; the reference is given the first ``F``
(``w_out``'s rows) and never sees them.  The head is its own matrix
(``lm_head``).  Everything is copied to one device and to float32 a
layer (or an expert) at a time, the embedding and the head a few rows at
a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


class ProgramWeights:
    def __init__(self, params, cfg: dict, device=None):
        self.p = params
        self.device = device or jax.devices()[0]
        self.use(cfg)

    def use(self, cfg: dict) -> None:
        """Read the tree by ``cfg``: the harness builds this adapter from
        the FILE's keys, and the probe hands it the pattern and the share
        of experts the program was really given (a rehearsal's differ)."""
        self.cfg = cfg
        self.nh = int(cfg["num_attention_heads"])
        self.ng = int(cfg["num_key_value_heads"])
        self.first = int(cfg.get("experts_first", 0))
        self.kinds = [KINDS[c] for c in cfg["hybrid_override_pattern"]][
            :int(cfg["num_hidden_layers"])]
        self.kind_index = [self.kinds[:i].count(k)
                           for i, k in enumerate(self.kinds)]

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
        layers = self.p["transformer"]["layers"]
        kind, j = self.kinds[i], self.kind_index[i]
        w = {"norm": self._f32(layers["input_norm"]["scale"][i])}
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
        elif kind == "moe":
            m = layers["moe"]
            w.update({
                "gate": self._f32(m["router"]["kernel"][j]),
                "choice_bias": self._f32(m["router"]["choice_bias"][j]),
                "shared_up": self._f32(
                    m["shared"]["dense_h_to_4h"]["kernel"][j]),
                "shared_down": self._f32(
                    m["shared"]["dense_4h_to_h"]["kernel"][j])})
        else:
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
        """The ROUTER's expert ``e`` of model layer i (an expert layer),
        which the program holds as its expert ``e - experts_first``."""
        ex = self.p["transformer"]["layers"]["moe"]["experts"]
        j = self.kind_index[i]
        w_down = self._f32(ex["w_out"][j, e - self.first])
        return {"w_up": self._f32(ex["w_in"][j, e - self.first])[
            :, :w_down.shape[0]], "w_down": w_down}
