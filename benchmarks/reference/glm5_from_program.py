"""``reference/glm5.py``'s weights, read out of the program's parameter
tree (``megatron_llm_tpu/models/transformer.py``): the leading dense
layers stacked under ``dense_layers`` and the sparse ones under
``layers`` (model layer i is sparse layer ``i - first_k_dense_replace``
there); latent attention's leaves (``query_down``, ``query_norm``,
``query`` from the compressed query, ``kv_down``, ``kv_norm``, ``kv_up``
whose columns are, a head, its 192 nope keys then its 256 values,
``dense``); the indexer's (``indexer.query`` from the compressed query,
``key``, ``weights``, the key's LayerNorm); the fused SwiGLU kernels as
[gate | up]; the router's ``kernel`` (every expert it scores) and
``choice_bias``; the shared MLP; the experts' ``w_in`` [L, held, H, 2F]
/ ``w_out`` [L, held, F, H], the program's share, where held expert j is
the router's expert ``experts_first + j``.  No relabelling: the program
and the reference both turn a rope vector's interleaved pairs where they
lie, the indexer's first ``qk_rope_head_dim`` columns among them.
Everything is copied to one device and to float32 a layer (or an expert)
at a time, the embedding and the head a few rows at a time.
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
        the FILE's keys, and the probe hands it the share of experts and
        the depth of dense layers the program was really given (a
        rehearsal's differ)."""
        self.cfg = cfg
        self.dense = int(cfg["first_k_dense_replace"])
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
        ix = a["indexer"]
        w = {
            "w_qa": self._f32(a["query_down"]["kernel"][j]),
            "q_norm": self._f32(a["query_norm"]["scale"][j]),
            "w_qb": self._f32(a["query"]["kernel"][j]),
            "w_kva": self._f32(a["kv_down"]["kernel"][j]),
            "kv_norm": self._f32(a["kv_norm"]["scale"][j]),
            "w_kvb": self._f32(a["kv_up"]["kernel"][j]),
            "wo": self._f32(a["dense"]["kernel"][j]),
            "index_wq": self._f32(ix["query"]["kernel"][j]),
            "index_wk": self._f32(ix["key"]["kernel"][j]),
            "index_ww": self._f32(ix["weights"]["kernel"][j]),
            "index_k_norm": self._f32(ix["key_norm"]["scale"][j]),
            "index_k_bias": self._f32(ix["key_norm"]["bias"][j]),
            "attention_norm": self._f32(stack["input_norm"]["scale"][j]),
            "ffn_norm": self._f32(stack["post_attention_norm"]["scale"][j]),
        }
        mlp = stack["mlp"]
        if not sparse:
            return {**w, **self._swiglu(mlp, j)}
        w["gate"] = self._f32(mlp["router"]["kernel"][j])
        w["choice_bias"] = self._f32(mlp["router"]["choice_bias"][j])
        return {**w, **self._swiglu(mlp["shared"], j, "shared_")}

    def expert(self, i: int, e: int) -> dict:
        """The ROUTER's expert ``e`` of model layer i, which the program
        holds as its expert ``e - experts_first``."""
        ex = self.p["transformer"]["layers"]["mlp"]["experts"]
        w_in = self._f32(ex["w_in"][i - self.dense, e - self.first])
        f = w_in.shape[1] // 2
        return {"w1": w_in[:, :f], "w3": w_in[:, f:],
                "w2": self._f32(ex["w_out"][i - self.dense, e - self.first])}
