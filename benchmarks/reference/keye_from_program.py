"""``reference/keye.py``'s weights, read out of the program's parameter
tree: ``from_program.py``'s adapter (the fused QKV kernel in Megatron's
grouped layout, the experts' ``w_in`` / ``w_out``) plus what Keye adds.

* The two per-head QK-norm scales, ``attention.q_norm.scale`` and
  ``attention.k_norm.scale``, each of one head's width.
* The indexer: ``attention.indexer.query`` / ``key`` / ``weights``
  kernels and the key's LayerNorm (``key_norm.scale`` / ``.bias``).
* The rotary relabelling.  The program rotates interleaved pairs of a
  head's columns (2i, 2i+1); the reference, like the published model,
  rotates (i, i + d/2).  So within each head the reference's column i is
  the program's column 2i and its column i + d/2 the program's 2i + 1:
  one fixed permutation, applied to the columns of Wq, Wk and the
  indexer's two rotated projections and to the entries of the norms'
  parameters over those columns (a norm over a head and a query-key
  product are sums over the head's columns, blind to their order).
* The embedding and the head a few rows at a time: at 151,936 rows a
  float32 copy of either would be 1.2 GB beside the program.
"""

from __future__ import annotations

import importlib.util
import os

import jax.numpy as jnp
import numpy as np

_spec = importlib.util.spec_from_file_location(
    "bench_from_program",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "from_program.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)


def rotate_half_columns(heads: int, d: int) -> np.ndarray:
    """For each column of the reference's (rotate-half) projection, the
    program's (interleaved) column that holds it."""
    within = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    return (np.arange(heads)[:, None] * d + within[None, :]).reshape(-1)


class ProgramWeights(_base.ProgramWeights):
    def embedding_rows(self, tokens):
        table = self.p["embedding"]["word"]["embedding"]
        return self._f32(table[jnp.asarray(np.asarray(tokens, np.int32))])

    def output_rows(self, first: int, last: int):
        return self._f32(self.p["lm_head"]["weight"][first:last])

    def layer(self, i: int) -> dict:
        w = super().layer(i)
        att = self.p["transformer"]["layers"]["attention"]
        d = w["wq"].shape[1] // self.nh
        w["wq"] = w["wq"][:, rotate_half_columns(self.nh, d)]
        w["wk"] = w["wk"][:, rotate_half_columns(self.ng, d)]
        one = rotate_half_columns(1, d)
        w["q_norm"] = self._f32(att["q_norm"]["scale"][i])[one]
        w["k_norm"] = self._f32(att["k_norm"]["scale"][i])[one]
        ix = att["indexer"]
        wk = self._f32(ix["key"]["kernel"][i])
        di = wk.shape[1]
        wq = self._f32(ix["query"]["kernel"][i])
        one = rotate_half_columns(1, di)
        w["index_wq"] = wq[:, rotate_half_columns(wq.shape[1] // di, di)]
        w["index_wk"] = wk[:, one]
        w["index_k_norm"] = self._f32(ix["key_norm"]["scale"][i])[one]
        w["index_k_bias"] = self._f32(ix["key_norm"]["bias"][i])[one]
        w["index_ww"] = self._f32(ix["weights"]["kernel"][i])
        return w
