"""``reference/mellum.py``'s weights, read out of the program's parameter
tree: ``from_program.py``'s adapter (the fused QKV kernel in Megatron's
grouped layout, the experts' ``w_in`` / ``w_out``) plus:

* The rotary relabelling.  The program rotates interleaved pairs of a
  head's columns (2i, 2i+1); the reference, like the published model,
  rotates (i, i + d/2).  So within each head the reference's column i is
  the program's column 2i and its column i + d/2 the program's 2i + 1:
  one fixed permutation of the columns of Wq and Wk (a query-key product
  is a sum over a head's columns, blind to their order).
* The embedding and the head a few rows at a time: at 98,304 rows a
  float32 copy of either would be 0.9 GB beside the program.
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
        d = w["wq"].shape[1] // self.nh
        w["wq"] = w["wq"][:, rotate_half_columns(self.nh, d)]
        w["wk"] = w["wk"][:, rotate_half_columns(self.ng, d)]
        return w
