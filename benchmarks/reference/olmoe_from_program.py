"""``reference/olmoe.py``'s weights, read out of the program's parameter
tree: ``from_program.py``'s adapter (the fused QKV kernel in Megatron's
grouped layout, the experts' ``w_in`` / ``w_out``) plus what OLMoE adds.

* The two QK-norm scales, ``attention.q_norm.scale`` and
  ``attention.k_norm.scale``, each over its whole projection.
* The rotary relabelling.  The program rotates interleaved pairs of a
  head's columns (2i, 2i+1); the reference, like the published model,
  rotates (i, i + d/2).  So within each head the reference's column i is
  the program's column 2i and its column i + d/2 the program's 2i + 1:
  one fixed permutation, applied to the columns of Wq and Wk and to the
  entries of the two scales (a norm over the whole projection and a
  query-key product are sums over those columns, blind to their order).
"""

from __future__ import annotations

import importlib.util
import os

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
    def layer(self, i: int) -> dict:
        w = super().layer(i)
        att = self.p["transformer"]["layers"]["attention"]
        d = w["wq"].shape[1] // self.nh
        for proj, norm, heads in (("wq", "q_norm", self.nh),
                                  ("wk", "k_norm", self.ng)):
            cols = rotate_half_columns(heads, d)
            w[proj] = w[proj][:, cols]
            w[norm] = self._f32(att[norm]["scale"][i])[cols]
        return w
