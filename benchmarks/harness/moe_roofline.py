"""Operations and bytes of the expert matrices of one launch of a sparse
model, from what the launch's record says it really routed, and the
least time a chip could take for them.

``cfg`` is a configuration in the published config's keys, as
``roofline.py`` reads it.  ``assignments`` is the launch's live (token,
choice) assignments summed over its layers, ``experts_touched`` the
experts that received at least one, summed over its layers
(``DispatchRecord.moe_assignments`` / ``.moe_experts_touched``).

Counted: each assignment multiplies one row with one expert's three
matrices (2 FLOPs per multiply-add); each touched expert's three
matrices are read once; every assignment's row is read and written
around both grouped matmuls (hidden in, twice the width out; the width
in, hidden out).  The experts touched are the launch's own, never
``roofline.experts_touched``'s expectation under uniform routing: a
kernel that skips the experts nobody chose would otherwise read above
100% whenever routing is more skewed than uniform.  Not counted: the
router, the sort, the gathers, the activation, the weighted sum.
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import roofline


def expert_matrices_cost(cfg, assignments: int, experts_touched: int
                         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of a launch's expert matrices."""
    per_expert = roofline.expert_params(cfg)
    flops = 2.0 * assignments * per_expert
    rows = assignments * (2 * cfg["hidden_size"]
                          + 3 * cfg["intermediate_size"])
    nbytes = (experts_touched * per_expert + rows) * roofline.BYTES
    return flops, nbytes


def least_seconds(cfg, assignments: int, experts_touched: int,
                  peaks: Dict[str, float]) -> Tuple[float, str]:
    return roofline.least_seconds(
        *expert_matrices_cost(cfg, assignments, experts_touched), peaks)
