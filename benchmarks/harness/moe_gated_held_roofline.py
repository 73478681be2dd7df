"""Operations and bytes of the GATED expert matrices of one launch of a
sparse model whose chip holds a SHARE of its experts and whose experts'
width is not its dense layers', from what the launch's record says this
chip really computed, and the least time a chip could take for them.

``cfg`` is a configuration in the published config's keys
(``hidden_size``; ``moe_intermediate_size``: an expert's width, which a
config with leading dense layers publishes beside their
``intermediate_size``).  ``assignments_held`` is the launch's live
(token, choice) assignments that fell on an expert this chip holds,
``experts_touched`` the HELD experts that received at least one, both
summed over the sparse layers (``DispatchRecord.moe_assignments_held`` /
``.moe_experts_touched_held``).

Counted, and only what no implementation could avoid: a gated expert is
THREE matrices (gate and up ``[hidden, width]``, down ``[width,
hidden]``) at ``moe_intermediate_size``; each held assignment multiplies
one row with all three (2 FLOPs per multiply-add); each touched held
expert's three matrices are read once; every held assignment's row is
read in and written out once at the hidden width.  Not counted: the rows
of the width between the matrices (a fused kernel would keep them on the
chip), the router, the sort, the gathers, the activation, the weighted
sum, the shared expert.  So the share can only read low.
``moe_roofline.py`` counts an expert at ``intermediate_size`` (the dense
layers' 6,144 here) and every routed expert as held, and
``moe_ungated_roofline.py`` two matrices an expert, which is why this
model's cell is listed in neither.
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import roofline


def expert_params(cfg) -> int:
    """One gated expert: three matrices at the experts' own width."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def expert_matrices_cost(cfg, assignments_held: int, experts_touched: int
                         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of a launch's held expert matrices."""
    per_expert = expert_params(cfg)
    flops = 2.0 * assignments_held * per_expert
    rows = assignments_held * 2 * int(cfg["hidden_size"])
    return flops, (experts_touched * per_expert + rows) * roofline.BYTES


def least_seconds(cfg, assignments_held: int, experts_touched: int,
                  peaks: Dict[str, float]) -> Tuple[float, str]:
    return roofline.least_seconds(
        *expert_matrices_cost(cfg, assignments_held, experts_touched), peaks)
