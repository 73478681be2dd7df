"""Operations and bytes of the UNGATED expert matrices of one launch of a
sparse model whose chip holds a SHARE of its experts, from what the
launch's record says this chip really computed, and the least time a
chip could take for them.

``cfg`` is a configuration in the published config's keys
(``hidden_size``, ``intermediate_size``: an expert's width, as
published).  ``assignments_held`` is the launch's live (token, choice)
assignments that fell on an expert this chip holds, ``experts_touched``
the HELD experts that received at least one, both summed over the
expert layers (``DispatchRecord.moe_assignments_held`` /
``.moe_experts_touched_held``).

Counted, and only what no implementation could avoid: an ungated expert
is TWO matrices ``[hidden, width]`` and ``[width, hidden]`` (no third:
nothing is gated), at the PUBLISHED width (1856; a layout that holds
the first at 1920 reads more and is not owed it); each held assignment
multiplies one row with both (2 FLOPs per multiply-add); each touched
held expert's two matrices are read once; every held assignment's row
is read in and written out once at the hidden width.  Not counted: the
row of the width between the two matrices (a fused kernel would keep it
on the chip), the router, the sort, the gathers, the activation, the
weighted sum, the shared MLP.  So the share can only read low.
``moe_roofline.py`` counts three matrices an expert and every routed
expert as held, which is why this model's cell is not listed there.
"""

from __future__ import annotations

from typing import Dict, Tuple

from . import roofline


def expert_params(cfg) -> int:
    """One ungated expert: two matrices."""
    return 2 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


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
