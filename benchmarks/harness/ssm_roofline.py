"""Bytes a decode step's state-space recurrences cannot avoid, from what
a launch's record says it worked on, and the least time a chip could
take for them.

``cfg`` is a configuration in the published config's keys
(``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``mamba_n_groups``, ``mamba_d_conv``).  A live row of one state-space
layer carries its recurrent state ``[heads, d_head, d_state]`` in float32
(4 bytes: the configuration's stated assumption) and the last
``d_conv - 1`` columns before the convolution over the ``heads * d_head
+ 2 * groups * d_state`` channels in bf16; a step reads both and writes
both, once.  The recurrence does under one operation a byte (a multiply
and an add a state element against 8 bytes moved), so bandwidth bounds
it and the operations are left out: the share reads a little low, never
high.  ``rows_live`` is the record's ``ssm_rows_live``, already summed
over the state-space layers; a program that advances every slot's state,
live or not, reads lower still.

No share of the CHUNK's scan is defined: its least time depends on the
algorithm's block (the products inside a block grow with it), which the
model leaves free.
"""

from __future__ import annotations

from typing import Dict

from . import roofline

STATE_BYTES = 4     # the recurrent state: float32


def row_bytes(cfg) -> int:
    """What one live row of one state-space layer holds: its state and
    its convolution's columns."""
    heads, d_head = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    d_state, groups = int(cfg["mamba_d_state"]), int(cfg["mamba_n_groups"])
    channels = heads * d_head + 2 * groups * d_state
    return (heads * d_head * d_state * STATE_BYTES
            + (int(cfg["mamba_d_conv"]) - 1) * channels * roofline.BYTES)


def decode_least_seconds(cfg, rows_live: int,
                         peaks: Dict[str, float]) -> float:
    """Read and written once."""
    return 2.0 * float(rows_live) * row_bytes(cfg) / peaks["hbm_bytes_per_s"]
