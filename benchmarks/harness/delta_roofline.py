"""Bytes a decode step's gated delta-rule recurrences cannot avoid, from
what a launch's record says it worked on, and the least time a chip
could take for them.

``cfg`` is a configuration in the published config's keys
(``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``).  A live row of one delta layer carries, a
value head, its state ``S`` ``[d_key, d_value]`` in float32 (4 bytes: the
configuration's stated assumption); a step reads it and writes it, once
(2,097,152 B a row a layer at 32 heads of 128 x 128).  The recurrence
does some six operations a state element against 8 bytes moved, so
bandwidth bounds it and the operations are left out, and so are the
convolution's three columns (a hundredth of the state): the share reads
a little low, never high.  ``rows_live`` is the record's
``delta_rows_live``, already summed over the delta layers; a program
that advances every slot's state, live or not, reads lower still.

No share of the CHUNK is defined, for ``ssm_roofline.py``'s reason: its
least time depends on the algorithm's block (the triangular system
grows with it), which the model leaves free.
"""

from __future__ import annotations

from typing import Dict

STATE_BYTES = 4     # the recurrent state: float32


def row_bytes(cfg) -> int:
    """What one live row of one delta layer holds: ``S`` of every value
    head."""
    return (int(cfg["linear_num_value_heads"])
            * int(cfg["linear_key_head_dim"])
            * int(cfg["linear_value_head_dim"]) * STATE_BYTES)


def decode_least_seconds(cfg, rows_live: int,
                         peaks: Dict[str, float]) -> float:
    """Read and written once."""
    return 2.0 * float(rows_live) * row_bytes(cfg) / peaks["hbm_bytes_per_s"]
