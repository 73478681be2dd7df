"""Bytes a decode step's power-retention recurrences cannot avoid, from
what a launch's record says it worked on, and the least time a chip
could take for them.

``cfg`` is a configuration in the published config's keys
(``num_key_value_heads``, ``head_dim``) with the program's stated layout
of ``phi`` under ``bytes.phi_rows`` (8,320 rows at a head size of 128:
``assumed.phi_layout``).  A live row of one retention layer carries, a
key-value head, its state ``S`` ``[phi_rows, head_dim]`` and its
normaliser ``z`` ``[phi_rows]`` in float32 (4 bytes: the configuration's
stated assumption); a step reads both and writes both, once.  The
recurrence does two operations a state element a query head against 8
bytes moved, so bandwidth bounds it and the operations are left out: the
share reads a little low, never high.  ``rows_live`` is the record's
``retention_rows_live``, already summed over the retention layers; a
program that advances every slot's state, live or not, reads lower
still.

No share of the CHUNK is defined, for ``ssm_roofline.py``'s reason: its
least time depends on the algorithm's block (the quadratic form inside a
block grows with it), which the model leaves free.
"""

from __future__ import annotations

from typing import Dict

STATE_BYTES = 4     # the recurrent state: float32


def row_bytes(cfg) -> int:
    """What one live row of one retention layer holds: ``S`` and ``z`` of
    every key-value head."""
    heads, d = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    rows = int(cfg["bytes"]["phi_rows"])
    return heads * (rows * d + rows) * STATE_BYTES


def decode_least_seconds(cfg, rows_live: int,
                         peaks: Dict[str, float]) -> float:
    """Read and written once."""
    return 2.0 * float(rows_live) * row_bytes(cfg) / peaks["hbm_bytes_per_s"]
