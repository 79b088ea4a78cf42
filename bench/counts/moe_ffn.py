"""Work the ragged expert FFN kernel must do in one layer of one step.

* operations: each routed choice of a live row goes through its
  expert's ``n_mats`` matrices of ``d × d_ff``: ``2 · d · d_ff · n_mats``
  a choice.  Rows that only pad an expert's segment are not counted.
* bytes: the weights of every expert that received at least one row,
  once, in the kernel's operand type, plus each choice's input row and
  output row.
"""
from __future__ import annotations


def ragged_ffn(choices: int, experts_hit: int, d_model: int, d_ff: int,
               n_mats: int, w_bytes: int = 2, x_bytes: int = 2):
    flops = 2 * choices * d_model * d_ff * n_mats
    nbytes = experts_hit * n_mats * d_model * d_ff * w_bytes
    nbytes += 2 * choices * d_model * x_bytes
    return flops, nbytes
