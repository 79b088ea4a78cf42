"""Work the paged attention kernel must do for one step's live rows.

Each live row is one query token of a slot attending to that slot's
first ``length`` positions (its own included).  The count is what any
implementation needs, whatever its grid:

* operations: q·kᵀ and p·v, 2 multiply-adds each per context position
  per query head per channel: ``4 · length · H · D`` a row;
* bytes: each slot's keys and values once (a slot's rows in one step
  share them; the longest row of the slot sets how many), plus each
  row's query and output.

Grid steps past a row's length and reads of the garbage block are not
work the algorithm needs, and are not counted.
"""
from __future__ import annotations


def paged_attention(rows, heads: int, kv_heads: int, head_dim: int,
                    kv_bytes: int = 2, q_bytes: int = 2):
    """rows: [(slot, length)] of one step's live rows -> (flops, bytes)."""
    flops = sum(4 * length * heads * head_dim for _, length in rows)
    context = {}
    for slot, length in rows:
        context[slot] = max(context.get(slot, 0), length)
    kv = sum(context.values()) * 2 * kv_heads * head_dim * kv_bytes
    qo = len(rows) * 2 * heads * head_dim * q_bytes
    return flops, kv + qo
