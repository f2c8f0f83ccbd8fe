"""What one decode step's attention over a window and its summaries needs in
one layer: the yardstick of ``eva_decode_roofline``.

For the slots running in a step, with ``exact_rows`` exact positions (each
slot's place in its window, the new token included) and ``summary_rows``
summary rows (128 a window passed) among them: each row's key and value are
read once (``kv_heads x head_dim`` numbers a side, in the pool's dtype), and
every query head does one multiply-add per number of its KV head's key for
the score and one per value number for the weighted sum.  The bytes are those
of the rows a slot attends to, not of its context (a summary row stands for
16 positions); queries and outputs are under 1 % and left out.  Bound by
bytes on every chip in the peaks table (one query head a KV head: 2
operations a byte against the v5e's 240).
"""
from __future__ import annotations

#: the kernel's instruction is named after its ``pallas_call``
PATTERNS = [r"%eva_paged_decode(\.\d+)? = "]


def cost(exact_rows: float, summary_rows: float, *, heads: int,
         kv_heads: int, head_dim: int, itemsize: int = 2):
    """``(flops, bytes)`` needed by one layer's call."""
    rows = exact_rows + summary_rows
    flops = 2.0 * rows * heads * head_dim * 2
    nbytes = rows * 2.0 * kv_heads * head_dim * itemsize
    return flops, nbytes
