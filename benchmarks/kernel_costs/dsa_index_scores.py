"""What one decode step's index scores need in one layer: the yardstick of
``dsa_index_roofline``.

For the slots running in a step, with ``context`` cached tokens among them:
every cached indexer key is read once, at the width it is *stored* in
(``stored_width`` numbers: the 64-wide key lies in a whole 128-lane row, and
the kernel copies whole rows, so the pad lanes are bytes it needs as the pool
is), and every indexer head does one multiply-add per number of the key's own
``width``, then a relu, a weight and a sum.  Queries, weights and the
``[slots, T]`` float32 scores written are counted too (the scores are 4 bytes
a token against the key's 256).  Bound by bytes on every chip in the peaks
table (16 heads share each key: 8 operations a byte against the v5e's 240).
"""
from __future__ import annotations

#: the decode program's call: one query a slot (``f32[slots, 1, T]``); the
#: prefill program's calls have a group's rows there
PATTERNS = [r"%dsa_index_scores(\.\d+)? = f32\[\d+,1,\d+\]"]


def cost(context: float, *, heads: int, width: int, stored_width: int,
         itemsize: int = 2):
    """``(flops, bytes)`` needed by one layer's call."""
    flops = 2.0 * context * heads * width + 3.0 * context * heads
    nbytes = context * (stored_width * itemsize + 4.0)
    return flops, nbytes
