"""What one decode step's attention over the selected tokens needs in one
layer: the yardstick of ``dsa_decode_roofline``.

For the slots running in a step, with ``selected`` tokens selected among
them: each selected token's K row and V row are read once (``kv_heads x
head_dim`` numbers a side), and every query head does one multiply-add per
number of its KV head's key for the score and one per value number for the
weighted sum.  The bytes are those of the *selected rows*, not of the
context; queries and outputs are under 1 % and left out.  Bound by bytes on
every chip in the peaks table (8 query heads share each byte: 8 operations a
byte against the v5e's 240).
"""
from __future__ import annotations

#: the kernel's instruction is named after its ``pallas_call``
PATTERNS = [r"%dsa_sparse_decode(\.\d+)? = "]


def cost(selected: float, *, heads: int, kv_heads: int, head_dim: int,
         itemsize: int = 2):
    """``(flops, bytes)`` needed by one layer's call."""
    flops = 2.0 * selected * heads * head_dim * 2
    nbytes = selected * 2.0 * kv_heads * head_dim * itemsize
    return flops, nbytes
