"""What one tail's gated delta rule needs in one KDA layer: the yardstick of
``kda_prefill_roofline``.

A real tail token costs a head three products with its ``[D, D]`` state (the
prediction ``S^T k``, the rank-one write, the output ``S^T q``): what the
recurrence itself needs, whatever the size of the chunks a kernel scans it
in; ``q``, ``k``, ``v`` and the gate ``g`` are read and ``o`` written once a
token a head in float32 (the precision the configurations state for the
recurrence's operands), the state once in and once out a head.  A chunked
form's score matrices, its forward substitution, the pad rows of the bucket
and of a tail's last chunk and the snapshots are the kernel's own and not
counted.
"""
from __future__ import annotations

#: the kernel's instruction is named after its ``pallas_call``
PATTERNS = [r"%kda_chunk_prefill(\.\d+)? = "]


def cost(tail_tokens: float, *, heads: int, dim: int):
    """``(flops, bytes)`` needed by one layer's call over a tail of
    ``tail_tokens`` real tokens."""
    flops = 2.0 * heads * tail_tokens * 3 * dim * dim
    nbytes = 4.0 * heads * (tail_tokens * 5 * dim + 2 * dim * dim)
    return flops, nbytes
