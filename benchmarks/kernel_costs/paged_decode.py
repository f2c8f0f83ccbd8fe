"""What one decode step's paged attention needs in one layer: the yardstick
of ``paged_decode_roofline``.

For the slots running in a step, with ``kv_tokens`` cached tokens among them:
every cached key and value is read once (``kv_tokens * kv_heads * head_dim``
elements each, in the cache's dtype) and every query head does one
multiply-add per cached element of its group for the scores and one for the
weighted sum.  The bytes are those of the *live lengths*, not of the pool nor
of whole blocks; queries and outputs are under 1% and left out.  Decode
attention is bound by bytes on every chip in the peaks table.
"""
from __future__ import annotations

#: matched on a device event's text.  The program's paged kernels carry no
#: named scope: in a v5e trace both are ``%program.N = ... custom-call(...)``
#: with ``custom_call_target="tpu_custom_call"`` (looked at by hand, PR 23).
#: The decode kernel is the one whose scalar-prefetch operands are the 2-D
#: block table and the 1-D lengths; the prefill kernel's are 1-D both.
PATTERNS = [r"custom-call\(s32\[\d+,\d+\]\S* %\S+ s32\[\d+\]\S* %\S+ "
            r".*tpu_custom_call"]


def cost(kv_tokens: float, *, heads: int, kv_heads: int, head_dim: int,
         itemsize: int = 2):
    """``(flops, bytes)`` needed by one layer's call."""
    flops = 4.0 * kv_tokens * heads * head_dim
    nbytes = 2.0 * kv_tokens * kv_heads * head_dim * itemsize
    return flops, nbytes
