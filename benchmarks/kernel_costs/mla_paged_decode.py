"""What one decode step's latent attention needs in one layer: the yardstick
of ``mla_decode_roofline``.

For the slots running in a step, with ``kv_tokens`` cached tokens among them:
every cached latent vector is read once — ``width`` numbers a token
(``kv_lora_rank + qk_rope_head_dim``; the pool stores them in whole 128-lane
rows, and the pad lanes are not counted as needed) — and every query head
does one multiply-add per number of it for the score and one per value number
(the first ``dv`` of the same vector) for the weighted sum.  The bytes are
those of the *live lengths*, not of the pool, of whole blocks or of whole
chunks; queries and outputs are under 1% and left out.  Bound by bytes on
every chip in the peaks table (32 heads share each byte: 60 operations a
byte against the v5e's 240).
"""
from __future__ import annotations

#: the kernel's instruction is named after its ``pallas_call``
PATTERNS = [r"%mla_paged_decode(\.\d+)? = "]


def cost(kv_tokens: float, *, heads: int, width: int, dv: int,
         itemsize: int = 2):
    """``(flops, bytes)`` needed by one layer's call."""
    flops = 2.0 * kv_tokens * heads * (width + dv)
    nbytes = 1.0 * kv_tokens * width * itemsize
    return flops, nbytes
