"""What one expert layer's two grouped products (gate|up, then down) need:
the yardstick of ``moe_experts_roofline``.

With ``assignments`` token-to-held-expert assignments spread over
``touched`` held experts: each touched expert's three matrices are read once
(``3 * hidden * ffn`` numbers; an expert that got no token is not read), each
assignment's row is read and written once per product, and each assignment
does ``3 * hidden * ffn`` multiply-adds.  At decode (about one assignment a
token) the weights are nearly all the bytes and the layer is bound by them.
"""
from __future__ import annotations

#: the kernel's instruction is named after its ``pallas_call``
PATTERNS = [r"%moe_grouped_matmul(\.\d+)? = "]


def patterns_for_rows(rows: int):
    """The calls whose output has ``rows`` rows: a decode step's are
    ``slots * top_k`` rows, a prefill's ``bucket * top_k``."""
    return [rf"%moe_grouped_matmul(\.\d+)? = \w+\[{int(rows)},"]


def cost(assignments: float, touched: float, *, hidden: int, ffn: int,
         itemsize: int = 2):
    """``(flops, bytes)`` of one layer's two calls together."""
    flops = 2.0 * assignments * 3 * hidden * ffn
    weights = touched * 3.0 * hidden * ffn * itemsize
    rows = assignments * (2.0 * hidden + 3.0 * ffn) * itemsize
    return flops, weights + rows
