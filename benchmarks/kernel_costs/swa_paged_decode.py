"""What one decode step's attention needs in one layer of a model whose
layers are of two kinds — some read a slot's whole sequence, the others the
``window`` keys up to the query: the yardstick of ``swa_decode_roofline``.

For the slots running in a step, ``full_rows`` is the keys a full layer must
read (each slot's every token and the new one) and ``window_rows`` the keys a
window layer must (the last ``window`` of them), summed over the slots: each
key and value is read once (``kv_heads x head_dim`` numbers a side, in the
pool's dtype), and every query head does one multiply-add per number of its
KV head's key for the score and one per value number for the weighted sum.
The cost is a layer's *mean* over the model's layers, so that times the
layers it is the step's; it counts what must be read whatever implements it
(a kernel that reads the whole context on a window layer gains nothing
here).  Queries and outputs are under 1 % and left out.  Bound by bytes on
every chip in the peaks table.
"""
from __future__ import annotations

#: the kernel's instruction is named after its ``pallas_call``
PATTERNS = [r"%paged_decode_attention(\.\d+)? = "]


def mean_rows(full: float, window: float, *, layers: int,
              full_layers: int) -> float:
    """A layer's mean of the two kinds' counts over the model's layers."""
    return (full_layers * full + (layers - full_layers) * window) / layers


def cost(full_rows: float, window_rows: float, *, layers: int,
         full_layers: int, heads: int, kv_heads: int, head_dim: int,
         itemsize: int = 2):
    """``(flops, bytes)`` needed by one layer's call, the layers' mean."""
    rows = mean_rows(full_rows, window_rows, layers=layers,
                     full_layers=full_layers)
    flops = 2.0 * rows * heads * head_dim * 2
    nbytes = rows * 2.0 * kv_heads * head_dim * itemsize
    return flops, nbytes
