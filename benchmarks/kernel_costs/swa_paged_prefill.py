"""What one tail's attention behind a cached prefix needs in one layer of a
model whose layers are of two kinds: the yardstick of
``swa_prefill_roofline``.

A tail's real query rows attend, each, to every key at or before it (a full
layer) or to the ``window`` keys up to it (a window layer): ``rows`` is that
count summed over the tail's queries (the program's ``engine.prefill`` span
carries ``swa_full_rows`` and ``swa_window_rows``), and every query head does
one multiply-add per number of a row's key and one per number of its value.
``keys`` is the distinct keys behind them (``swa_full_keys``: the prompt up
to the tail's end; ``swa_window_keys``: from the first query's window on),
each read once, key and value.  Both are a layer's *mean* over the model's
layers.  Pad rows of the bucket, and the second reading of a key by a later
query tile, are the kernel's own and not counted.
"""
from __future__ import annotations

#: the kernel's instruction is named after its ``pallas_call``
PATTERNS = [r"%paged_prefill_attention(\.\d+)? = "]


def cost(rows: float, keys: float, *, heads: int, kv_heads: int,
         head_dim: int, itemsize: int = 2):
    """``(flops, bytes)`` needed by one layer's call; ``rows`` and ``keys``
    the layers' means."""
    flops = 2.0 * rows * heads * head_dim * 2
    nbytes = keys * 2.0 * kv_heads * head_dim * itemsize
    return flops, nbytes
