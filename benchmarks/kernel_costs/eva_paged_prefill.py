"""What one tail's attention over its windows and the summaries before them
needs in one layer: the yardstick of ``eva_prefill_roofline``.

A tail's real query rows attend, each, to the exact keys of its own window at
or before it and to the summary rows of every earlier window: ``rows`` is
that count summed over the tail's queries (the program's ``engine.prefill``
span carries it as ``eva_rows``), and every query head does one multiply-add
per number of a row's key and one per number of its value.  ``keys`` is the
distinct rows behind them (the exact keys of the windows the tail touches up
to its end, and the summary rows of the windows before its last:
``eva_keys``), each read once, key and value.  Pad rows of the bucket, and
the second reading of a key by a later query tile, are the kernel's own and
not counted.  Bound by operations at the cell's tails (a 512-byte tail
behind 28k bytes: 1.8 M rows, 29 GFLOP against 60 MB a layer).
"""
from __future__ import annotations

#: the kernel's instruction is named after its ``pallas_call``
PATTERNS = [r"%eva_paged_prefill(\.\d+)? = "]


def cost(rows: float, keys: float, *, heads: int, kv_heads: int,
         head_dim: int, itemsize: int = 2):
    """``(flops, bytes)`` needed by one layer's call."""
    flops = 2.0 * rows * heads * head_dim * 2
    nbytes = keys * 2.0 * kv_heads * head_dim * itemsize
    return flops, nbytes
