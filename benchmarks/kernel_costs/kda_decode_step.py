"""What one decode step's gated delta rule needs: the yardstick of
``kda_decode_roofline``.

``state_bytes`` is what the program's ``engine.step`` span says the step's KDA
kernels must move: every running slot's float32 state, once in and once out,
in every KDA layer (running slots x layers x 2 x heads x D x D x 4 B) — of
the running slots only: a kernel that also touches idle slots' state takes
longer for the same count and reads low.  A number of the state is multiplied
three times (the decay, the prediction and the write count as one each, the
output one): bound by bytes on every chip of the peaks table.  The token's
``q``, ``k``, ``v`` and gates are under 1 % and left out.
"""
from __future__ import annotations

#: the kernel's instruction is named after its ``pallas_call``
PATTERNS = [r"%kda_decode_step(\.\d+)? = "]


def cost(state_bytes: float):
    """``(flops, bytes)`` needed by a step's calls, all layers."""
    numbers = state_bytes / (2 * 4)
    return 2.0 * 3 * numbers, float(state_bytes)
