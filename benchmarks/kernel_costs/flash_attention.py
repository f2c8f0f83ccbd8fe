"""What causal flash attention needs, forward and backward, for one call at
``[batch, heads, seq, head_dim]``: the yardstick of ``flash_roofline``.

One matmul of the causal half is ``batch * heads * seq^2 * head_dim`` FLOPs
(2 per multiply-add over half of ``seq x seq``).  The forward needs two (QK^T,
PV); the backward five (QK^T again, dP, dV, dK, dQ).  The program splits its
backward over two kernels that both recompute QK^T and dP; what is done twice
is not needed twice, so the backward pair is charged five together.  Bytes:
forward reads Q, K, V and writes O; backward reads Q, K, V, O, dO and writes
dQ, dK, dV — every one once, in the kernel's dtype (log-sum-exp rows are
under 1% and left out).
"""
from __future__ import annotations

#: matched on a device event's text.  The program wraps its flash kernels in
#: ``jax.named_scope("attention.pallas_flash")``; in a v5e trace the forward
#: custom call is ``%jvp_attention.pallas_flash_.N`` (``%attention...`` when
#: nothing is differentiated) and each backward kernel
#: ``%transpose_jvp_attention.pallas_flash__.N`` (looked at by hand, PR 23)
FORWARD = [r"^%?(jvp_)?attention\.pallas_flash\S* = .*custom-call"]
BACKWARD = [r"^%?transpose_jvp_attention\.pallas_flash\S* = .*custom-call"]
PATTERNS = FORWARD + BACKWARD


def unit(batch: int, heads: int, seq: int, head_dim: int) -> float:
    return float(batch) * heads * seq * seq * head_dim


def cost(n_forward: int, n_backward_kernels: int, *, batch: int, heads: int,
         seq: int, head_dim: int, itemsize: int = 2):
    """``(flops, bytes)`` needed by ``n_forward`` forward calls and
    ``n_backward_kernels`` backward kernel launches (two to a call)."""
    u = unit(batch, heads, seq, head_dim)
    tensor = float(batch) * heads * seq * head_dim * itemsize
    n_bwd = n_backward_kernels / 2.0
    return (n_forward * 2 * u + n_bwd * 5 * u,
            n_forward * 4 * tensor + n_bwd * 8 * tensor)
