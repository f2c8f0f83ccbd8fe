"""fused_linear_cross_entropy: value + grad equivalence with the unfused
logits path (reference contract: c_softmax_with_cross_entropy ≡ matmul +
softmax_with_cross_entropy)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops.fused import fused_linear_cross_entropy


def _naive_loss(h, w, labels, ignore_index=-100, loss_mask=None):
    logits = h.matmul(w.t())
    loss = F.cross_entropy(
        logits.reshape([-1, logits.shape[-1]]).astype("float32"),
        labels.reshape([-1]), ignore_index=ignore_index, reduction="none")
    if loss_mask is not None:
        m = loss_mask.reshape([-1]).astype("float32")
        return (loss * m).sum() / m.sum().clip(min=1.0)
    valid = (labels.reshape([-1]) != ignore_index).astype("float32")
    return (loss * valid).sum() / valid.sum().clip(min=1.0)


class TestFusedLinearCrossEntropy:
    def _setup(self, N=6, S=7, H=16, V=37, seed=0):
        rs = np.random.RandomState(seed)
        h = paddle.to_tensor(rs.randn(N, S, H).astype(np.float32))
        w = paddle.to_tensor(0.1 * rs.randn(V, H).astype(np.float32))
        y = paddle.to_tensor(rs.randint(0, V, (N, S)).astype(np.int64))
        h.stop_gradient = False
        w.stop_gradient = False
        return h, w, y

    def test_matches_naive_value_and_grads(self):
        h, w, y = self._setup()
        loss = fused_linear_cross_entropy(h, w, y, block_size=16)
        loss.backward()
        gh, gw = np.asarray(h.grad), np.asarray(w.grad)

        h2, w2, _ = self._setup()
        ref = _naive_loss(h2, w2, y)
        ref.backward()
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
        np.testing.assert_allclose(gh, np.asarray(h2.grad), atol=1e-5)
        np.testing.assert_allclose(gw, np.asarray(w2.grad), atol=1e-5)

    def test_ignore_index(self):
        h, w, y = self._setup()
        yn = np.array(y.numpy())
        yn[0, :4] = -100
        y = paddle.to_tensor(yn)
        loss = fused_linear_cross_entropy(h, w, y, block_size=8)
        loss.backward()
        h2, w2, _ = self._setup()
        ref = _naive_loss(h2, w2, y)
        ref.backward()
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(h.grad), np.asarray(h2.grad),
                                   atol=1e-5)
        # ignored rows get exactly zero hidden-grad
        np.testing.assert_array_equal(np.asarray(h.grad)[0, :4], 0.0)

    def test_loss_mask(self):
        h, w, y = self._setup()
        m = paddle.to_tensor(
            (np.arange(6 * 7).reshape(6, 7) % 3 != 0).astype(np.float32))
        loss = fused_linear_cross_entropy(h, w, y, loss_mask=m, block_size=64)
        h2, w2, _ = self._setup()
        ref = _naive_loss(h2, w2, y, loss_mask=m)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)

    def test_transpose_weight_layout(self):
        h, w, y = self._setup()
        wt = paddle.to_tensor(np.asarray(w.numpy()).T.copy())
        wt.stop_gradient = False
        loss = fused_linear_cross_entropy(h, wt, y, transpose_weight=True,
                                          block_size=16)
        ref = _naive_loss(*self._setup()[:2], y)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)

    def test_bf16_close_to_f32(self):
        h, w, y = self._setup(H=32, V=64)
        hb = h.astype("bfloat16")
        hb.stop_gradient = False
        loss = fused_linear_cross_entropy(hb, w, y, block_size=32)
        ref = _naive_loss(*self._setup(H=32, V=64)[:2], y)
        assert abs(float(loss) - float(ref)) / float(ref) < 0.02

    def test_under_jit(self):
        h, w, y = self._setup()

        @paddle.jit.to_static
        def f(h, w, y):
            return fused_linear_cross_entropy(h, w, y, block_size=16)

        ref = _naive_loss(*self._setup()[:2], y)
        np.testing.assert_allclose(float(f(h, w, y)), float(ref), rtol=1e-5)

    def test_model_compute_loss_matches_criterion(self):
        from paddle_tpu.models import (
            gpt_tiny, GPTForCausalLM, GPTPretrainingCriterion)

        paddle.seed(0)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion()
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (2, 8)))
        y = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (2, 8)))
        ref = crit(model(x), y)
        fused = model.compute_loss(x, y)
        np.testing.assert_allclose(float(fused), float(ref), rtol=2e-4)


# -- the TPU forward's kernel, interpreted ------------------------------------

#: ``(rows as [B, S], vocab)`` and what else a case turns: a grid step of the
#: kernel is 128 rows x 128 vocabulary rows in these tests, walked 64 at a time
KERNEL_CASES = {
    "float32": dict(),
    "bfloat16": dict(dtype="bfloat16"),
    "vocab_no_multiple_of_the_tile": dict(V=300),
    "vocab_smaller_than_one_tile": dict(V=37),
    "rows_no_multiple_of_tm": dict(B=5, S=64, V=300),
    "label_in_the_last_ragged_block": dict(V=300, labels_from=256),
    "ignore_index_rows": dict(V=300, ignored=True),
    "loss_mask": dict(V=300, masked=True),
    "transpose_weight": dict(V=300, transpose_weight=True),
    "data_mesh_of_four": dict(B=8, S=64, V=300, dp=4),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_forward_kernel_matches_the_scan_and_the_unfused_loss(
        case, monkeypatch):
    """The streamed CE's forward kernel (``ops/pallas/streamed_ce_kernel``,
    the TPU's path) run by the Pallas interpreter: its ``lse`` and ``picked``
    against the scan's, and the loss and both gradients it gives through the
    public op against the scan's and against ``cross_entropy(h @ w.T)``.  One
    case runs it per shard of a four-device data mesh."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.ops import fused
    from paddle_tpu.ops.pallas import (streamed_ce_kernel,
                                       streamed_ce_stats_on_mesh)

    c = dict(B=2, S=128, H=32, V=256, dtype="float32", labels_from=0,
             ignored=False, masked=False, transpose_weight=False, dp=1)
    c.update(KERNEL_CASES[case])
    B, S, H, V = c["B"], c["S"], c["H"], c["V"]
    for name, rows in (("ROW_TILE", 128), ("VOCAB_TILE", 128),
                       ("VOCAB_SUB", 64)):
        monkeypatch.setattr(streamed_ce_kernel, name, rows)
    tm, tn, sub = streamed_ce_kernel.plan(B * S, H, V, 4)
    assert (tm, sub) == (128, 64 if V >= 128 else V) and tn == min(128, V)
    rs = np.random.RandomState(0)
    h0 = rs.randn(B, S, H).astype(np.float32)
    w0 = (0.1 * rs.randn(V, H)).astype(np.float32)
    y0 = rs.randint(c["labels_from"], V, (B, S)).astype(np.int64)
    y0[0, 0], y0[0, 1] = V - 1, c["labels_from"]
    if c["ignored"]:
        y0[1, :40] = -100
    mask = (np.arange(B * S).reshape(B, S) % 3 != 0).astype(np.float32) \
        if c["masked"] else None
    bf16 = c["dtype"] == "bfloat16"

    if c["dp"] > 1:
        monkeypatch.setattr(mesh_mod, "_global_mesh", mesh_mod.hybrid_mesh(
            dp=c["dp"], devices=jax.devices()[:c["dp"]]))

    # the statistics themselves
    cdt = jnp.bfloat16 if bf16 else jnp.float32
    hc = jnp.asarray(h0.reshape(B * S, H)).astype(cdt)
    wc = jnp.asarray(w0).astype(cdt)
    lbl = jnp.asarray(np.clip(y0, 0, V - 1).reshape(-1), jnp.int32)
    on_mesh = jax.jit(lambda h, w, y: streamed_ce_stats_on_mesh(
        h, w, y, interpret=True))
    if c["dp"] > 1:
        assert "manual" in on_mesh.lower(hc, wc, lbl).as_text().lower()
    lse, picked = on_mesh(hc, wc, lbl)
    want_lse, want_picked = fused._scan_stats(hc, wc, lbl, 64)
    np.testing.assert_allclose(lse, want_lse, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(picked, want_picked, rtol=2e-6, atol=2e-6)
    if c["dp"] > 1:     # and against one device's call of the kernel
        one = streamed_ce_kernel.streamed_ce_stats(hc, wc, lbl,
                                                   interpret=True)
        np.testing.assert_array_equal(lse, one[0])
        np.testing.assert_array_equal(picked, one[1])

    # the loss and both gradients through the public op
    def run(loss_of):
        h = paddle.to_tensor(h0)
        w = paddle.to_tensor(w0.T.copy() if c["transpose_weight"] else w0)
        h.stop_gradient = w.stop_gradient = False
        m = None if mask is None else paddle.to_tensor(mask)
        loss = loss_of(h.astype("bfloat16") if bf16 else h, w,
                       paddle.to_tensor(y0), m)
        loss.backward()
        return float(loss), np.asarray(h.grad), np.asarray(w.grad)

    def fused_loss(interpret):
        def loss_of(h, w, y, m):
            monkeypatch.setattr(fused, "_forward_interpret",
                                lambda *a: interpret)
            return fused_linear_cross_entropy(
                h, w, y, loss_mask=m, block_size=64,
                transpose_weight=c["transpose_weight"])
        return loss_of

    def naive(h, w, y, m):
        return _naive_loss(h.astype("float32"),
                           w.t() if c["transpose_weight"] else w, y,
                           loss_mask=m)

    kernel, scan, plain = run(fused_loss(True)), run(fused_loss(None)), \
        run(naive)
    # against the scan: the same operands, only the sums' order differs
    np.testing.assert_allclose(kernel[0], scan[0], rtol=1e-6)
    np.testing.assert_allclose(kernel[1], scan[1], atol=1e-7)
    np.testing.assert_allclose(kernel[2], scan[2], atol=1e-6)
    tol = dict(rtol=2e-2, atol=2e-3) if bf16 else dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(kernel[0], plain[0], rtol=tol["rtol"])
    np.testing.assert_allclose(kernel[1], plain[1], **tol)
    np.testing.assert_allclose(kernel[2], plain[2], **tol)
    if c["ignored"]:
        np.testing.assert_array_equal(kernel[1][1, :40], 0.0)
