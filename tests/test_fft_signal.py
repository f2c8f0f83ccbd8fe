"""paddle.fft + paddle.signal vs numpy oracles; regularizer/hub/version
surface tests."""
import math
import os

import numpy as np
import pytest

import paddle_tpu as paddle


def _np(t):
    return np.asarray(t.numpy())


class TestFFT1D:
    x = np.random.RandomState(0).randn(3, 16).astype(np.float32)

    @pytest.mark.parametrize("norm", ["backward", "ortho", "forward"])
    def test_fft_ifft_roundtrip(self, norm):
        X = paddle.fft.fft(paddle.to_tensor(self.x), norm=norm)
        np.testing.assert_allclose(
            _np(X), np.fft.fft(self.x, norm=norm), rtol=1e-4, atol=1e-4)
        back = paddle.fft.ifft(X, norm=norm)
        np.testing.assert_allclose(_np(back).real, self.x, rtol=1e-4,
                                   atol=1e-4)

    def test_rfft_irfft(self):
        X = paddle.fft.rfft(paddle.to_tensor(self.x))
        np.testing.assert_allclose(_np(X), np.fft.rfft(self.x),
                                   rtol=1e-4, atol=1e-4)
        back = paddle.fft.irfft(X, n=16)
        np.testing.assert_allclose(_np(back), self.x, rtol=1e-4, atol=1e-4)

    def test_hfft_ihfft(self):
        spec = np.fft.rfft(self.x)  # hermitian half
        got = paddle.fft.hfft(paddle.to_tensor(spec.astype(np.complex64)))
        np.testing.assert_allclose(_np(got), np.fft.hfft(spec),
                                   rtol=1e-3, atol=1e-3)
        ih = paddle.fft.ihfft(paddle.to_tensor(self.x))
        np.testing.assert_allclose(_np(ih), np.fft.ihfft(self.x),
                                   rtol=1e-4, atol=1e-4)

    def test_n_and_axis(self):
        X = paddle.fft.fft(paddle.to_tensor(self.x), n=8, axis=0)
        np.testing.assert_allclose(_np(X), np.fft.fft(self.x, n=8, axis=0),
                                   rtol=1e-4, atol=1e-4)

    def test_bad_norm(self):
        with pytest.raises(ValueError):
            paddle.fft.fft(paddle.to_tensor(self.x), norm="bogus")


class TestFFTND:
    x = np.random.RandomState(1).randn(2, 8, 12).astype(np.float32)

    def test_fft2_ifft2(self):
        X = paddle.fft.fft2(paddle.to_tensor(self.x))
        np.testing.assert_allclose(_np(X), np.fft.fft2(self.x),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(
            _np(paddle.fft.ifft2(X)).real, self.x, rtol=1e-4, atol=1e-4)

    def test_rfftn_irfftn(self):
        X = paddle.fft.rfftn(paddle.to_tensor(self.x))
        np.testing.assert_allclose(_np(X), np.fft.rfftn(self.x),
                                   rtol=1e-3, atol=1e-3)
        back = paddle.fft.irfftn(X, s=self.x.shape)
        np.testing.assert_allclose(_np(back), self.x, rtol=1e-3, atol=1e-4)

    def test_hfftn_matches_explicit_extension(self):
        # oracle: hermitian-extend the last axis then full fftn, real part
        spec = np.fft.rfftn(self.x)          # [2, 8, 7] one-sided
        got = _np(paddle.fft.hfftn(
            paddle.to_tensor(spec.astype(np.complex64))))
        n = 2 * (spec.shape[-1] - 1)
        # rebuild full spectrum along last axis
        tail = np.conj(spec[..., 1:-1][..., ::-1])
        full = np.concatenate([spec, tail], axis=-1)
        expect = np.fft.fftn(full, axes=(0, 1, 2)).real
        np.testing.assert_allclose(got, expect, rtol=1e-2, atol=1e-2)

    def test_ihfftn_line_equivalence(self):
        # each last-axis line must match np.fft.ihfft; other axes inverse
        x1 = self.x[0, 0]
        got = _np(paddle.fft.ihfftn(paddle.to_tensor(x1)))
        np.testing.assert_allclose(got, np.fft.ihfft(x1), rtol=1e-4,
                                   atol=1e-5)

    def test_freq_shift_helpers(self):
        np.testing.assert_allclose(_np(paddle.fft.fftfreq(10, 0.5)),
                                   np.fft.fftfreq(10, 0.5), rtol=1e-6)
        np.testing.assert_allclose(_np(paddle.fft.rfftfreq(10, 0.5)),
                                   np.fft.rfftfreq(10, 0.5), rtol=1e-6)
        a = np.arange(10.0)
        np.testing.assert_allclose(
            _np(paddle.fft.fftshift(paddle.to_tensor(a))), np.fft.fftshift(a))
        np.testing.assert_allclose(
            _np(paddle.fft.ifftshift(paddle.to_tensor(a))),
            np.fft.ifftshift(a))

    def test_fft_grad(self):
        t = paddle.to_tensor(self.x, stop_gradient=False)
        out = paddle.fft.rfft(t)
        # |X|^2 energy — real scalar loss through the complex op
        loss = (paddle.real(out) ** 2 + paddle.imag(out) ** 2).sum()
        loss.backward()
        assert t.grad is not None
        g = _np(t.grad)
        assert g.shape == self.x.shape and np.isfinite(g).all()

    def test_complex_ops(self):
        z = np.array([1 + 2j, 3 - 4j], dtype=np.complex64)
        t = paddle.to_tensor(z)
        np.testing.assert_allclose(_np(paddle.real(t)), z.real)
        np.testing.assert_allclose(_np(t.imag()), z.imag)
        np.testing.assert_allclose(_np(paddle.conj(t)), z.conj())
        np.testing.assert_allclose(_np(paddle.angle(t)), np.angle(z),
                                   rtol=1e-6)
        r = paddle.as_real(t)
        assert tuple(r.shape) == (2, 2)
        np.testing.assert_allclose(_np(paddle.as_complex(r)), z)


class TestSignal:
    def test_frame_overlap_add_roundtrip(self):
        x = np.arange(32.0, dtype=np.float32)
        f = paddle.signal.frame(paddle.to_tensor(x), frame_length=8,
                                hop_length=8)
        assert tuple(f.shape) == (8, 4)
        back = paddle.signal.overlap_add(f, hop_length=8)
        np.testing.assert_allclose(_np(back), x)

    def test_frame_batched_overlapping(self):
        x = np.random.RandomState(3).randn(2, 20).astype(np.float32)
        f = _np(paddle.signal.frame(paddle.to_tensor(x), 8, 4))
        assert f.shape == (2, 8, 4)
        for i in range(4):
            np.testing.assert_allclose(f[:, :, i], x[:, i * 4:i * 4 + 8])

    def test_overlap_add_sums(self):
        frames = np.ones((4, 3), dtype=np.float32)  # L=4, F=3, hop 2
        out = _np(paddle.signal.overlap_add(paddle.to_tensor(frames), 2))
        np.testing.assert_allclose(out, [1, 1, 2, 2, 2, 2, 1, 1])

    def test_stft_matches_manual(self):
        rs = np.random.RandomState(5)
        x = rs.randn(512).astype(np.float32)
        n_fft, hop = 64, 16
        w = np.hanning(n_fft).astype(np.float32)
        spec = _np(paddle.signal.stft(
            paddle.to_tensor(x), n_fft, hop_length=hop,
            window=paddle.to_tensor(w), center=True))
        # manual oracle
        xp = np.pad(x, n_fft // 2, mode="reflect")
        n_frames = 1 + (len(xp) - n_fft) // hop
        man = np.stack([np.fft.rfft(xp[i * hop:i * hop + n_fft] * w)
                        for i in range(n_frames)], axis=1)
        assert spec.shape == man.shape
        np.testing.assert_allclose(spec, man, rtol=1e-3, atol=1e-3)

    def test_stft_istft_roundtrip(self):
        rs = np.random.RandomState(7)
        x = rs.randn(1024).astype(np.float32)
        n_fft, hop = 128, 32
        w = np.hanning(n_fft).astype(np.float32)
        spec = paddle.signal.stft(paddle.to_tensor(x), n_fft,
                                  hop_length=hop,
                                  window=paddle.to_tensor(w))
        back = _np(paddle.signal.istft(spec, n_fft, hop_length=hop,
                                       window=paddle.to_tensor(w),
                                       length=1024))
        np.testing.assert_allclose(back, x, rtol=1e-3, atol=1e-3)


class TestRegularizerHubVersion:
    def test_l2_decay_equals_float(self):
        import paddle_tpu.nn as nn

        paddle.seed(0)
        l1 = nn.Linear(4, 4)
        l2 = nn.Linear(4, 4)
        l2.set_state_dict(l1.state_dict())
        o1 = paddle.optimizer.Momentum(0.1, parameters=l1.parameters(),
                                       weight_decay=0.1)
        o2 = paddle.optimizer.Momentum(
            0.1, parameters=l2.parameters(),
            weight_decay=paddle.regularizer.L2Decay(0.1))
        x = paddle.to_tensor(np.random.RandomState(0).randn(2, 4)
                             .astype(np.float32))
        for m, o in ((l1, o1), (l2, o2)):
            loss = m(x).sum()
            loss.backward()
            o.step()
            o.clear_grad()
        np.testing.assert_allclose(
            _np(l1.weight), _np(l2.weight), rtol=1e-6)

    def test_l1_decay_signs(self):
        import paddle_tpu.nn as nn

        paddle.seed(0)
        lin = nn.Linear(2, 2, bias_attr=False)
        w0 = _np(lin.weight).copy()
        opt = paddle.optimizer.SGD(
            0.5, parameters=lin.parameters(),
            weight_decay=paddle.regularizer.L1Decay(0.3))
        x = paddle.to_tensor(np.zeros((1, 2), np.float32))
        loss = lin(x).sum()
        loss.backward()
        opt.step()
        # grad is 0 (x=0) so update = -lr * coeff * sign(w)
        np.testing.assert_allclose(
            _np(lin.weight), w0 - 0.5 * 0.3 * np.sign(w0), rtol=1e-5)

    def test_hub_local(self, tmp_path):
        (tmp_path / "hubconf.py").write_text(
            "def tiny_model(scale=2):\n"
            "    'doc for tiny'\n"
            "    return {'scale': scale}\n")
        assert paddle.hub.list(str(tmp_path)) == ["tiny_model"]
        assert "doc for tiny" in paddle.hub.help(str(tmp_path),
                                                 "tiny_model")
        assert paddle.hub.load(str(tmp_path), "tiny_model",
                               scale=5) == {"scale": 5}
        with pytest.raises(RuntimeError):
            paddle.hub.load(str(tmp_path), "missing")
        with pytest.raises(RuntimeError):
            paddle.hub.list("x", source="github")

    def test_version(self):
        assert paddle.__version__ == paddle.version.full_version
        assert paddle.version.cuda() == "False"


class TestReviewRegressions:
    """Regressions for the round-3 code-review findings."""

    def test_overlap_add_axis0_roundtrip(self):
        x = np.arange(12.0, dtype=np.float32)
        f = paddle.signal.frame(paddle.to_tensor(x), 4, 2, axis=0)
        assert tuple(f.shape) == (4, 5)
        back = _np(paddle.signal.overlap_add(f, 2, axis=0))
        # overlapping regions sum; ends are single-counted
        expect = np.zeros(12)
        for i in range(5):
            expect[i * 2:i * 2 + 4] += x[i * 2:i * 2 + 4]
        np.testing.assert_allclose(back, expect)

    def test_stft_complex_onesided_raises(self):
        z = (np.random.RandomState(0).randn(256)
             + 1j * np.random.RandomState(1).randn(256)).astype(np.complex64)
        with pytest.raises(ValueError):
            paddle.signal.stft(paddle.to_tensor(z), 64)
        spec = paddle.signal.stft(paddle.to_tensor(z), 64, onesided=False)
        assert spec.shape[0] == 64

    def test_hfftn_s_axes_none(self):
        spec = np.fft.rfft(np.random.RandomState(2).randn(3, 16)
                           .astype(np.float32))
        out = _np(paddle.fft.hfftn(
            paddle.to_tensor(spec.astype(np.complex64)), s=[16]))
        expect = np.stack([np.fft.hfft(spec[i], n=16) for i in range(3)])
        np.testing.assert_allclose(out, expect, rtol=1e-3, atol=1e-3)

    def test_tensor_as_complex_method(self):
        r = paddle.to_tensor(np.array([[1.0, 2.0]], np.float32))
        z = r.as_complex()
        np.testing.assert_allclose(_np(z), [1 + 2j])

    def test_sparse_attention_per_head_pattern(self):
        import paddle_tpu.nn.functional as F

        rs = np.random.RandomState(0)
        B, H, S, D = 1, 2, 4, 8
        q = rs.randn(B, H, S, D).astype(np.float32)
        k = rs.randn(B, H, S, D).astype(np.float32)
        v = rs.randn(B, H, S, D).astype(np.float32)
        # head 0: diagonal-only; head 1: row 0 attends everywhere,
        # rows 1-3 diagonal-only — DIFFERENT row structure per head
        offs = np.array([[[0, 1, 2, 3, 4], [0, 4, 5, 6, 7]]], np.int32)
        cols = np.array([[[0, 1, 2, 3, 0, 0, 0, 0][:4] + [0] * 3,
                          [0, 1, 2, 3, 1, 2, 3]]], np.int32)
        # head 0 has 4 nnz, head 1 has 7 → pad head 0 cols to 7 by
        # repeating its last entries within the same rows is invalid;
        # instead give both heads 7 entries with head-0 rows [0,0,1,2,3..]
        offs = np.array([[[0, 4, 5, 6, 7], [0, 4, 5, 6, 7]]], np.int32)
        cols = np.array([[[0, 1, 2, 3, 1, 2, 3],
                          [0, 1, 2, 3, 1, 2, 3]]], np.int32)
        # make head 1's row structure different: row0 1 entry, row1 4...
        offs[0, 1] = [0, 1, 5, 6, 7]
        cols[0, 1] = [0, 0, 1, 2, 3, 2, 3]
        out = _np(F.sparse_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            paddle.to_tensor(offs), paddle.to_tensor(cols)))

        # oracle: densify per head independently
        def dense(qh, kh, vh, o, c):
            mask = np.full((S, S), False)
            for r in range(S):
                for j in range(o[r], o[r + 1]):
                    mask[r, c[j]] = True
            sc = qh @ kh.T / np.sqrt(D)
            sc = np.where(mask, sc, -1e30)
            p = np.exp(sc - sc.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            return p @ vh

        for h in range(H):
            np.testing.assert_allclose(
                out[0, h], dense(q[0, h], k[0, h], v[0, h],
                                 offs[0, h], cols[0, h]),
                rtol=1e-4, atol=1e-5)

    def test_hsigmoid_custom_tree(self):
        import paddle_tpu.nn.functional as F

        rs = np.random.RandomState(1)
        x = rs.randn(2, 3).astype(np.float32)
        w = rs.randn(5, 3).astype(np.float32)
        lbl = np.array([[0], [1]], np.int64)
        ptab = np.array([[0, 2, -1], [1, 3, 4]], np.int64)
        pcode = np.array([[1, 0, 0], [0, 1, 1]], np.int64)
        out = _np(F.hsigmoid_loss(
            paddle.to_tensor(x), paddle.to_tensor(lbl), 4,
            paddle.to_tensor(w), path_table=paddle.to_tensor(ptab),
            path_code=paddle.to_tensor(pcode)))

        def sce(z, t):
            return max(z, 0) - z * t + np.log1p(np.exp(-abs(z)))

        expect = []
        for n in range(2):
            tot = 0.0
            for l in range(3):
                if ptab[n, l] < 0:
                    continue
                tot += sce(float(x[n] @ w[ptab[n, l]]), float(pcode[n, l]))
            expect.append([tot])
        np.testing.assert_allclose(out, expect, rtol=1e-4)


class TestTopLevelParity:
    def test_batch(self):
        r = paddle.batch(lambda: iter(range(7)), 3)
        assert [len(b) for b in r()] == [3, 3, 1]
        r = paddle.batch(lambda: iter(range(7)), 3, drop_last=True)
        assert [len(b) for b in r()] == [3, 3]

    def test_compiled_with(self):
        assert paddle.is_compiled_with_cuda() is False
        assert paddle.is_compiled_with_xpu() is False
        assert paddle.get_cudnn_version() is None

    def test_iinfo_finfo(self):
        ii = paddle.iinfo("int32")
        assert ii.min == -2**31 and ii.max == 2**31 - 1 and ii.bits == 32
        fi = paddle.finfo("float32")
        assert fi.max > 3e38 and fi.eps < 1e-6
        bf = paddle.finfo("bfloat16")
        assert bf.max > 3e38  # bf16 has f32-like range

    def test_sysconfig(self):
        assert paddle.sysconfig.get_include().endswith("include")
        assert paddle.sysconfig.get_lib().endswith("libs")

    def test_flops_linear(self):
        import paddle_tpu.nn as nn

        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
        total = paddle.flops(net, [2, 16])
        # 2*(16*32) + 2*32 (relu) + 2*(32*4) = 1024+64+256... reference
        # counts MACs for linear: batch*in*out
        assert total == 2 * 16 * 32 + 2 * 32 + 2 * 32 * 4


class TestReviewRegressions2:
    """Round-3 second review batch."""

    def test_flash_supports_non_default_multiples(self):
        from paddle_tpu.ops.pallas.flash_attention_kernel import (
            supports, flash_plan)

        # shapes that divided the old 128 blocks must stay supported
        for S in (768, 1536, 640):
            assert supports((2, S, 4, 64), (2, S, 4, 64)), S
        # (block, sub-block) for bf16 heads of 64
        assert flash_plan(1536, 64, 2)[:2] == (512, 256)
        assert flash_plan(768, 64, 2)[:2] == (768, 256)
        assert flash_plan(1024, 64, 2)[:2] == (1024, 256)

    def test_multinomial_entropy_exact(self):
        from paddle_tpu import distribution as D
        from math import lgamma, log

        m = D.Multinomial(2, np.array([0.5, 0.5]))
        # support {(2,0),(1,1),(0,2)} probs {1/4, 1/2, 1/4}
        expect = -(0.25 * log(0.25) * 2 + 0.5 * log(0.5))
        np.testing.assert_allclose(float(m.entropy()), expect, rtol=1e-5)

    def test_chain_injective_nested(self):
        from paddle_tpu import distribution as D

        inner = D.ChainTransform([D.AbsTransform()])
        outer = D.ChainTransform([inner, D.ExpTransform()])
        assert not inner._is_injective()
        assert not outer._is_injective()

    def test_as_complex_single_impl_validates(self):
        t = paddle.to_tensor(np.zeros((3, 4), np.float32))
        with pytest.raises(ValueError):
            paddle.as_complex(t)
        with pytest.raises(ValueError):
            t.as_complex()

    def test_hub_force_reload(self, tmp_path):
        p = tmp_path / "hubconf.py"
        p.write_text("def f():\n    return 1\n")
        assert paddle.hub.load(str(tmp_path), "f") == 1
        p.write_text("def f():\n    return 2\n")
        assert paddle.hub.load(str(tmp_path), "f") == 1  # cached
        assert paddle.hub.load(str(tmp_path), "f",
                               force_reload=True) == 2


class TestUtilsParity:
    def test_deprecated_warns(self):
        import warnings
        from paddle_tpu.utils import deprecated

        @deprecated(update_to="paddle.new_api", since="0.3")
        def old_api():
            return 42

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert old_api() == 42
        assert any(issubclass(x.category, DeprecationWarning) for x in w)
        assert "deprecated" in (old_api.__doc__ or "")

    def test_run_check(self, capsys):
        paddle.utils.run_check()
        out = capsys.readouterr().out
        assert "successfully" in out

    def test_unique_name(self):
        from paddle_tpu.utils import unique_name

        a = unique_name.generate("fc")
        b = unique_name.generate("fc")
        assert a != b
        with unique_name.guard():
            c = unique_name.generate("fc")
            assert c == "fc_0"

    def test_deprecated_level2_raises(self):
        from paddle_tpu.utils import deprecated

        @deprecated(level=2)
        def removed_api():
            return 1

        with pytest.raises(RuntimeError):
            removed_api()

    def test_unique_name_guard_prefix(self):
        from paddle_tpu.utils import unique_name

        with unique_name.guard("blockA_"):
            assert unique_name.generate("fc") == "blockA_fc_0"
        with unique_name.guard(lambda key: f"custom::{key}"):
            assert unique_name.generate("fc") == "custom::fc"
