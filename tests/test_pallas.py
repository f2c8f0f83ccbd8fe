"""Pallas kernel tests (interpret mode on CPU; numerics vs the XLA oracle,
the reference's own test strategy for fused ops — SURVEY.md §4 OpTest)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import _sdpa_reference
from paddle_tpu.ops.pallas.flash_attention_kernel import flash_attention_fused


def _qkv(B=2, S=256, H=4, D=64, dtype=jnp.float32, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(B, S, H, D), dtype)  # noqa: E731
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_oracle(self, causal):
        q, k, v = _qkv()
        o = flash_attention_fused(q, k, v, causal=causal, interpret=True)
        ref = _sdpa_reference(q, k, v, None, None, 0.0, causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_oracle(self, causal):
        q, k, v = _qkv()

        def loss_fa(q, k, v):
            return (flash_attention_fused(q, k, v, causal=causal,
                                          interpret=True) * v).sum()

        def loss_ref(q, k, v):
            return (_sdpa_reference(q, k, v, None, None, 0.0, causal) * v).sum()

        g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4)

    def test_nondivisible_seq_raises(self):
        q, k, v = _qkv(S=100)
        with pytest.raises(ValueError):
            flash_attention_fused(q, k, v, block_q=128, block_k=128,
                                  interpret=True)

    def test_supports_guard(self):
        from paddle_tpu.ops.pallas.flash_attention_kernel import supports
        assert supports((2, 256, 4, 64), (2, 256, 4, 64))
        assert not supports((2, 300, 4, 64), (2, 300, 4, 64),
                            block_q=128, block_k=128)
        assert not supports((2, 1, 4, 64), (2, 256, 4, 64))  # decode

    def test_cross_attention_raises(self):
        q, _, _ = _qkv(S=128)
        _, k, v = _qkv(S=256)
        with pytest.raises(ValueError):
            flash_attention_fused(q, k, v, interpret=True)

    def test_small_seq_block_clamp(self):
        q, k, v = _qkv(S=64)
        o = flash_attention_fused(q, k, v, causal=True, interpret=True)
        ref = _sdpa_reference(q, k, v, None, None, 0.0, True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=2e-5)

    def test_bf16(self):
        q, k, v = _qkv(dtype=jnp.bfloat16)
        o = flash_attention_fused(q, k, v, causal=True, interpret=True)
        ref = _sdpa_reference(q, k, v, None, None, 0.0, True)
        assert o.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(ref, np.float32), atol=3e-2)


class TestFlashPerShard:
    """GSPMD refuses to partition a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned", met on four real chips): under a
    multi-device mesh the kernel runs per (batch, heads) shard inside a
    fully-manual shard_map.  Interpret mode on the CPU mesh runs the same
    wrapper."""

    def test_values_and_grads_match_oracle_on_a_dp_mp_mesh(self):
        from paddle_tpu.distributed import mesh as mesh_mod
        from paddle_tpu.ops.pallas import flash_on_mesh

        q, k, v = _qkv(B=4, S=128, H=4, D=16)
        mesh_mod.set_global_mesh(
            mesh_mod.hybrid_mesh(dp=2, mp=2, devices=jax.devices()[:4]))
        try:
            def loss(attn):
                return lambda q, k, v: (attn(q, k, v) * v).sum()

            fa = jax.jit(jax.value_and_grad(loss(
                lambda q, k, v: flash_on_mesh(q, k, v, causal=True,
                                              interpret=True)),
                argnums=(0, 1, 2)))
            hlo = fa.lower(q, k, v).as_text()
            got = fa(q, k, v)
        finally:
            mesh_mod.set_global_mesh(None)
        assert "shard_map" in hlo or "manual" in hlo.lower()
        want = jax.value_and_grad(loss(
            lambda q, k, v: _sdpa_reference(q, k, v, None, None, 0.0, True)),
            argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for a, b in zip(got[1], want[1]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4)

    def test_no_mesh_means_a_direct_call(self):
        from paddle_tpu.ops.pallas import per_shard

        assert per_shard(lambda a: a + 1, (jnp.ones(3),), (None,),
                         None).tolist() == [2.0, 2.0, 2.0]


class TestPackageWiring:
    def test_flash_attention_callable_after_kernel_import(self):
        """Regression: the kernel submodule used to shadow the package-level
        flash_attention function (round-1 ship-breaker)."""
        import importlib
        import paddle_tpu.ops.pallas as pkg
        import paddle_tpu.ops.pallas.flash_attention_kernel  # noqa: F401
        importlib.reload(paddle_tpu.ops.pallas.flash_attention_kernel)
        assert callable(pkg.flash_attention)
        # the models bind the function directly too
        from paddle_tpu.models.gpt import _flash_attention
        assert callable(_flash_attention)

    def test_pallas_kernel_in_hlo_on_tpu(self):
        """On a real TPU backend the jitted attention must lower to the Pallas
        custom-call (kernel-engagement proof demanded by round-1 verdict)."""
        from paddle_tpu.ops.pallas import use_pallas
        if not use_pallas():
            pytest.skip("no TPU backend attached")
        from paddle_tpu.ops.pallas import flash_attention
        from paddle_tpu.core.tensor import Tensor
        q, k, v = _qkv(B=1, S=256, H=4, D=64, dtype=jnp.bfloat16)

        def fn(q, k, v):
            return flash_attention(Tensor._wrap(q), Tensor._wrap(k),
                                   Tensor._wrap(v), is_causal=True)._value()

        hlo = jax.jit(fn).lower(q, k, v).compile().as_text()
        assert "custom-call" in hlo and (
            "tpu_custom_call" in hlo or "mosaic" in hlo.lower()), (
            "Pallas flash-attention kernel not engaged in compiled HLO")
