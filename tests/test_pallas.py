"""Pallas kernel tests (interpret mode on CPU; numerics vs the XLA oracle,
the reference's own test strategy for fused ops — SURVEY.md §4 OpTest)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import _sdpa_reference
from paddle_tpu.ops.pallas.flash_attention_kernel import (
    flash_attention_fused, flash_attention_fused_qkv)


def _qkv(B=2, S=256, H=4, D=64, dtype=jnp.float32, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(B, S, H, D), dtype)  # noqa: E731
    return mk(), mk(), mk()


# (S, head_dim, causal, block_q, sub-block); None: the plan's own choice.
# Float32 operands: the plan gives a grid step at most 512 rows, so S = 1024
# is two blocks and S = 1536 three by itself.
_PARITY = {
    # the cases this test took over: defaults at [2, 256, 4, 64], a
    # sequence shorter than every preferred size
    "default-noncausal": (256, 64, False, None, None),
    "default-causal": (256, 64, True, None, None),
    "small-seq-block-clamp": (64, 64, True, None, None),
    # one block: the block larger than and equal to the sub-block
    "256-q>sub": (256, 64, True, 256, 64),
    "256-q=sub": (256, 64, True, 256, 256),
    "256-d128-q>sub": (256, 128, True, 256, 128),
    "256-d128-q>sub-noncausal": (256, 128, False, 256, 64),
    # several blocks: pairs above, on and under the diagonal, the clamped
    # index_map
    "256-blocks-q=sub": (256, 64, True, 64, 64),
    "256-d128-blocks": (256, 128, True, 128, 64),
    "256-d128-blocks-noncausal": (256, 128, False, 128, 64),
    "512-d128-blocks": (512, 128, True, 256, 128),
    "512-blocks-four": (512, 64, True, 128, 64),
    "512-blocks-q=sub": (512, 64, True, 256, 256),
    "512-d128-blocks-noncausal": (512, 128, False, 128, 64),
    "512-blocks-q=sub-noncausal": (512, 64, False, 256, 256),
    # the cell's sequence
    "1024-default-two-blocks": (1024, 64, True, None, None),
    "1024-one-block": (1024, 64, True, 1024, 256),
    "1024-d128-blocks": (1024, 128, True, 256, 256),
    "1024-d128-sub-512": (1024, 128, True, 1024, 512),
    "1024-noncausal": (1024, 64, False, 512, 256),
    # a sequence that is no power of two
    "1536-default-three-blocks": (1536, 64, True, None, None),
    "1536-d128-block-768": (1536, 128, True, 768, 128),
    "1536-default-noncausal": (1536, 64, False, None, None),
    "1536-d128-q=sub-noncausal": (1536, 128, False, 512, 512),
    # bf16 operands on the MXU path's dtypes (1024 rows a block)
    "bf16": (256, 64, True, None, None),
    "bf16-blocks": (512, 128, True, 256, 64),
}


def _attn_and_grads(attn, q, k, v):
    o, vjp = jax.vjp(attn, q, k, v)
    return (o,) + vjp(v)            # cotangent: v, as (o * v).sum()'s


def _assert_close(got, want, dtype):
    """out, dq, dk, dv against the oracle's: float32 by the tolerances the
    parity table always had; bf16 the output as absolute as it always was,
    a gradient, which sums bf16 products over the sequence, by its
    magnitude."""
    for name, a, b, atol in zip(("out", "dq", "dk", "dv"), got, want,
                                (2e-5, 5e-4, 5e-4, 5e-4)):
        assert a.dtype == dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == jnp.bfloat16:
            atol = 3e-2 * (1.0 if name == "out"
                           else max(1.0, float(np.abs(b).max())))
        np.testing.assert_allclose(a, b, atol=atol, err_msg=name)


# the feature-major kernels at the head widths, element sizes and sequence
# lengths of the models that train through them: one block a head (256;
# 1,024 in bf16) and several (1,024 in f32: two; 2,048: two or four)
_FEATURE_MAJOR = [(D, dtype, S, causal)
                  for D in (64, 128)
                  for dtype in ("bfloat16", "float32")
                  for S in (256, 1024, 2048)
                  for causal in (True, False)]


class TestFlashAttention:
    @pytest.mark.parametrize("case", list(_PARITY))
    def test_output_and_grads_match_oracle(self, case):
        """The output AND dq, dk, dv of every branch of the walk against the
        XLA oracle."""
        S, D, causal, block_q, sub = _PARITY[case]
        old = case.startswith(("default", "small", "bf16"))
        bf16 = case.startswith("bf16")
        q, k, v = _qkv(B=2 if old else 1, S=S, H=4 if old else 1, D=D,
                       dtype=jnp.bfloat16 if bf16 else jnp.float32)
        got = _attn_and_grads(
            lambda q, k, v: flash_attention_fused(
                q, k, v, causal=causal, block_q=block_q, block_k=sub,
                interpret=True), q, k, v)
        want = _attn_and_grads(
            lambda q, k, v: _sdpa_reference(q, k, v, None, None, 0.0,
                                            causal), q, k, v)
        _assert_close(got, want, q.dtype)

    @pytest.mark.parametrize(
        "D,dtype,S,causal", _FEATURE_MAJOR,
        ids=[f"d{D}-{dtype}-{S}-{'causal' if c else 'full'}"
             for D, dtype, S, c in _FEATURE_MAJOR])
    def test_feature_major_kernels_match_oracle(self, D, dtype, S, causal):
        """Forward and all three gradients of the ``[head_dim, S]`` kernels
        at the plan's own sizes."""
        q, k, v = _qkv(B=1, S=S, H=2 if S == 256 else 1, D=D,
                       dtype=jnp.dtype(dtype))
        got = _attn_and_grads(
            lambda q, k, v: flash_attention_fused(
                q, k, v, causal=causal, interpret=True), q, k, v)
        want = _attn_and_grads(
            lambda q, k, v: _sdpa_reference(q, k, v, None, None, 0.0,
                                            causal), q, k, v)
        _assert_close(got, want, q.dtype)

    @pytest.mark.parametrize("S,D,dtype,causal,block_q,sub", [
        (256, 64, "float32", True, None, None),       # one block a head
        (512, 64, "float32", True, 128, 64),          # four: above, on, under
        (512, 128, "float32", False, 256, 128),
        (1024, 64, "bfloat16", True, None, None),     # the cell's call
        (2048, 128, "bfloat16", True, None, None),    # two 1,024-row blocks
    ])
    def test_fused_projection_entry_equals_three_tensor_entry(
            self, S, D, dtype, causal, block_q, sub):
        """The kernels reading q, k, v out of a head-major ``[B, S, H, 3D]``
        projection and writing its gradient as one array give, bit for bit,
        what they give on the three tensors split off it: the entries
        differ in index maps only."""
        rs = np.random.RandomState(1)
        B, H = (2, 3) if S == 256 else (1, 2)
        qkv = jnp.asarray(rs.randn(B, S, H, 3 * D), jnp.dtype(dtype))
        g = jnp.asarray(rs.randn(B, S, H, D), jnp.dtype(dtype))

        def split(qkv):
            q, k, v = jnp.split(qkv, 3, axis=-1)
            return flash_attention_fused(q, k, v, causal=causal,
                                         block_q=block_q, block_k=sub,
                                         interpret=True)

        def fused(qkv):
            return flash_attention_fused_qkv(qkv, causal=causal,
                                             block_q=block_q, block_k=sub,
                                             interpret=True)

        o3, vjp3 = jax.vjp(split, qkv)
        o1, vjp1 = jax.vjp(fused, qkv)
        assert o1.shape == (B, S, H, D) and o1.dtype == qkv.dtype
        np.testing.assert_array_equal(np.asarray(o1, np.float32),
                                      np.asarray(o3, np.float32))
        (d3,), (d1,) = vjp3(g), vjp1(g)
        assert d1.shape == qkv.shape and d1.dtype == qkv.dtype
        np.testing.assert_array_equal(np.asarray(d1, np.float32),
                                      np.asarray(d3, np.float32))

    @pytest.mark.parametrize("fused", [False, True])
    def test_lse_is_one_lane_dense_row_a_head(self, fused):
        """The residual the forward leaves: ``[B·H, 1, S]`` float32, the
        sequence on the lanes — no ``[.., S, 1]`` column anywhere."""
        from paddle_tpu.ops.pallas.flash_attention_kernel import (
            _fwd, flash_plan)

        B, S, H, D = 2, 256, 3, 64
        q, k, v = _qkv(B=B, S=S, H=H, D=D)

        def feature_major(x):
            return x.transpose(0, 2, 3, 1).reshape(B, H * D, S)

        if fused:       # head n's q, k, v: row blocks 3n, 3n + 1, 3n + 2
            x = (jnp.concatenate([q, k, v], -1).reshape(B, S, H * 3 * D)
                 .transpose(0, 2, 1),)
        else:
            x = (feature_major(q), feature_major(k), feature_major(v))
        o, lse = _fwd(x, head_dim=D, scale=D ** -0.5, causal=True,
                      plan=flash_plan(S, D, 4), interpret=True)
        assert o.shape == (B, H * D, S)
        assert lse.shape == (B * H, 1, S) and lse.dtype == jnp.float32
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
        logits = jnp.where(jnp.tril(jnp.ones((S, S), bool)), logits, -1e30)
        want = jax.scipy.special.logsumexp(logits, axis=-1)      # [B,H,S]
        np.testing.assert_allclose(np.asarray(lse).reshape(B, H, S),
                                   np.asarray(want), atol=2e-5)

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


# (S, block_q, sub)
_PLANS = [(1024, 1024, 256), (1024, 1024, 128), (1024, 1024, 512),
          (1024, 512, 256), (1024, 256, 256), (1024, 128, 128),
          (1536, 512, 256), (1536, 768, 128), (640, 640, 128),
          (768, 768, 256), (4096, 1024, 256), (256, 128, 64), (96, 96, 96)]


class TestFlashPlan:
    """The plan function alone: what a causal call's walk visits."""

    @pytest.mark.parametrize("S,block_q,sub", _PLANS)
    def test_walk_visits_exactly_the_squares_on_or_under_the_diagonal(
            self, S, block_q, sub):
        from paddle_tpu.ops.pallas.flash_attention_kernel import (
            _walk, flash_plan)

        n, blocks = block_q // sub, S // block_q
        # square (query sub-block, key sub-block) -> masked?, as the
        # kernels' walks visit them, a pair's key sub-blocks one after the
        # other; and the same squares read from the queries' end (query
        # sub-block j meets the keys 0 .. j), as PR 31's bwd_dq walked them
        by_keys, by_queries = {}, {}
        for iq in range(blocks):
            for ik in range(iq + 1):                  # pairs not skipped
                diagonal = iq == ik
                for j, first in _walk(n, diagonal):
                    for other in range(first, n):
                        square = (iq * n + other, ik * n + j)
                        assert square not in by_keys
                        by_keys[square] = diagonal and other == first
                    # from the other end: query sub-block j, keys 0 .. j
                    for other in range(j + 1 if diagonal else n):
                        square = (iq * n + j, ik * n + other)
                        assert square not in by_queries
                        by_queries[square] = diagonal and other == j
        want = {}
        for i in range(S // sub):
            for j in range(S // sub):
                rows = (i * sub, (i + 1) * sub - 1)
                cols = (j * sub, (j + 1) * sub - 1)
                if rows[1] >= cols[0]:          # an entry with row >= col
                    # masked iff an entry with row < col too
                    want[(i, j)] = rows[0] < cols[1]
        assert by_keys == want and by_queries == want
        plan = flash_plan(S, 64, 2, True, block_q, sub)
        assert plan[:3] == (block_q, sub, S // block_q)
        assert plan.tiles_total == (S // sub) ** 2
        assert plan.tiles_visited == len(want)
        assert plan.tiles_masked == sum(want.values())
        full = flash_plan(S, 64, 2, False, block_q, sub)
        assert full.tiles_visited == full.tiles_total and \
            full.tiles_masked == 0

    def test_the_cells_shape_visits_at_most_three_quarters(self):
        from paddle_tpu.ops.pallas.flash_attention_kernel import flash_plan

        plan = flash_plan(1024, 64, 2)        # GPT-2 345M: bf16, 16 x 64
        assert plan.block_q == 1024           # one grid step a head
        assert plan.tiles_visited / plan.tiles_total <= 0.75
        assert 0 < plan.tiles_masked < plan.tiles_visited

    def test_sizes_follow_the_operands_bytes_and_the_head_width(self):
        from paddle_tpu.ops.pallas.flash_attention_kernel import flash_plan

        assert flash_plan(4096, 128, 2).block_q == 1024
        assert flash_plan(4096, 128, 4).block_q == 512    # f32 halves it
        assert flash_plan(4096, 256, 2).block_q == 512    # two lane rows
        assert flash_plan(1536, 64, 4).block_q == 512     # 3 x 512
        assert flash_plan(1536, 64, 2).block_q == 512
        # a piece is whole 128-lane columns, or the block
        assert flash_plan(320, 64, 2)[:2] == (320, 320)
        assert flash_plan(640, 64, 2)[:2] == (640, 128)
        # what fills VMEM is float32 whatever the operands: 1-byte operands
        # take no more rows than 2-byte ones
        assert flash_plan(4096, 128, 1).block_q == 1024
        assert flash_plan(4096, 64, 1).block_q == 1024

    @pytest.mark.parametrize("S", [96, 300, 520, 640, 768, 1536, 516, 700,
                                   1100])
    def test_supports_holds_whatever_the_operands_bytes(self, S):
        """The dispatch guard sees shapes only: what it accepts has a plan
        at every element size, so a call behind it never raises."""
        from paddle_tpu.ops.pallas.flash_attention_kernel import (
            flash_plan, supports)

        shape = (2, S, 4, 64)
        if supports(shape, shape):
            for itemsize in (1, 2, 4):
                assert S % flash_plan(S, 64, itemsize).block_q == 0
        else:
            # 520 = 8 x 65: one block at two bytes, but no block of whole
            # 128-lane columns divides it at four
            assert S in (516, 520, 700, 1100)
            with pytest.raises(ValueError):
                flash_plan(S, 64, 4)

    def test_sizes_that_do_not_divide_are_refused(self):
        from paddle_tpu.ops.pallas.flash_attention_kernel import flash_plan

        with pytest.raises(ValueError):
            flash_plan(512, 64, 2, True, 128, 96)     # sub !| block
        with pytest.raises(ValueError):
            flash_plan(512, 64, 2, True, 384, 128)    # block !| S


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
        from paddle_tpu.models.gpt import _flash_attention_qkv
        from paddle_tpu.models.llama import _flash_attention
        assert callable(_flash_attention_qkv) and callable(_flash_attention)

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
