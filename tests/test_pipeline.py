"""Pipeline-parallel tests: compiled schedule vs sequential execution
(the reference's PP loss-equivalence strategy, SURVEY.md §4)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet.meta_parallel import LayerDesc, PipelineLayer


@pytest.fixture(scope="module")
def hybrid_pp():
    s = paddle.distributed.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 2}
    s.pipeline_configs = {"accumulate_steps": 4}
    fleet.init(is_collective=True, strategy=s)
    return fleet.get_hybrid_communicate_group(), s


class Block(nn.Layer):
    def __init__(self):
        super().__init__()
        mp = fleet.meta_parallel
        self.fc1 = mp.ColumnParallelLinear(16, 32, gather_output=False)
        self.fc2 = mp.RowParallelLinear(32, 16, input_is_parallel=True)
        self.ln = nn.LayerNorm(16)

    def forward(self, x):
        return self.ln(x + self.fc2(F.gelu(self.fc1(x))))


def _loss(out, y):
    return ((out - y) ** 2).mean()


def _build(hybrid_pp):
    hcg, _ = hybrid_pp
    paddle.seed(0)
    pipe = PipelineLayer(
        [nn.Linear(8, 16)] + [LayerDesc(Block) for _ in range(4)]
        + [nn.Linear(16, 4)],
        topology=hcg.topology(), loss_fn=_loss)
    return pipe, fleet.distributed_model(pipe)


class TestPipelineSchedule:
    def test_uniform_run_detected(self, hybrid_pp):
        pipe, model = _build(hybrid_pp)
        assert model._use_schedule
        assert len(model._prologue) == 1 and len(model._epilogue) == 1
        assert len(model._body) == 4

    def test_forward_matches_sequential(self, hybrid_pp):
        pipe, model = _build(hybrid_pp)
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(8, 8).astype(np.float32))
        with paddle.no_grad():   # value comparison only
            np.testing.assert_allclose(model(x).numpy(), pipe(x).numpy(),
                                       atol=1e-5)

    def test_grads_match_sequential(self, hybrid_pp):
        pipe, model = _build(hybrid_pp)
        rs = np.random.RandomState(1)
        x = paddle.to_tensor(rs.randn(8, 8).astype(np.float32))
        y = paddle.to_tensor(rs.randn(8, 4).astype(np.float32))
        _loss(model(x), y).backward()
        g_pipe = {n: p.grad.numpy().copy()
                  for n, p in pipe.named_parameters()}
        for p in pipe.parameters():
            p.clear_grad()
        _loss(pipe(x), y).backward()
        for n, p in pipe.named_parameters():
            np.testing.assert_allclose(g_pipe[n], p.grad.numpy(), atol=1e-5)

    def test_train_batch_converges_jitted(self, hybrid_pp):
        pipe, model = _build(hybrid_pp)
        opt = fleet.distributed_optimizer(
            paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=model.parameters()))
        rs = np.random.RandomState(2)
        x = paddle.to_tensor(rs.randn(8, 8).astype(np.float32))
        y = paddle.to_tensor(rs.randn(8, 4).astype(np.float32))

        @paddle.jit.to_static
        def step(x, y):
            return model.train_batch((x, y), opt)

        l0 = float(step(x, y))
        for _ in range(10):
            ln = float(step(x, y))
        assert np.isfinite(ln) and ln < l0

    def test_micro_batch_indivisible_raises(self, hybrid_pp):
        pipe, model = _build(hybrid_pp)
        rs = np.random.RandomState(3)
        x = paddle.to_tensor(rs.randn(6, 8).astype(np.float32))  # 6 % 4 != 0
        with pytest.raises(ValueError):
            model(x)

    def test_gpt_pipe_model(self, hybrid_pp):
        hcg, _ = hybrid_pp
        from paddle_tpu.models import gpt_tiny, GPTForCausalLMPipe
        paddle.seed(0)
        cfg = gpt_tiny()
        pipe = GPTForCausalLMPipe(cfg, topology=hcg.topology())
        model = fleet.distributed_model(pipe)
        assert model._use_schedule
        rs = np.random.RandomState(4)
        x = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (8, 16)))
        np.testing.assert_allclose(model(x).numpy(), pipe(x).numpy(),
                                   atol=2e-5)


@pytest.mark.slow
class TestJaxSwitchVmaAD:
    """Pins the jax 0.9.0 bug that forced the non-uniform pipeline schedule
    to stay sequential: lax.switch under shard_map varying-manual-axes
    computes WRONG gradients (forward exact, backward corrupt), while the
    dynamic-index select formulation is exact.  When this test starts
    failing (i.e. switch grads become correct), a switch-based non-uniform
    pipeline schedule becomes implementable — see pp_schedule.py docstring."""

    def test_switch_grads_corrupt_select_grads_exact(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, Mesh
        from jax import shard_map

        devs = np.array(jax.devices()[:2])
        mesh = Mesh(devs, ("pipe",))
        n_stages, n_micro, mb, width = 2, 4, 2, 16
        rs = np.random.RandomState(0)
        w1 = jnp.asarray(rs.randn(width, width) * 0.1)
        w2 = jnp.asarray(rs.randn(width, width) * 0.1)
        xs = jnp.asarray(rs.randn(n_micro, mb, width))

        def make_loss(kind):
            def stage_fn(stage, x, w1_, w2_):
                if kind == "switch":
                    return jax.lax.switch(
                        stage, [lambda a: jnp.tanh(a @ w1_),
                                lambda a: jnp.tanh(a @ w2_)], x)
                ws = jnp.stack([w1_, w2_])
                return jnp.tanh(x @ ws[stage])

            def inner(xs_full, w1_, w2_):
                stage = jax.lax.axis_index("pipe")
                pad = jnp.zeros((n_stages - 1,) + xs_full.shape[1:],
                                xs_full.dtype)
                ticks = jnp.concatenate([xs_full, pad], axis=0)
                z = jnp.zeros(xs_full.shape[1:], xs_full.dtype)
                if hasattr(jax.lax, "pcast"):
                    z = jax.lax.pcast(z, ("pipe",), to="varying")
                else:
                    z = jax.lax.pvary(z, ("pipe",))

                def tick(carry, inp):
                    x_in = jnp.where(stage == 0, inp, carry)
                    y = stage_fn(stage, x_in, w1_, w2_)
                    perm = [(i, (i + 1) % n_stages)
                            for i in range(n_stages)]
                    return jax.lax.ppermute(y, "pipe", perm), y

                _, ys = jax.lax.scan(tick, z, ticks)
                return ys[n_stages - 1:][None]

            f = shard_map(inner, mesh=mesh, in_specs=(P(), P(), P()),
                          out_specs=P("pipe"), axis_names={"pipe"})

            def loss(xs_full, w1_, w2_):
                return (f(xs_full, w1_, w2_)[n_stages - 1] ** 2).mean()
            return loss

        def seq_loss(xs_full, w1_, w2_):
            ys = []
            for i in range(n_micro):
                h = jnp.tanh(xs_full[i] @ w1_)
                ys.append(jnp.tanh(h @ w2_))
            return (jnp.stack(ys) ** 2).mean()

        ref = jax.grad(seq_loss, argnums=(1, 2))(xs, w1, w2)
        g_sel = jax.grad(make_loss("select"), argnums=(1, 2))(xs, w1, w2)
        for a, b in zip(ref, g_sel):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        g_sw = jax.grad(make_loss("switch"), argnums=(1, 2))(xs, w1, w2)
        still_broken = not all(
            np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)
            for a, b in zip(ref, g_sw))
        assert still_broken, (
            "jax lax.switch gradients under shard_map vma are now CORRECT "
            "— revisit the switch-based non-uniform pipeline schedule "
            "(pp_schedule.py docstring)")


class TestPipelineMemoryBound:
    """The compiled schedule's activation memory must not grow with the
    microbatch count M at fixed total batch (the 1F1B memory property,
    achieved here by per-tick remat — round-1 verdict item 5)."""

    def test_temp_memory_flat_in_microbatches(self):
        """Measured on the REAL train path: the to_static-compiled
        train_batch (fwd + tape backward + optimizer), introspected via the
        cached program's jax.jit lowering."""

        class BigBlock(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(256, 1024)
                self.fc2 = nn.Linear(1024, 256)

            def forward(self, x):
                return x + self.fc2(F.gelu(self.fc1(x)))

        def temp_bytes(n_micro):
            s = paddle.distributed.DistributedStrategy()
            s.hybrid_configs = {"dp_degree": -1, "mp_degree": 1,
                                "pp_degree": 2}
            s.pipeline_configs = {"accumulate_steps": n_micro}
            fleet.init(is_collective=True, strategy=s)
            hcg = fleet.get_hybrid_communicate_group()
            paddle.seed(0)
            pipe = PipelineLayer(
                [nn.Linear(8, 256)] + [LayerDesc(BigBlock)
                                       for _ in range(4)]
                + [nn.Linear(256, 4)],
                topology=hcg.topology(), loss_fn=_loss)
            model = fleet.distributed_model(pipe)
            opt = fleet.distributed_optimizer(
                paddle.optimizer.AdamW(learning_rate=1e-3,
                                       parameters=model.parameters()))

            @paddle.jit.to_static
            def step(x, y):
                return model.train_batch((x, y), opt)

            rs = np.random.RandomState(0)
            x = paddle.to_tensor(rs.randn(64, 8).astype(np.float32))
            y = paddle.to_tensor(rs.randn(64, 4).astype(np.float32))
            step(x, y)
            (prog,) = step._programs.values()
            aa = [x._value(), y._value()]
            sd, sk = prog._split_state([k.current()
                                        for k in prog.state_keys])
            ma = prog.jitted.lower(aa, sd, sk).compile().memory_analysis()
            return int(getattr(ma, "temp_size_in_bytes", 0))

        t2, t8 = temp_bytes(2), temp_bytes(8)
        # 4x more microbatches must not cost more live activation memory
        # (remat bounds live state to per-tick stage inputs, total ∝ batch)
        assert t8 <= t2 * 1.25, (t2, t8)


class TestInterleavedSchedule:
    """num_virtual_pipeline_stages=v: the interleaved schedule must compute
    exactly what the sequential stack computes (values AND grads), with a
    (P-1)/(vM+P-1) bubble (beyond-reference; the reference ships plain
    1F1B)."""

    def _build(self, hybrid_pp, v):
        hcg, _ = hybrid_pp
        paddle.seed(0)
        pipe = PipelineLayer(
            [nn.Linear(8, 16)] + [LayerDesc(Block) for _ in range(4)]
            + [nn.Linear(16, 4)],
            topology=hcg.topology(), loss_fn=_loss,
            num_virtual_pipeline_stages=v)
        return pipe, fleet.distributed_model(pipe)

    def test_virtual_stages_engaged(self, hybrid_pp):
        pipe, model = self._build(hybrid_pp, 2)
        assert model._use_schedule
        assert model.num_virtual == 2

    def test_forward_matches_sequential(self, hybrid_pp):
        pipe, model = self._build(hybrid_pp, 2)
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randn(8, 8).astype(np.float32))
        with paddle.no_grad():   # value comparison only
            np.testing.assert_allclose(model(x).numpy(), pipe(x).numpy(),
                                       atol=1e-5)

    def test_grads_match_sequential(self, hybrid_pp):
        pipe, model = self._build(hybrid_pp, 2)
        rs = np.random.RandomState(1)
        x = paddle.to_tensor(rs.randn(8, 8).astype(np.float32))
        y = paddle.to_tensor(rs.randn(8, 4).astype(np.float32))
        _loss(model(x), y).backward()
        g_pipe = {n: p.grad.numpy().copy()
                  for n, p in pipe.named_parameters()}
        for p in pipe.parameters():
            p.clear_grad()
        _loss(pipe(x), y).backward()
        for n, p in pipe.named_parameters():
            np.testing.assert_allclose(g_pipe[n], p.grad.numpy(),
                                       atol=1e-5, err_msg=n)

    def test_indivisible_degrades_to_v1(self, hybrid_pp):
        # 4 body layers cannot split into 2 stages x 4 chunks
        pipe, model = self._build(hybrid_pp, 4)
        assert model.num_virtual == 1
        assert model._use_schedule
