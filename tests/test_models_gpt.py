"""GPT flagship model tests + driver entry checks."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.models import (
    GPTConfig, GPTForCausalLM, GPTPretrainingCriterion, gpt_tiny,
)


@pytest.fixture(scope="module")
def hybrid():
    s = paddle.distributed.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "sharding_degree": 2}
    fleet.init(is_collective=True, strategy=s)
    return fleet.get_hybrid_communicate_group()


class TestGPTSingle:
    def test_forward_shapes_and_loss(self):
        paddle.seed(0)
        cfg = gpt_tiny()
        m = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion()
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (2, 16)))
        y = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (2, 16)))
        logits = m(x)
        assert logits.shape == [2, 16, cfg.vocab_size]
        loss = crit(logits, y)
        # random init → loss ≈ ln(vocab)
        assert abs(float(loss) - np.log(cfg.vocab_size)) < 0.5
        loss.backward()
        assert all(p.grad is not None for p in m.parameters())

    def test_tied_embeddings_single_param(self):
        cfg = gpt_tiny()
        m = GPTForCausalLM(cfg)
        names = [n for n, _ in m.named_parameters()]
        assert not any("lm_head" in n for n in names)

    def test_untied_lm_head(self):
        cfg = gpt_tiny(tie_word_embeddings=False)
        m = GPTForCausalLM(cfg)
        names = [n for n, _ in m.named_parameters()]
        assert any("lm_head" in n for n in names)

    def test_recompute_matches_no_recompute(self):
        paddle.seed(0)
        m1 = GPTForCausalLM(gpt_tiny(recompute=True))
        paddle.seed(0)
        m2 = GPTForCausalLM(gpt_tiny(recompute=False))
        crit = GPTPretrainingCriterion()
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randint(0, 128, (2, 16)))
        y = paddle.to_tensor(rs.randint(0, 128, (2, 16)))
        l1 = crit(m1(x), y)
        l2 = crit(m2(x), y)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
        l1.backward()
        l2.backward()
        g1 = m1.parameters()[0].grad.numpy()
        g2 = m2.parameters()[0].grad.numpy()
        np.testing.assert_allclose(g1, g2, atol=1e-5)


class TestGPTHybrid:
    def test_hybrid_train_converges(self, hybrid):
        paddle.seed(0)
        cfg = gpt_tiny(recompute=True)
        m = fleet.distributed_model(GPTForCausalLM(cfg))
        crit = GPTPretrainingCriterion()
        opt = fleet.distributed_optimizer(
            paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=m.parameters()))

        @paddle.jit.to_static
        def step(x, y):
            loss = crit(m(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (8, 16)))
        y = paddle.to_tensor(rs.randint(0, cfg.vocab_size, (8, 16)))
        l0 = float(step(x, y))
        for _ in range(15):
            ln = float(step(x, y))
        assert np.isfinite(ln) and ln < l0

    def test_qkv_heads_on_model_axis(self, hybrid):
        cfg = gpt_tiny()
        m = fleet.distributed_model(GPTForCausalLM(cfg))
        w = m._layers.gpt.layers[0].attn.qkv_proj.weight
        assert "model" in tuple(w._value().sharding.spec)


class TestGraftEntry:
    def test_dryrun_multichip_8(self):
        # light mode: the riskiest factorization + the single-device
        # equivalence reference; the driver runs the full 4-config sweep
        import __graft_entry__ as ge
        ge.dryrun_multichip(8, configs="hybrid-only")

    @pytest.mark.slow
    def test_dryrun_multichip_8_full_sweep(self):
        import __graft_entry__ as ge
        ge.dryrun_multichip(8)
