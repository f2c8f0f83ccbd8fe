"""ISSUE 11: on-device sampling — parity against the host oracle.

``serving.sampling.device_sample`` runs inside the compiled decode step;
``serving.sampling.sample`` is the retained host reference.  Contract:
greedy is BITWISE identical (argmax over the same f32 logits), seeded
top-k/top-p is statistically identical (same support, close empirical
distribution — the streams differ: numpy RandomState vs jax.random), and
the key-state mechanics make preempt-resume replay deterministic (the
engine-level half lives in tests/test_overload.py).

The host oracle's dtype contract is pinned here too: the ISSUE 11
bugfix made ``sample`` float32-explicit (it used to upcast to float64,
silently computing a softmax nothing in the f32 serving system ever
produces — the regression test distinguishes the two by a sub-f32-
precision logit difference).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.serving.sampling import (
    DeviceSampler, SamplingParams, device_sample, sample,
)


def _keys(n, base=0):
    return jax.vmap(jax.random.PRNGKey)(
        jnp.arange(base, base + n)).astype(jnp.uint32)


def _device_draws(logits, n, *, temp, top_k=0, top_p=1.0, base=0):
    toks, _ = device_sample(
        jnp.tile(jnp.asarray(logits, jnp.float32)[None], (n, 1)),
        jnp.full((n,), temp, jnp.float32),
        jnp.full((n,), top_k, jnp.int32),
        jnp.full((n,), top_p, jnp.float32),
        _keys(n, base))
    return np.asarray(toks)


class TestGreedyParity:
    def test_bitwise_matches_host(self):
        rs = np.random.RandomState(0)
        logits = rs.randn(32, 128).astype(np.float32)
        toks, _ = device_sample(
            jnp.asarray(logits), jnp.zeros((32,)),
            jnp.zeros((32,), jnp.int32), jnp.ones((32,)), _keys(32))
        host = [sample(row, SamplingParams()) for row in logits]
        assert np.asarray(toks).tolist() == host

    def test_tie_breaks_like_host(self):
        # equal maxima: both argmaxes take the FIRST occurrence
        logits = np.asarray([1.0, 5.0, 5.0, -2.0], np.float32)
        toks, _ = device_sample(
            jnp.asarray(logits)[None], jnp.zeros((1,)),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,)), _keys(1))
        assert int(toks[0]) == sample(logits, SamplingParams()) == 1


class TestSeededParity:
    N = 4000

    def test_top_k_support(self):
        rs = np.random.RandomState(1)
        logits = (rs.randn(16) * 2).astype(np.float32)
        top3 = set(np.argsort(-logits)[:3].tolist())
        dev = _device_draws(logits, self.N, temp=1.0, top_k=3)
        assert set(dev.tolist()) <= top3
        host = {sample(logits, SamplingParams(temperature=1.0, top_k=3),
                       np.random.RandomState(i)) for i in range(500)}
        assert host <= top3

    def test_top_p_support_matches_host(self):
        rs = np.random.RandomState(2)
        logits = (rs.randn(12) * 2).astype(np.float32)
        params = SamplingParams(temperature=0.7, top_p=0.8)
        host = np.array([sample(logits, params, np.random.RandomState(i))
                         for i in range(self.N)])
        dev = _device_draws(logits, self.N, temp=0.7, top_p=0.8)
        assert set(host.tolist()) == set(dev.tolist())

    def test_statistical_parity(self):
        """Empirical distributions agree (L1 < 0.05 over 4k draws) for a
        mixed temperature/top-k/top-p restriction — different RNG
        streams, same distribution."""
        rs = np.random.RandomState(3)
        logits = (rs.randn(12) * 1.5).astype(np.float32)
        params = SamplingParams(temperature=0.9, top_k=8, top_p=0.9)
        host = np.array([sample(logits, params, np.random.RandomState(i))
                         for i in range(self.N)])
        dev = _device_draws(logits, self.N, temp=0.9, top_k=8, top_p=0.9)
        hf = np.bincount(host, minlength=12) / self.N
        df = np.bincount(dev, minlength=12) / self.N
        assert np.abs(hf - df).sum() < 0.05, (hf, df)

    def test_top_p_one_keeps_full_support_under_peaked_logits(self):
        """Regression (review finding): with ``top_p == 1.0`` a peaked
        distribution must stay UNRESTRICTED.  f32 cumsum saturates at
        1.0 right after the dominant token, so without the explicit
        ``top_p >= 1`` skip the nucleus mask silently dropped the whole
        tail the host oracle (which skips top-p at 1.0) keeps."""
        from paddle_tpu.serving.sampling import _device_masked_logits

        logits = np.zeros((1, 64), np.float32)
        logits[0, 7] = 30.0                       # tail probs ~5e-13
        z = _device_masked_logits(
            jnp.asarray(logits), jnp.ones((1,)),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,)))
        assert np.isfinite(np.asarray(z)).all(), "tail truncated"
        # and < 1.0 still restricts (here: to the dominant token)
        z2 = _device_masked_logits(
            jnp.asarray(logits), jnp.ones((1,)),
            jnp.zeros((1,), jnp.int32), jnp.full((1,), 0.9))
        kept = np.asarray(z2)[0] > -1e29
        assert kept.sum() == 1 and kept[7]

    def test_same_key_same_token_advanced_key_differs(self):
        rs = np.random.RandomState(4)
        logits = jnp.asarray(rs.randn(1, 64), jnp.float32)
        args = (jnp.ones((1,)), jnp.zeros((1,), jnp.int32),
                jnp.ones((1,)))
        k0 = _keys(1, base=7)
        t1, k1 = device_sample(logits, *args, k0)
        t2, k2 = device_sample(logits, *args, k0)
        assert int(t1[0]) == int(t2[0])          # re-seed → same stream
        assert np.array_equal(np.asarray(k1), np.asarray(k2))
        assert not np.array_equal(np.asarray(k0), np.asarray(k1))
        # key advancement is real AND replayable: continuing from the
        # advanced key yields the same two-token stream a re-seeded
        # replay from k0 reproduces (the preempt-resume contract in
        # miniature)
        t3, _ = device_sample(logits, *args, k1)
        r1, rk = device_sample(logits, *args, k0)
        r2, _ = device_sample(logits, *args, rk)
        assert [int(t1[0]), int(t3[0])] == [int(r1[0]), int(r2[0])]


class TestHostOracleDtype:
    def test_float32_explicit_not_float64(self):
        """The bugfix pin: a logit difference below f32 resolution must
        be invisible (both values round to the same float32, argmax
        takes the first).  The old float64 path saw the difference and
        returned index 1."""
        logits = np.asarray([1.0, 1.0 + 1e-9, 0.0], np.float64)
        assert sample(logits, SamplingParams()) == 0
        # and the distribution math stays in-range/finite in f32
        p = SamplingParams(temperature=1.0)
        tok = sample(logits, p, np.random.RandomState(0))
        assert tok in (0, 1, 2)

    def test_extreme_logits_no_overflow(self):
        # f32 softmax of widely-spread logits: max-subtraction keeps it
        # finite; the winner dominates
        logits = np.asarray([300.0, -300.0, 0.0], np.float32)
        p = SamplingParams(temperature=1.0)
        draws = {sample(logits, p, np.random.RandomState(i))
                 for i in range(50)}
        assert draws == {0}

    def test_validation(self):
        with pytest.raises(ValueError):
            SamplingParams(top_p=0.0)
        with pytest.raises(ValueError):
            SamplingParams(top_p=1.5)
        with pytest.raises(ValueError):
            SamplingParams(temperature=-0.1)
        with pytest.raises(ValueError):
            SamplingParams(top_k=-1)


class TestDeviceSampler:
    def test_stage_and_reset_roundtrip(self):
        s = DeviceSampler(3)
        s.stage_slot(1, SamplingParams(temperature=0.5, top_k=4,
                                       top_p=0.9, seed=42), 42)
        assert float(np.asarray(s.temps.numpy())[1]) == pytest.approx(0.5)
        assert int(np.asarray(s.top_ks.numpy())[1]) == 4
        key = np.asarray(s.keys.numpy())[1]
        assert key.any()                         # seeded, not zeros
        np.testing.assert_array_equal(
            key, np.asarray(jax.random.PRNGKey(42)))
        s.reset()
        assert not np.asarray(s.keys.numpy()).any()
        assert np.asarray(s.top_ps.numpy()).tolist() == [1.0] * 3

    def test_sample_slot_updates_only_its_lane(self):
        rs = np.random.RandomState(5)
        s = DeviceSampler(3)
        s.stage_slot(0, SamplingParams(), 1)
        s.stage_slot(2, SamplingParams(temperature=1.0, seed=9), 9)
        logits = jnp.asarray(rs.randn(64), jnp.float32)
        tok = s.sample_slot(jnp.int32(2), logits)
        toks = np.asarray(s.tokens.numpy())
        assert toks[2] == int(np.asarray(tok))
        assert toks[0] == toks[1] == 0           # untouched lanes
        np.testing.assert_array_equal(
            np.asarray(s.keys.numpy())[0],
            np.asarray(jax.random.PRNGKey(1)))   # slot 0 key unmoved

    def test_greedy_engine_reproducible_with_seeds(self):
        """Engine-level: two identical seeded-sampling runs produce
        identical outputs through the compiled on-device path (the
        cross-run determinism the old host RandomState gave)."""
        import paddle_tpu as paddle
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny
        from paddle_tpu.serving import Engine

        paddle.seed(0)
        eng = Engine(GPTForCausalLM(gpt_tiny()), num_slots=2,
                     max_seq=32, min_bucket=8, block_size=8)
        eng.warmup()
        sp = dict(max_new_tokens=5,
                  sampling=SamplingParams(temperature=1.0, top_k=12,
                                          top_p=0.95, seed=123))
        a = eng.add_request([3, 1, 4], **sp)
        eng.run()
        b = eng.add_request([3, 1, 4], **sp)
        eng.run()
        assert a.output_ids == b.output_ids
        assert all(0 <= t < 128 for t in a.output_ids)
