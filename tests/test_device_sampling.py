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

from paddle_tpu.serving import sampling
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


# -- ISSUE 29: cut-offs searched, not sorted for ------------------------------
#
# The oracle is the sampler as it was before: one sort of the whole
# vocabulary for every row of every call.  The sampler now searches both
# cut-offs (the largest value a count or a mass still reaches its mark at,
# bit by bit), and computes none where no live row samples; neither may
# change a token.

def _oracle_masked_logits(logits, temps, top_ks, top_ps):
    """``_device_masked_logits`` as PR 28 had it, plus the distance of the
    nucleus' compare from its cut: ``min |csum - p - top_p|`` of each
    row."""
    V = logits.shape[-1]
    z = logits / temps[:, None]
    k = jnp.where(top_ks > 0, jnp.clip(top_ks, 1, V), V)
    z_desc = jnp.sort(z, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(z_desc, (k - 1)[:, None], axis=1)
    z = jnp.where(z >= kth, z, sampling._NEG_INF)
    z_desc = jnp.where(z_desc >= kth, z_desc, sampling._NEG_INF)
    p_desc = jax.nn.softmax(z_desc, axis=-1)
    csum = jnp.cumsum(p_desc, axis=-1)
    keep_n = jnp.sum((csum - p_desc) < top_ps[:, None], axis=-1)
    z_thr = jnp.take_along_axis(z_desc, (keep_n - 1)[:, None], axis=1)
    margin = jnp.min(jnp.abs(csum - p_desc - top_ps[:, None]), axis=-1)
    return jnp.where((z >= z_thr) | (top_ps[:, None] >= 1.0),
                     z, sampling._NEG_INF), margin


@jax.jit
def _oracle_sample(logits, temps, top_ks, top_ps, keys):
    """``device_sample`` as PR 28 had it."""
    logits = logits.astype(jnp.float32)
    greedy = temps <= 0.0
    z, _ = _oracle_masked_logits(logits, jnp.where(greedy, 1.0, temps),
                                 top_ks, top_ps)
    split = jax.vmap(jax.random.split)(keys)
    new_keys, subkeys = split[:, 0], split[:, 1]
    drawn = jax.vmap(jax.random.categorical)(subkeys, z)
    tokens = jnp.where(greedy, jnp.argmax(logits, axis=-1),
                       drawn).astype(jnp.int32)
    return tokens, new_keys


_oracle_masked = jax.jit(_oracle_masked_logits)
_new_masked = jax.jit(sampling._device_masked_logits)
_new_sample = jax.jit(device_sample)

VOCAB = 50304
#: The nucleus compares a float32 sum of probabilities with ``top_p``; the
#: search adds them in another order than the sort's running sum did, so
#: the two may differ in the sum's last bits — by the error of adding V
#: numbers pairwise, ``log2(V)`` ulp.  Only a row whose compare the oracle
#: decides by less than that may come out with another kept set, and the
#: tests say how many do: none on these seeds.
CUT_ULPS = 16


@pytest.fixture(scope="module")
def rows():
    """Seeded ``f32[8, 50304]`` logits, flat to peaked."""
    rs = np.random.RandomState(29)
    scale = np.asarray([0.5, 1, 2, 3, 4, 6, 8, 10], np.float32)[:, None]
    return jnp.asarray(rs.randn(8, VOCAB).astype(np.float32) * scale)


def _lanes(n, temp, top_k, top_p):
    return (jnp.full((n,), temp, jnp.float32),
            jnp.full((n,), top_k, jnp.int32),
            jnp.full((n,), top_p, jnp.float32))


def _kept(z):
    return np.asarray(z) > -1e29


def _far_from_the_cut(margin, top_p):
    return (np.asarray(margin) > CUT_ULPS * np.spacing(np.float32(top_p))) \
        | (top_p >= 1.0)


@pytest.mark.parametrize("temp", [0.0, 0.8])
@pytest.mark.parametrize("top_p", [0.5, 0.9, 1.0])
@pytest.mark.parametrize("top_k", [0, 1, 3, 50, 128, 129, 4000])
def test_kept_set_tokens_and_keys_equal_the_full_sorts(rows, top_k, top_p,
                                                       temp):
    temps, top_ks, top_ps = _lanes(8, temp, top_k, top_p)
    tempered = jnp.where(temps <= 0, 1.0, temps)
    want, margin = _oracle_masked(rows, tempered, top_ks, top_ps)
    got = _new_masked(rows, tempered, top_ks, top_ps)
    differ = (np.asarray(got) != np.asarray(want)).any(axis=1)
    assert not (differ & _far_from_the_cut(margin, top_p)).any()
    assert differ.sum() == 0                 # and none does on these seeds
    keys = _keys(8, base=top_k)
    toks, new_keys = _new_sample(rows, temps, top_ks, top_ps, keys)
    o_toks, o_keys = _oracle_sample(rows, temps, top_ks, top_ps, keys)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(o_toks))
    np.testing.assert_array_equal(np.asarray(new_keys), np.asarray(o_keys))


@pytest.mark.parametrize("tied", [3, 200])
def test_ties_at_the_kth_value_keep_every_tied_token(tied):
    """``z >= kth`` keeps every token tied with the k-th, three or two
    hundred, and they all weigh in the nucleus."""
    row = np.full((1, VOCAB), -4.0, np.float32)
    row[0, [7, 9]] = [2.0, 1.5]
    row[0, 100:100 + tied] = 1.0             # the 3rd value, `tied` times
    lanes = _lanes(1, 1.0, 3, 0.9)
    got = _kept(_new_masked(jnp.asarray(row), *lanes))[0]
    want = _kept(_oracle_masked(jnp.asarray(row), *lanes)[0])[0]
    assert got.sum() == 2 + tied and (got == want).all()
    # a nucleus that ends above three tied tokens drops them all; two
    # hundred carry the mass, and stay whole
    lanes = _lanes(1, 1.0, 3, 0.3)
    got = _kept(_new_masked(jnp.asarray(row), *lanes))[0]
    want = _kept(_oracle_masked(jnp.asarray(row), *lanes)[0])[0]
    assert (got == want).all() and got.sum() == (1 if tied == 3 else 202)
    # without the nucleus the tie is kept whole too
    lanes = _lanes(1, 1.0, 3, 1.0)
    assert _kept(_new_masked(jnp.asarray(row), *lanes))[0].sum() == 2 + tied


def test_signed_zeros_are_one_value():
    """``-0.0 >= 0.0`` in the sort's order; the search's integer keys have
    to agree."""
    row = np.full((1, 256), -3.0, np.float32)
    row[0, :4] = [0.0, -0.0, 1.0, -0.0]
    for lanes in (_lanes(1, 1.0, 2, 1.0), _lanes(1, 1.0, 0, 0.7)):
        got = _new_masked(jnp.asarray(row), *lanes)
        want, _ = _oracle_masked(jnp.asarray(row), *lanes)
        np.testing.assert_array_equal(_kept(got), _kept(want))
        assert _kept(got)[0, :4].all()


def test_a_row_with_fewer_than_k_legal_tokens(rows):
    """A grammar mask puts ``-1e30`` into a row before the sampler sees it:
    with 5 legal tokens and ``top_k`` 50 the k-th value is ``-1e30 / temp``,
    as it was."""
    legal = [3, 77, 1000, 20000, 50303]
    row = np.full((1, VOCAB), sampling._NEG_INF, np.float32)
    row[0, legal] = np.asarray(rows)[0, legal]
    lanes = _lanes(1, 0.8, 50, 0.9)
    want, _ = _oracle_masked(jnp.asarray(row), *lanes)
    got = _new_masked(jnp.asarray(row), *lanes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert set(np.flatnonzero(np.asarray(got)[0] > -1e29)) <= set(legal)
    for base in range(4):
        toks, _ = _new_sample(jnp.asarray(row), *lanes, _keys(1, base))
        o_toks, _ = _oracle_sample(jnp.asarray(row), *lanes, _keys(1, base))
        assert int(toks[0]) == int(o_toks[0]) and int(toks[0]) in legal


@pytest.mark.parametrize("top_p", [0.7, 1.0])
@pytest.mark.parametrize("top_k", [0, 3, 64, 100])
def test_a_tiny_vocabulary(top_k, top_p):
    """``V`` 64, under every ``top_k`` a mix sends but 3."""
    rs = np.random.RandomState(64)
    logits = jnp.asarray((rs.randn(8, 64) * 2).astype(np.float32))
    lanes = _lanes(8, 0.8, top_k, top_p)
    want, _ = _oracle_masked(logits, *lanes)
    np.testing.assert_array_equal(
        np.asarray(_new_masked(logits, *lanes)), np.asarray(want))
    toks, keys = _new_sample(logits, *lanes, _keys(8))
    o_toks, o_keys = _oracle_sample(logits, *lanes, _keys(8))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(o_toks))
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(o_keys))


def test_an_open_nucleus_beside_them_leaves_the_other_rows_tokens(rows):
    """A row's cut-offs are its own: one row with no ``top_k`` (the request
    that made every step sort) changes no other row's token."""
    temps, top_ks, top_ps = _lanes(8, 0.8, 50, 0.9)
    keys = _keys(8, base=5)
    alone, k_alone = _new_sample(rows, temps, top_ks, top_ps, keys)
    beside, k_beside = _new_sample(rows, temps, top_ks.at[0].set(0),
                                   top_ps, keys)
    np.testing.assert_array_equal(np.asarray(beside)[1:],
                                  np.asarray(alone)[1:])
    np.testing.assert_array_equal(np.asarray(k_beside), np.asarray(k_alone))


GREEDY, K50, NUCLEUS = (0.0, 0, 1.0), (0.8, 50, 0.9), (0.8, 0, 0.9)


@pytest.fixture
def cutoffs_run(monkeypatch):
    """One entry for each time the cut-offs are RUN (a conditional runs one
    of its branches)."""
    ran = []
    real = sampling._device_masked_logits

    def spy(*args):
        jax.debug.callback(lambda: ran.append("sampled"))
        return real(*args)

    monkeypatch.setattr(sampling, "_device_masked_logits", spy)
    return ran


@pytest.mark.parametrize("lanes, live, want", [
    ([GREEDY] * 4, [1, 1, 1, 1], "greedy"),
    ([GREEDY, K50, GREEDY, GREEDY], [1, 1, 1, 0], "sampled"),
    ([K50, NUCLEUS, GREEDY, GREEDY], [1, 1, 1, 1], "sampled"),
    # an idle row keeps the lanes of the request that left it
    ([GREEDY, K50, NUCLEUS, GREEDY], [1, 0, 0, 1], "greedy"),
    # a greedy row's top_k / top_p lanes are never read
    ([(0.0, 0, 0.9), (0.0, 4000, 0.5), GREEDY, GREEDY], [1, 1, 1, 1],
     "greedy"),
], ids=["all_greedy", "top_k_50", "open_nucleus", "idle_sampled_lanes",
        "greedy_with_lanes"])
def test_no_cutoff_is_computed_unless_a_live_row_samples(rows, cutoffs_run,
                                                         lanes, live, want):
    """What the program runs, against what the host says of the same rows
    (``sampler_path``, the ``engine.step`` span's attribute), and the live
    rows' tokens against the full sort's."""
    temps, top_ks, top_ps = (jnp.asarray(c, dt) for c, dt in zip(
        zip(*lanes), (jnp.float32, jnp.int32, jnp.float32)))
    keys = _keys(4, base=11)
    toks, new_keys = device_sample(rows[:4], temps, top_ks, top_ps, keys,
                                   live=jnp.asarray(live) > 0)
    jax.effects_barrier()
    assert (cutoffs_run or ["greedy"]) == [want]
    assert sampling.sampler_path(
        SamplingParams(temperature=t, top_k=k, top_p=p)
        for (t, k, p), on in zip(lanes, live) if on) == want
    o_toks, o_keys = _oracle_sample(rows[:4], temps, top_ks, top_ps, keys)
    on = np.asarray(live) > 0
    np.testing.assert_array_equal(np.asarray(toks)[on],
                                  np.asarray(o_toks)[on])
    np.testing.assert_array_equal(np.asarray(new_keys), np.asarray(o_keys))
