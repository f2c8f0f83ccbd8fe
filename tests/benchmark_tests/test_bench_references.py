"""The plain references against the program's own forward at tiny size, the
seeded weights, and the lower-precision control."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testlib import tiny
from benchmarks.adapters import _load
from benchmarks.harness import weights
from benchmarks.harness.manifest import load_module

SEED = 2 ** 31 + 7


def _setup(family):
    cfg = tiny("tiny_" + family)
    ref = load_module("references", family)
    adapter = load_module("adapters", family)
    d = ref.dims(cfg)
    tree = weights.make(ref.weight_shapes(cfg), SEED, jnp.float32)
    return cfg, ref, adapter, d, tree


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_reference_agrees_with_the_programs_forward(family):
    import paddle_tpu as paddle

    cfg, ref, adapter, d, tree = _setup(family)
    model = adapter.build_model(cfg)
    model.eval()
    n = _load.load(model, adapter, tree, d)
    assert n == sum(int(np.prod(s)) for s, _k in
                    ref.weight_shapes(cfg).values())
    toks = np.random.default_rng(0).integers(0, d["vocab"], (48,))
    prog = np.asarray(model(paddle.to_tensor(toks[None]))._value())[0]
    h = ref.hidden(tree, jnp.asarray(toks), d)
    want = np.asarray(ref.logits_rows(
        {k: tree[k] for k in ref.HEAD_KEYS}, h, d))
    # both float32 at highest precision (conftest): rounding only
    assert np.abs(prog - want).max() < 2e-5 * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_control_is_a_coarser_forward_but_the_same_model(family):
    cfg, ref, _adapter, d, tree = _setup(family)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, d["vocab"], (40,)))
    head = {k: tree[k] for k in ref.HEAD_KEYS}
    want = np.asarray(ref.logits_rows(head, ref.hidden(tree, toks, d), d))
    got = np.asarray(ref.logits_rows(
        head, ref.hidden(tree, toks, d, control=True), d, control=True))
    err = np.abs(got - want).max()
    assert 1e-4 < err < 0.1 * np.abs(want).max()


def test_weights_are_seeded_bf16_exact_and_parts_equal_the_whole():
    cfg = tiny("tiny_llama")
    ref = load_module("references", "llama")
    shapes = ref.weight_shapes(cfg)
    a = weights.make(shapes, SEED, jnp.float32)
    b = weights.make(shapes, SEED, jnp.float32)
    c = weights.make(shapes, SEED + 1, jnp.float32)
    names = ref.layer_names(1)
    part = weights.make(shapes, SEED, jnp.bfloat16, only=names)
    for k in shapes:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
        assert np.array_equal(
            np.asarray(a[k]),
            np.asarray(a[k].astype(jnp.bfloat16).astype(jnp.float32)))
    assert not np.array_equal(np.asarray(a[names[1]]), np.asarray(c[names[1]]))
    assert sorted(part) == sorted(names)
    for k in names:
        assert part[k].dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(part[k].astype(jnp.float32)),
                              np.asarray(a[k]))
    gains = np.asarray(a["norm.g"])
    assert abs(gains.mean() - 1.0) < 0.1 and gains.std() > 0.02


def test_gpt_training_loss_is_the_mean_token_cross_entropy():
    cfg, ref, _adapter, d, tree = _setup("gpt")
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.integers(0, d["vocab"], (2, 16)), jnp.int32)
    y = jnp.asarray(rng.integers(0, d["vocab"], (2, 16)), jnp.int32)
    total = float(ref.loss_rows(tree, x, y, d))
    head = {k: tree[k] for k in ref.HEAD_KEYS}
    by_hand = 0.0
    for r in range(2):
        lg = np.asarray(ref.logits_rows(head, ref.hidden(tree, x[r], d), d),
                        np.float64)
        lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
            + lg.max(-1)
        by_hand += float((lse - lg[np.arange(16), np.asarray(y[r])]).sum())
    assert total == pytest.approx(by_hand, rel=1e-5)
    assert abs(total / 32 - np.log(d["vocab"])) < 0.5


def test_layer_by_layer_gradient_equals_autodiff_of_the_loss():
    import jax

    cfg, ref, _adapter, d, tree = _setup("gpt")
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, d["vocab"], (2, 16)), jnp.int32)
    y = jnp.asarray(rng.integers(0, d["vocab"], (2, 16)), jnp.int32)
    want_l, want_g = jax.value_and_grad(
        lambda w: ref.loss_rows(w, x, y, d))(tree)
    got_l, got_g = ref.grad_rows(tree, x, y, d)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-6)
    assert sorted(got_g) == sorted(want_g)
    # some gradients are exactly zero in theory (a key bias shifts every
    # score of a row alike): compare on the scale of the whole gradient
    scale = max(float(jnp.abs(g).max()) for g in want_g.values())
    for k in want_g:
        assert float(jnp.abs(got_g[k] - want_g[k]).max()) < 1e-5 * scale, k
