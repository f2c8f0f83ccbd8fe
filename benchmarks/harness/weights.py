"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program's model gets
them through its adapter and the plain reference gets the same tree, so the
reference takes nothing that the program has made.  Every value is exactly
representable in bfloat16, whatever ``dtype`` holds it: a model served in
bf16 and a reference that upcasts layer by layer then start from identical
numbers, and AMP-O2's f32 master weights equal the seeded tree exactly.
"""
from __future__ import annotations

import functools


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**48 (the driver's seeds
    exceed 32 signed bits)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _builder(spec: tuple, dtype_name: str):
    """One jitted maker per list of ``(shape, kind)``: a leaf's place in the
    tree is an argument, so every layer of a model shares one program."""
    import jax
    import jax.numpy as jnp

    def build(key, places):
        out = []
        for n, (shape, kind) in enumerate(spec):
            z = jax.random.normal(jax.random.fold_in(key, places[n]), shape,
                                  jnp.float32)
            w = 1.0 + 0.1 * z if kind == "scale" else 0.02 * z
            # reduce_precision, not a cast there and back: XLA:TPU removes
            # such a pair of converts and would leave float32 values
            w = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
            out.append(w.astype(dtype_name))
        return out

    return jax.jit(build)


def make(shapes: dict, seed: int, dtype, only=None):
    """``{name: array}`` for ``shapes = {name: (shape, kind)}``; ``kind`` is
    ``"normal"`` (std 0.02: matrices, embeddings, biases) or ``"scale"``
    (1 + 0.1 N(0,1): norm gains, so that a dropped gain shows).  ``only``
    names the leaves to make now (a layer's, say): a leaf's values depend on
    the seed and its place in the whole tree alone, so parts made apart equal
    the parts of a tree made whole."""
    import jax.numpy as jnp

    index = {name: i for i, name in enumerate(sorted(shapes))}
    names = sorted(shapes) if only is None else list(only)
    spec = tuple((tuple(shapes[n][0]), shapes[n][1]) for n in names)
    places = jnp.asarray([index[n] for n in names], jnp.uint32)
    leaves = _builder(spec, jnp.dtype(dtype).name)(seed_key(seed), places)
    return dict(zip(names, leaves))
