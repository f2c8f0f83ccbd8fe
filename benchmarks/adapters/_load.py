"""Hands a seeded weight tree to the program's model, leaf by leaf, so that
the program's own initial value of a leaf is freed as its replacement comes."""
from __future__ import annotations


def load(model, adapter, tree: dict, d: dict) -> int:
    """Sets every ``state_dict`` entry of ``model`` from ``tree`` (cast to
    the entry's dtype); raises on a key either side lacks.  Returns the
    number of elements set."""
    import jax.numpy as jnp

    own = model.state_dict()
    seen, n = set(), 0
    for key, arr in adapter.program_leaves(tree, d):
        if key not in own:
            raise KeyError(f"the program's model has no {key!r}")
        t = own[key]
        if tuple(t.shape) != tuple(arr.shape):
            raise ValueError(f"{key}: shape {arr.shape} vs {tuple(t.shape)}")
        t._set_data(jnp.asarray(arr, dtype=t._value().dtype))
        seen.add(key)
        n += int(arr.size)
    missing = sorted(set(own) - seen)
    if missing:
        raise KeyError(f"no seeded weights for {missing[:4]} ...")
    return n
