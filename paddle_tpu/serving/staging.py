"""One slot's rows of the engine's per-slot device state, written by one
program.

An admission changes one row of several small ``[slots, ...]`` arrays: the
sampler's lanes (key, temperature, top-k, top-p, the grammar lanes) and every
table the cache addresses the slot through (a block table a pool, a summary
table, a state group's plan).  Written an array at a time they are a dozen
eager programs, each a dispatch and a host-to-device copy in front of the
prefill.  :class:`SlotStager` writes them all in one: the rows are made on the
host as numpy arrays of the arrays' own widths, packed into one ``int32``
vector (every array here is 32 bits an element, so a row is its own bits),
and one jitted program, its arrays donated like a compiled step's state,
unpacks the vector and sets the slot's row of each.

The same program serves the calls that change the tables and must leave the
lanes alone — the blocks let go behind a window after a first token, whose
key lane the prefill has advanced since: the lanes' rows ride in the vector
either way and a flag in it says whether they are taken.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SlotStager"]


class SlotStager:
    """The program over ``lanes`` (rows that are staged or kept, by the
    call) and ``tables`` (rows that every call writes): lists of persistable
    ``[slots, ...]`` tensors of 32-bit elements, read when a call is made so
    that a reset or a placement on a mesh in between is seen."""

    def __init__(self, lanes: Sequence, tables: Sequence):
        self.lanes, self.tables = list(lanes), list(tables)
        arrays = [t._value() for t in (*self.lanes, *self.tables)]
        if any(a.dtype.itemsize != 4 for a in arrays):
            raise TypeError("a staged array has 32-bit elements")
        shapes = [tuple(a.shape[1:]) for a in arrays]
        sizes = [int(np.prod(s, dtype=np.int64)) for s in shapes]
        n_lanes = len(self.lanes)
        self._zeros = [np.zeros(s, np.int32) for s in shapes[:n_lanes]]
        self._packed_size = 2 + sum(sizes)
        #: programs issued
        self.calls = 0

        def stage_slot(arrays, packed):
            slot, take_lanes = packed[0], packed[1] != 0
            out, lo = [], 2
            for i, (a, shape, n) in enumerate(zip(arrays, shapes, sizes)):
                row = jax.lax.bitcast_convert_type(
                    packed[lo:lo + n], a.dtype).reshape(shape)
                lo += n
                if i < n_lanes:
                    row = jnp.where(take_lanes, row, a[slot])
                out.append(a.at[slot].set(row))
            return out

        self._apply = jax.jit(stage_slot, donate_argnums=(0,))

    def stage(self, slot: int, lane_rows: Optional[Sequence],
              table_rows: Sequence) -> None:
        """Set ``slot``'s row of every table to ``table_rows`` and, unless
        ``lane_rows`` is None, of every lane to ``lane_rows`` (numpy values
        of the arrays' dtypes and row shapes)."""
        rows = [*(self._zeros if lane_rows is None else lane_rows),
                *table_rows]
        packed = np.concatenate(
            [np.array([slot, lane_rows is not None], np.int32)]
            + [np.ascontiguousarray(r).reshape(-1).view(np.int32)
               for r in rows])
        if packed.size != self._packed_size:
            raise ValueError(
                f"staged rows hold {packed.size - 2} 32-bit elements, the "
                f"arrays' rows {self._packed_size - 2}")
        tensors = (*self.lanes, *self.tables)
        new = self._apply([t._value() for t in tensors], packed)
        for t, a in zip(tensors, new):
            t._set_data(a)
        self.calls += 1
