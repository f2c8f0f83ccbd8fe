"""Pallas TPU grouped matmul for a dropless expert layer.

``moe_grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs [M, K]`` are
sorted by expert, group ``g`` is the ``group_sizes[g]`` rows after those of
groups ``0..g-1``, and each row is multiplied by its own group's matrix of
``rhs [G, K, N]``.  Rows past ``sum(group_sizes)`` belong to no group (in
the expert layer: assignments to experts this chip does not hold) and come
back zero.  No capacity, no padding of a group to a tile, no dropped row.

The grid walks *(tile of N) x (visit)*, a visit being one ``tm``-row tile of
``lhs`` under one group; a tile that two groups share is visited once for
each and every visit stores only its own group's rows (the scheme of jax's
``pallas/ops/tpu/megablox``, forward only and without its K tiling, its
sharding offsets and its transposes).  The visits are listed outside the
kernel, handed over in scalar prefetch, and their number is the grid's
(dynamic) bound: an expert that got no row is never visited, so its weights
are never read — at decode the kernel reads the touched experts' weights and
nothing else — and the tiles past the last group cost nothing.

``K`` is taken whole (2,048 and 768, or 2,304 and 896, in the models served
here: a ``[128, K]`` tile of rows and a ``[K, 512]`` tile of weights are 0.5
and 2 MiB in bf16; an N that 512 does not divide takes its largest divisor in
whole lane tiles whose weights tile fits, 896 of 1,792).
The ``pallas_call`` is named ``moe_grouped_matmul``, which is the name the
device trace shows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a tile (a whole number of bf16 sublane tiles)
TILE_M = 128
#: columns of a tile when N is a multiple of it; else (:func:`_tile_n`) the
#: most whole lane tiles that divide N and keep a ``[K, tn]`` tile of weights
#: within :data:`RHS_TILE_BYTES`
TILE_N = 512
#: a weights tile is double-buffered under v5e's 16 MiB of scoped VMEM: N
#: whole at ``[2304, 1792]`` of bf16 (8.3 MB a buffer) does not compile
RHS_TILE_BYTES = 9 << 19


def _tile_n(K: int, N: int, itemsize: int) -> int:
    if N % TILE_N == 0:
        return TILE_N
    fits = [t for t in range(128, N + 1, 128)
            if N % t == 0 and K * t * itemsize <= RHS_TILE_BYTES]
    return max(fits, default=N)


def _visits(group_sizes, tiles_m: int, tm: int):
    """``(offsets [G+1], group [V], tile [V], n)``: visit ``v < n`` is tile
    ``tile[v]`` of the rows under group ``group[v]``; groups in order, a
    group's tiles in order, empty groups absent.  ``V = tiles_m + G - 1``
    bounds ``n``."""
    G = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first_tile = starts // tm
    tiles = jnp.where(group_sizes == 0, 0, (ends + tm - 1) // tm - first_tile)
    n = tiles.sum()
    V = tiles_m + G - 1
    group = jnp.repeat(jnp.arange(G, dtype=jnp.int32), tiles,
                       total_repeat_length=V)
    first_visit = jnp.cumsum(tiles) - tiles
    tile = first_tile[group] + jnp.arange(V, dtype=jnp.int32) \
        - first_visit[group]
    tile = jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               ends.astype(jnp.int32)])
    return offsets, group, tile, n.astype(jnp.int32)


def _kernel(off_ref, group_ref, tile_ref, lhs_ref, rhs_ref, out_ref, *, tm):
    v = pl.program_id(1)
    g = group_ref[v]
    # DEFAULT, whatever the process-wide matmul precision (Mosaic refuses
    # bf16 operands at "highest")
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  precision=jax.lax.Precision.DEFAULT,
                  preferred_element_type=jnp.float32)
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = jnp.logical_and(row >= off_ref[g], row < off_ref[g + 1])
    # the other rows of the tile are another visit's (or no group's: the
    # wrapper zeroes those)
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


def moe_grouped_matmul(lhs, rhs, group_sizes, *, interpret=False):
    """``out[r] = lhs[r] @ rhs[group of r]``; zero for rows in no group.

    Args:
        lhs:         ``[M, K]`` rows sorted by group.
        rhs:         ``[G, K, N]`` one matrix a group.
        group_sizes: ``[G]`` int32; ``sum <= M``.

    Returns:
        ``[M, N]`` in ``lhs``'s dtype.
    """
    M, K = lhs.shape
    G, _, N = rhs.shape
    tm = TILE_M
    tn = _tile_n(K, N, rhs.dtype.itemsize)
    pad = -M % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tiles_m = (M + pad) // tm
    group_sizes = group_sizes.astype(jnp.int32)
    offsets, group, tile, n = _visits(group_sizes, tiles_m, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N // tn, n),
        in_specs=[
            pl.BlockSpec((tm, K), lambda j, v, off, gr, ti: (ti[v], 0)),
            pl.BlockSpec((None, K, tn),
                         lambda j, v, off, gr, ti: (gr[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, v, off, gr, ti: (ti[v], j)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M + pad, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(offsets, group, tile, lhs, rhs)
    in_group = jnp.arange(M + pad, dtype=jnp.int32) < offsets[-1]
    return jnp.where(in_group[:, None], out, 0)[:M]


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """The oracle: a loop over the groups, each over all rows, masked."""
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(lhs.shape[0])
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        mine = jnp.logical_and(row >= ends[g] - group_sizes[g], row < ends[g])
        out = out + jnp.where(
            mine[:, None],
            jnp.dot(lhs, rhs[g], preferred_element_type=jnp.float32), 0)
    return out.astype(lhs.dtype)
