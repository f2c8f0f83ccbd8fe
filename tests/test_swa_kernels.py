"""The paged kernels under a window (ISSUE 38): decode and tail prefill
against a numpy oracle written from the rule — query ``i`` reads keys ``j``
with ``i - window < j <= i`` (``window`` 0: every ``j <= i``) — in interpret
mode, with lengths on and off block and chunk edges, blocks behind the window
released to the scratch block (as the window group's table has them), and the
jnp reference path (``kernel="reference"``) against the same oracle.
"""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.cached_attention import (
    block_prefill_attention, cached_attention, gather_block_kv,
)
from paddle_tpu.ops.pallas.mla_attention_kernel import decode_work_list
from paddle_tpu.ops.pallas.paged_attention_kernel import (
    _decode_call, _prefill_call, first_chunks, paged_decode_attention_kernel,
    paged_prefill_attention_kernel, prefill_places, prefill_plan,
    prefill_tile_chunks, prefill_work_list,
)

BS, HKV, H, D = 8, 2, 4, 16
WINDOW = 40            # off the 8-token blocks and the 32-token chunks


def _pools(rs, nb, hkv=HKV):
    return (jnp.asarray(rs.randn(nb, BS, hkv, D), jnp.float32),
            jnp.asarray(rs.randn(nb, BS, hkv, D), jnp.float32))


def _oracle(q, k, v, qpos, window):
    """``q [n, H, D]`` at absolute positions ``qpos [n]`` over the contiguous
    ``k``/``v [T, HKV, D]``: float64, one softmax a row."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    k, v = (np.repeat(a, q.shape[1] // k.shape[1], axis=1) for a in (k, v))
    kpos = np.arange(k.shape[0])
    out = np.zeros_like(q)
    for r, p in enumerate(qpos):
        keep = kpos <= p
        if window:
            keep &= kpos > p - window
        s = np.einsum("hd,khd->hk", q[r], k[keep]) / np.sqrt(D)
        s = np.exp(s - s.max(axis=1, keepdims=True))
        out[r] = np.einsum("hk,khd->hd", s / s.sum(axis=1, keepdims=True),
                           v[keep])
    return out


def _table(rs, slots, mb, nb):
    """Distinct blocks a slot, none the scratch block."""
    return rs.permutation(np.arange(1, nb))[:slots * mb].reshape(slots, mb)


def _released_behind(tbl, lengths, window):
    """The table as a window group keeps it: blocks whose every key lies
    behind the query's window point at the scratch block."""
    tbl = np.array(tbl)
    if window:
        for b, n in enumerate(lengths):
            tbl[b, :max(0, n - window + 1) // BS] = 0
    return tbl


#: on and off a block's (8) and a chunk's (32) edge, under and over a window
LENGTHS = (0, 7, 8, 31, 32, 39, 40, 41, 63, 64, 100, 127)


#: two query heads a KV head go a row at a time on the VPU, eight are the
#: rows of one matmul (``MXU_QUERY_ROWS``)
@pytest.mark.parametrize("heads", [H, 8 * HKV])
@pytest.mark.parametrize("window", [0, WINDOW, 8, 1])
def test_decode_kernel_against_oracle(window, heads):
    rs = np.random.RandomState(window)
    slots, mb = len(LENGTHS), 16
    nb = slots * mb + 1
    kp, vp = _pools(rs, nb)
    tbl = _table(rs, slots, mb, nb)
    lens = np.asarray(LENGTHS, np.int32)
    q = jnp.asarray(rs.randn(slots, 1, heads, D), jnp.float32)
    active = np.ones(slots, np.int32)
    active[3] = 0
    full = np.asarray(gather_block_kv(kp, jnp.asarray(tbl))), \
        np.asarray(gather_block_kv(vp, jnp.asarray(tbl)))
    want = np.stack([_oracle(q[b], full[0][b], full[1][b], [lens[b]], window)
                     for b in range(slots)])
    # a chunk of 4 blocks, so that lengths cross several chunks
    got = _decode_call(q, kp, vp, jnp.asarray(
        _released_behind(tbl, lens, window), jnp.int32), jnp.asarray(lens),
        jnp.asarray(active), first_chunks(jnp.asarray(lens), window, 4 * BS),
        chunk_tokens=4 * BS, window=window, interpret=True)
    got = np.asarray(got)
    assert np.all(got[3] == 0)                  # an idle slot's row
    live = active > 0
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    # the entry the pool calls, with its own chunk size
    got = np.asarray(paged_decode_attention_kernel(
        q, kp, vp, jnp.asarray(tbl, jnp.int32), jnp.asarray(lens),
        jnp.asarray(active), window=window, interpret=True))
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    # and the jnp reference path
    ref = cached_attention(
        paddle.to_tensor(np.asarray(q)), paddle.to_tensor(full[0]),
        paddle.to_tensor(full[1]), paddle.to_tensor(lens),
        window=window).numpy()
    np.testing.assert_allclose(ref[live], want[live], rtol=2e-5, atol=2e-5)


def test_window_work_list_holds_only_chunks_that_meet_the_window():
    lens = jnp.asarray([0, 31, 32, 100, 127, 50], jnp.int32)
    active = jnp.asarray([1, 1, 1, 1, 1, 0], jnp.int32)
    ct, mc = 32, 4
    first = first_chunks(lens, WINDOW, ct)
    slot, chunk, n = (np.asarray(a) for a in decode_work_list(
        lens, active, ct, mc, first))
    want = []
    for b, (length, act) in enumerate(zip(np.asarray(lens),
                                          np.asarray(active))):
        lo = max(0, int(length) - WINDOW + 1)
        want += [(b, c) for c in range(mc) if act
                 and c * ct <= length and (c + 1) * ct - 1 >= lo]
    assert int(n) == len(want)
    assert list(zip(slot[:n], chunk[:n])) == want
    # with no window every chunk up to the length is listed
    slot0, chunk0, n0 = decode_work_list(lens, active, ct, mc)
    assert int(n0) == sum(int(l) // ct + 1 for l, a in zip(
        np.asarray(lens), np.asarray(active)) if a)


@pytest.mark.parametrize("window", [0, WINDOW, 8])
@pytest.mark.parametrize("start,S", [(0, 16), (0, 64), (48, 16), (104, 24),
                                     (64, 64)])
def test_prefill_kernel_against_oracle(window, start, S):
    rs = np.random.RandomState(start + S + window)
    mb = 24
    nb = mb + 1
    kp, vp = _pools(rs, nb)
    row = _table(rs, 1, mb, nb)[0]
    q = jnp.asarray(rs.randn(1, S, H, D), jnp.float32)
    k, v = (np.asarray(gather_block_kv(p, jnp.asarray(row[None])))[0]
            for p in (kp, vp))
    want = _oracle(q[0], k, v, start + np.arange(S), window)
    # blocks wholly behind the first query's window are released
    released = _released_behind(row[None], [start], window)[0]
    got = paged_prefill_attention_kernel(
        q, kp, vp, jnp.asarray(released, jnp.int32),
        jnp.asarray([start], jnp.int32), window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-5,
                               atol=2e-5)
    ref = block_prefill_attention(
        paddle.to_tensor(np.asarray(q)), paddle.to_tensor(k[None]),
        paddle.to_tensor(v[None]), paddle.to_tensor(np.int32(start)),
        window=window).numpy()
    np.testing.assert_allclose(ref[0], want, rtol=2e-5, atol=2e-5)


def _pallas_calls(jaxpr):
    """``(grid, the operands' shapes)`` of every ``pallas_call`` under
    ``jaxpr``, inside jitted entries too."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append((tuple(e.params["grid_mapping"].grid),
                        [tuple(v.aval.shape) for v in e.invars]))
        for v in e.params.values():
            if hasattr(v, "jaxpr"):
                out += _pallas_calls(v.jaxpr)
    return out


def test_prefill_places_cover_a_window_and_a_tile_not_the_row():
    """The kernel's grid is a work list of (query tile, key chunk) items: a
    step an item (the bound is the list's live count), the list itself in a
    static number of places.  With a window a tile has at most ``(window +
    tile) / chunk + 2`` of them, whatever the row's length; with none, the
    row's chunks."""
    import jax

    S = 64
    kp, vp = _pools(np.random.RandomState(0), 4)
    q = jnp.zeros((1, S, H, D), jnp.float32)

    def places(window, mb):
        jaxpr = jax.make_jaxpr(lambda *a: paged_prefill_attention_kernel(
            *a, window=window, interpret=True))(
            q, kp, vp, jnp.zeros((mb,), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.int32(S))
        ((grid, shapes),) = _pallas_calls(jaxpr.jaxpr)
        (bound,) = grid
        assert not isinstance(bound, int)        # no idle place is visited
        # the operands open with the scalars: the bound, the block row, start
        # and length, then the list's tiles and chunks
        assert shapes[:4] == [(), (mb,), (1,), (1,)]
        assert shapes[4] == shapes[5]
        return shapes[4]

    for mb in (64, 512):
        ts, ct = prefill_plan(S, HKV, H // HKV, D, 4, BS, mb)
        assert (ts, ct) == (64, 256)             # the decode kernel's chunk
        assert places(0, mb) == (S // ts * (mb * BS // ct),)
        assert places(WINDOW, mb) == (S // ts * ((WINDOW + ts - 2) // ct
                                                 + 2),)
    # the items of a tail, by the host's count of the same list: the tiles
    # that hold a real row times a window's chunks, however long the row is
    ts, ct, mb = 16, 32, 512
    places = prefill_places(S, ts, ct, mb, BS, WINDOW)
    assert places == S // ts * ((WINDOW + ts - 2) // ct + 2)
    for start in (0, 104, 2048, 4000):
        for real in (1, 16, 37, 64):
            n, n0 = (int(prefill_tile_chunks(
                np.int32(start), np.int32(start + real), S=S, tile=ts,
                chunk_tokens=ct, window=w, xp=np)[1].sum())
                for w in (WINDOW, 0))
            tiles = -(-real // ts)
            assert tiles <= n <= tiles * ((WINDOW + ts) // ct + 2) <= places
            assert n0 >= tiles * (start // ct + 1)      # the whole prefix


@pytest.mark.parametrize("window", [0, WINDOW, 8])
def test_prefill_work_list_equals_a_host_enumeration(window):
    S, ts, ct, mb = 64, 16, 32, 48
    places = prefill_places(S, ts, ct, mb, BS, window)
    for start, length in [(0, 64), (0, 1), (96, 112), (104, 141), (64, 128),
                          (40, 40), (320, 384)]:
        want = []
        for t in range(S // ts):
            q0 = start + t * ts
            if q0 >= length:                     # a tile of padding: no item
                continue
            last = min(q0 + ts, length) - 1
            lo = max(0, q0 - window + 1) if window else 0
            want += [(t, c) for c in range(mb * BS // ct)
                     if c * ct <= last and (c + 1) * ct - 1 >= lo]
        assert len(want) <= places
        tile, chunk, n = (np.asarray(a) for a in prefill_work_list(
            jnp.int32(start), jnp.int32(length), S=S, tile=ts,
            chunk_tokens=ct, window=window, places=places))
        assert int(n) == len(want)
        assert list(zip(tile[:n], chunk[:n])) == want
        # a place past the list keeps the last item's tile (with no item
        # at all: one tile, whichever)
        assert len(set(tile[n:])) <= 1
        assert not want or set(tile[n:]) <= {want[-1][0]}
        # the host's count, by the same rule in numpy, and the host's walk of
        # the same list
        assert int(prefill_tile_chunks(
            np.int32(start), np.int32(length), S=S, tile=ts, chunk_tokens=ct,
            window=window, xp=np)[1].sum()) == len(want)
        on_host = prefill_work_list(
            np.int32(start), np.int32(length), S=S, tile=ts, chunk_tokens=ct,
            window=window, places=places, xp=np)
        assert all(isinstance(a, (np.ndarray, np.integer)) for a in on_host)
        for a, b in zip(on_host, (tile, chunk, n)):
            np.testing.assert_array_equal(a, b)


#: the work items of the cases below with no window (tiles of 16 rows, chunks
#: of 32 keys): a list of one item fetches for itself and starts nothing, of
#: two the first fetches for both, of an odd number the last sits in the first
#: buffer again
ITEMS = {(0, 16, 5): 1, (32, 16, 16): 2, (64, 16, 3): 3, (96, 256, 16): 4}


def _chunked_table(rs, mb, nb, cb, table):
    """A slot's row by how its chunks of ``cb`` blocks lie in the pool:
    ``shuffled`` (no chunk a run), ``runs`` (the whole row one after another)
    or ``mixed`` (every other chunk a run, the rest shuffled)."""
    if table == "shuffled":
        return _table(rs, 1, mb, nb)[0]
    row = np.arange(1, mb + 1)
    if table == "mixed":
        for c in range(1, mb // cb, 2):
            row[c * cb:(c + 1) * cb] = rs.permutation(row[c * cb:(c + 1) * cb])
    return row


@pytest.mark.parametrize("window", [0, WINDOW, 8])
@pytest.mark.parametrize("rep", [1, 8])
@pytest.mark.parametrize("start,S,real,table", [
    (96, 256, 16, "shuffled"),   # a short tail in a wide bucket, on a chunk
                                 # boundary: places past the list stay idle
    (104, 64, 37, "shuffled"),   # off it; the length ends inside a tile
    (0, 64, 64, "shuffled"),     # cold, every row real
    (64, 32, None, "shuffled"),  # no length given: every row is real
    (96, 256, 16, "runs"),       # the prefix's chunks come in one copy each
    (104, 64, 37, "mixed"),      # every other chunk does
    (0, 64, 64, "runs"),
    (64, 32, None, "mixed"),
    (0, 16, 5, "runs"),          # lists of one item,
    (32, 16, 16, "mixed"),       # of two
    (64, 16, 3, "runs"),         # and of three
])
def test_prefill_kernel_with_a_real_length(window, rep, start, S, real,
                                           table):
    """Tiles of 16 rows and chunks of 32 keys (4 blocks): the real rows
    equal the oracle's, the pad rows are exactly zero, and neither a NaN past
    the prompt's real length nor one in a block behind the window reaches an
    output row — whether the entry of such a block was released to the
    scratch block (``shuffled``) or still names a block that another slot has
    since filled (``runs`` / ``mixed``: the chunk is a run by the table, and a
    window cuts it, so it is copied block by block from the window on)."""
    rs = np.random.RandomState(start + S + window + rep)
    hkv = 2
    mb = 48
    nb = mb + 1
    kp, vp = _pools(rs, nb, hkv)
    row = _chunked_table(rs, mb, nb, 4, table)
    q = jnp.asarray(rs.randn(1, S, hkv * rep, D), jnp.float32)
    n = S if real is None else real
    k, v = (np.asarray(gather_block_kv(p, jnp.asarray(row[None])))[0]
            for p in (kp, vp))
    want = _oracle(q[0, :n], k, v, start + np.arange(n), window)
    if not window and (start, S, real) in ITEMS:
        assert int(prefill_tile_chunks(
            np.int32(start), np.int32(start + n), S=S, tile=16,
            chunk_tokens=32, window=0, xp=np)[1].sum()) \
            == ITEMS[start, S, real]
    # what no real row may read is poison: everything past the real length,
    # and what lies wholly behind the first query's window — the scratch
    # block the released entries point at, or the blocks themselves where the
    # table still names them
    released = _released_behind(row[None], [start], window)[0]
    poison = [(b, slice(None)) for b in row[(start + n - 1) // BS + 1:]]
    poison += [(row[(start + n) // BS], slice((start + n) % BS, None))] \
        if (start + n) % BS else []
    poison += [(0, slice(None))]
    if table != "shuffled":
        poison += [(b, slice(None)) for b in row[released == 0]]
        released = row
    for b, at in poison:
        kp = kp.at[b, at].set(np.nan)
        vp = vp.at[b, at].set(np.nan)
    length = None if real is None else jnp.int32(start + real)
    got = np.asarray(_prefill_call(
        q, kp, vp, jnp.asarray(released, jnp.int32), jnp.int32(start),
        jnp.int32(start + n), tile=16, chunk_tokens=32, window=window,
        interpret=True))[0]
    np.testing.assert_allclose(got[:n], want, rtol=2e-5, atol=2e-5)
    assert not got[n:].any()                     # zeros, not NaN, not stale
    # the public entry, its tile and chunk from the shapes
    planned = np.asarray(paged_prefill_attention_kernel(
        q, kp, vp, jnp.asarray(released, jnp.int32),
        jnp.asarray([start], jnp.int32), length, window=window,
        interpret=True))[0]
    np.testing.assert_allclose(planned[:n], want, rtol=2e-5, atol=2e-5)
    assert not planned[n:].any()


def test_chunk_runs_and_the_hosts_count_of_one_copy_items():
    """A chunk is a run where its table entries are consecutive block ids; an
    item's chunk comes in one copy where it is a run that neither the tile's
    last real row nor its window cuts — counted on the host by the kernel's
    own rule, against an enumeration."""
    from paddle_tpu.ops.pallas.paged_attention_kernel import (
        chunk_runs, prefill_item_counts)

    row = np.r_[5:9, [20, 22, 21, 23], 9:13, 30:33, 0, 40:42]   # 18 blocks
    runs = np.asarray(chunk_runs(jnp.asarray(row), 4))
    assert runs.tolist() == [True, False, True, False, False]
    assert chunk_runs(row, 4, xp=np).tolist() == runs.tolist()
    assert chunk_runs(row[None], 1, xp=np).all()     # a block is a run
    S, ts, ct = 64, 16, 32
    for window in (0, WINDOW):
        for start, length in [(0, 64), (64, 100), (96, 97), (80, 144)]:
            want = 0
            for t in range(S // ts):
                q0 = start + t * ts
                if q0 >= length:
                    continue
                hi = min(q0 + ts, length) - 1
                lo = max(0, q0 - window + 1) if window else 0
                want += sum(bool(runs[c]) and c * ct >= lo // BS * BS
                            and (c + 1) * ct - 1 <= hi // BS * BS + BS - 1
                            for c in range(lo // ct, hi // ct + 1))
            items, whole = prefill_item_counts(
                row, start, length, S=S, tile=ts, chunk_tokens=ct,
                block_size=BS, window=window)
            assert whole == want
            assert items == int(prefill_tile_chunks(
                np.int32(start), np.int32(length), S=S, tile=ts,
                chunk_tokens=ct, window=window, xp=np)[1].sum())
    # a resident prefix of runs behind a short tail: every chunk of it, for
    # each tile with a real row; the chunk the tail itself ends in is cut
    assert prefill_item_counts(np.arange(1, 19), 128, 140, S=64, tile=16,
                               chunk_tokens=32, block_size=BS,
                               window=0) == (5, 4)


@pytest.mark.parametrize("window", [0, WINDOW])
@pytest.mark.parametrize("start,real,table", [
    (0, 5, "runs"),          # one item: it fetches for itself, starts nothing
    (32, 16, "mixed"),       # two: the first fetches for both
    (64, 3, "runs"),         # three: the last sits in the first buffer again
    (96, 21, "mixed"),       # two tiles, runs and shuffled chunks in turn
])
def test_prefill_kernel_awaits_exactly_what_it_started(window, start, real,
                                                       table):
    """The kernel under the simulator of the chip's copies and semaphores,
    which moves a copy's bytes only when its semaphore is awaited, with the
    race detector on: an item's chunk arrives through the copies the item
    before started into the other buffer, by a size and a semaphore the wait
    rebuilds from the list alone — the outputs are, bit for bit, those of the
    plain interpreter (whose copies land when they start), and no buffer is
    read while a copy into it is in flight."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc
    from jax.experimental.pallas import tpu as pltpu

    rs = np.random.RandomState(start + window)
    mb = 24
    kp, vp = _pools(rs, mb + 1)
    row = jnp.asarray(_chunked_table(rs, mb, mb + 1, 4, table), jnp.int32)
    q = jnp.asarray(rs.randn(1, 32, H, D), jnp.float32)
    got = [np.asarray(_prefill_call(
        q, kp, vp, row, jnp.int32(start), jnp.int32(start + real), tile=16,
        chunk_tokens=32, window=window, interpret=how))
        for how in (True, pltpu.InterpretParams(
            dma_execution_mode="on_wait", detect_races=True))]
    np.testing.assert_array_equal(got[0], got[1])
    assert got[0][0, :real].any() and not got[0][0, real:].any()
    assert not ipc.races.races_found
