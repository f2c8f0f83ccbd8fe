"""An admission's host path (ISSUE 41): the prompt hashed once, the slot
staged by one program, the first token out before the bookkeeping — over every
kind of cache the tiny presets give.  What the device holds after a staging
program is held against an independent numpy statement of the rule, bitwise;
the chain keys against the implementation this PR replaced."""
import hashlib

import numpy as np
import pytest
import jax

from paddle_tpu.obs import spans as _spans
from paddle_tpu.serving.paging import BlockAllocator
from paddle_tpu.serving.prefix_cache import ChainKeys, PrefixCache
from paddle_tpu.serving.sampling import SamplingParams, host_prng_key

from families import BLOCK, BY_KIND

#: the kinds of cache served here, each by the family that states it
KINDS = {kind: f for kind, f in BY_KIND.items() if f.prompts}


@pytest.fixture(scope="module", params=list(KINDS))
def served(request):
    """``(kind, its warmed engine)``, one a kind for the whole file."""
    family = KINDS[request.param]
    return request.param, family.engine(family.tiny_model(),
                                        kernel="reference", buckets=())


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(1, 60, (n,), dtype=np.int64)


# -- the independent statement of what a slot's rows hold ----------------------

def table_row(ids, width):
    row = np.zeros((width,), np.int32)           # the scratch block
    row[:len(ids)] = ids
    return row


def stated_tables(eng, slot):
    """``[(device table, the row the rule gives)]`` from the host's lists:
    every pool's block table, and a windowed cache's summary table."""
    cache = eng.cache
    pools = getattr(cache, "pools", [cache])
    out = [(p.block_tables, table_row(p._slot_blocks[slot],
                                      p.max_blocks_per_slot)) for p in pools]
    if hasattr(cache, "summary_tables"):
        out.append((cache.summary_tables,
                    table_row(cache._slot_windows[slot], cache.max_windows)))
    return out


def stated_plans(eng, slot, start):
    """The same for a state group's plan of the program over ``[start, ..)``."""
    out = []
    for st in getattr(eng.cache, "states", []):
        # the row started from; then the rows to write, by their ends, and
        # those ends from the tail's start; past them a row beyond the pool
        # (nothing written) and a zero
        held, wrote = st._held[slot], st._wrote[slot]
        first = held[0] if held and held[0] not in wrote.values() else 0
        row = np.full((1 + 2 * st.max_snaps,), st.num_blocks, np.int32)
        row[0] = first
        row[1 + st.max_snaps:] = 0
        for k, end in enumerate(sorted(wrote)):
            row[1 + k], row[1 + st.max_snaps + k] = wrote[end], end - start
        out.append((st.plan, row))
    return out


def stated_lanes(eng, params, seed):
    s = eng.sampler
    return [(s.keys, np.asarray(jax.random.PRNGKey(seed))),
            (s.temps, np.float32(params.temperature)),
            (s.top_ks, np.int32(params.top_k)),
            (s.top_ps, np.float32(params.top_p))]


class StageSpy:
    """Wraps the engine's staging program: after every call, the device's
    arrays against the statement."""

    def __init__(self, eng, req_of, start=0):
        self.eng, self.req_of, self.calls = eng, req_of, []
        self.start = start                  # where the request's tail starts
        self.sound = eng._stager.stage
        eng._stager.stage = self

    def __call__(self, slot, lane_rows, table_rows):
        eng = self.eng
        before = [np.asarray(t._value()).copy()
                  for t in (*eng._stager.lanes, *eng._stager.tables)]
        self.sound(slot, lane_rows, table_rows)
        req = self.req_of()
        assert slot == req.slot
        checks = stated_tables(eng, slot) \
            + stated_plans(eng, slot, self.start)
        if lane_rows is not None:
            checks += stated_lanes(eng, req.sampling, eng._seed_for(req))
        for tensor, want in checks:
            got = np.asarray(tensor._value())[slot]
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
        # and nothing else moved: the other slots' rows, the lanes of a call
        # that leaves them
        for t, old in zip((*eng._stager.lanes, *eng._stager.tables), before):
            new = np.asarray(t._value())
            mine = any(t is c[0] for c in checks)
            keep = np.ones(len(new), bool)
            keep[slot] = not mine
            np.testing.assert_array_equal(new[keep], old[keep])
        self.calls.append(lane_rows is not None)

    def undo(self):
        self.eng._stager.stage = self.sound


def admit_span(req):
    """The request's last ``engine.admit`` row and its direct children."""
    rows = _spans.snapshot()
    mine = [r for r in rows if r[0] == "engine.admit"
            and r[4].get("trace", "").endswith(f":r{req.request_id}")]
    sid = mine[-1][5]
    return mine[-1], [r for r in rows if r[3] == sid]


def device_matches_host(eng):
    """Every slot's row of every table is what the host's lists say."""
    for slot in range(eng.num_slots):
        for tensor, want in stated_tables(eng, slot):
            np.testing.assert_array_equal(
                np.asarray(tensor._value())[slot], want)


# -- the staging program --------------------------------------------------------

@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("prompt", ["short", "long"])
def test_an_admission_stages_its_slot_by_the_rule(served, sampled, prompt):
    kind, eng = served
    short, long, n_pieces = KINDS[kind].prompts
    L, n = (short, 1) if prompt == "short" else (long, n_pieces)
    params = SamplingParams(temperature=0.8, top_k=7, top_p=0.9, seed=1234) \
        if sampled else SamplingParams()
    eng.prefix_cache.clear()
    base = dict(eng.stats()["admission"])
    req = eng.add_request(prompt_of(L, seed=L + sampled), max_new_tokens=4,
                          sampling=params)
    spy = StageSpy(eng, lambda: req)
    try:
        eng.step()
    finally:
        spy.undo()
    assert req.state == "running", req.error
    # a program a piece with the lanes; at most one more, after the first
    # token, for the rows of what went behind a window (the lanes kept)
    assert spy.calls[:n] == [True] * n
    assert spy.calls[n:] in ([], [False])
    span, kids = admit_span(req)
    staged = [k for k in kids if k[0] == "engine.stage"]
    assert [k[4]["programs"] for k in staged] == [1] * n
    assert [k[4]["piece"] for k in staged] == list(range(n))
    assert span[4]["key_passes"] == 1
    assert span[4]["staging_programs"] == len(spy.calls)
    names = [k[0] for k in kids]
    assert names.index("engine.first_token") < names.index("engine.register")
    now = eng.stats()["admission"]
    assert now["admissions"] - base["admissions"] == 1
    assert now["key_passes"] - base["key_passes"] == 1
    assert now["staging_programs"] - base["staging_programs"] \
        == len(spy.calls)
    eng.run()
    assert req.state == "finished", req.error
    device_matches_host(eng)
    assert eng.health()["kv_block_invariants"] == "ok"


def test_a_hit_stages_the_shared_blocks_and_walks_the_prompt_once(served):
    """A second prompt behind the first's blocks: the staged rows hold the
    hit (a state group's plan its snapshot row), and its admission walked its
    own prompt once."""
    kind, eng = served
    eng.prefix_cache.clear()
    doc = prompt_of(40, seed=5)
    first = eng.add_request(doc, max_new_tokens=1)
    eng.run()
    assert first.state == "finished"
    req = eng.add_request(np.concatenate([doc, prompt_of(9, seed=6)]),
                          max_new_tokens=2)
    hit = eng.prefix_probe(req.prompt_ids)
    assert hit > 0
    spy = StageSpy(eng, lambda: req, start=hit)
    try:
        eng.step()
    finally:
        spy.undo()
    span, _kids = admit_span(req)
    assert span[4]["hit_tokens"] == hit and span[4]["key_passes"] == 1
    assert spy.calls[0] is True
    eng.run()
    assert req.state == "finished", req.error
    device_matches_host(eng)


def test_the_program_is_compiled_by_warmup_and_only_once(served):
    _kind, eng = served
    assert eng._stager._apply._cache_size() == 1


@pytest.mark.parametrize("seed", [0, 1, 7919, 2 ** 31 - 1, 2 ** 31, 2 ** 32 - 1,
                                  2 ** 32 + 5, 7919 * 10 ** 7, -1, -5])
def test_the_host_key_is_jax_prng_key_bitwise(seed):
    want = np.asarray(jax.random.PRNGKey(seed))
    got = host_prng_key(seed)
    assert got.dtype == want.dtype == np.uint32
    np.testing.assert_array_equal(got, want)


# -- the first token before the bookkeeping ---------------------------------------

def spied_register(eng, events):
    sound = eng.prefix_cache.register

    def register(*a, **kw):
        events.append("register")
        return sound(*a, **kw)
    eng.prefix_cache.register = register
    return lambda: setattr(eng.prefix_cache, "register", sound)


def test_the_first_token_is_out_before_the_prompt_is_registered(served):
    _kind, eng = served
    eng.prefix_cache.clear()
    events = []
    undo = spied_register(eng, events)
    try:
        req = eng.add_request(
            prompt_of(33, seed=11), max_new_tokens=3,
            stream_cb=lambda tok, r: events.append("token"))
        eng.run()
    finally:
        undo()
    assert req.state == "finished", req.error
    assert events == ["token", "register", "token", "token"]


def test_a_request_done_at_its_first_token_leaves_its_blocks_hittable(served):
    """Registration comes before the request can retire: the resident
    set-up's one-token requests are hit by every later probe."""
    _kind, eng = served
    eng.prefix_cache.clear()
    prompt = prompt_of(41, seed=12)
    req = eng.add_request(prompt, max_new_tokens=1)
    eng.run()
    assert req.state == "finished" and len(req.output_ids) == 1
    assert eng.prefix_probe(prompt) == 40
    device_matches_host(eng)
    assert eng.health()["kv_block_invariants"] == "ok"


@pytest.mark.parametrize("what", ["callback", "prefill"])
def test_nothing_is_registered_after_a_failure(served, what):
    _kind, eng = served
    eng.prefix_cache.clear()
    events = []
    undo = [spied_register(eng, events)]
    prompt = prompt_of(33, seed=13)

    def boom(*a, **kw):
        raise RuntimeError("boom")
    if what == "prefill":
        sound = eng._prefill_fn
        eng._prefill_fn = boom
        boom.program_cache = sound.program_cache
        undo.append(lambda: setattr(eng, "_prefill_fn", sound))
    try:
        req = eng.add_request(prompt, max_new_tokens=4,
                              stream_cb=boom if what == "callback" else None)
        eng.run()
    finally:
        for u in undo:
            u()
    assert req.state == "failed"
    assert events == [] and eng.prefix_probe(prompt) == 0
    assert len(eng.prefix_cache) == 0
    device_matches_host(eng)
    assert eng.health()["kv_block_invariants"] == "ok"


# -- a deferred admission ----------------------------------------------------------

def device_state(eng):
    return [np.asarray(t._value()).copy()
            for t in (*eng._stager.lanes, *eng._stager.tables)]


def refcounts(eng):
    cache = eng.cache
    als = [p.allocator for p in getattr(cache, "pools", [cache])] \
        + [st.allocator for st in getattr(cache, "states", [])]
    if hasattr(cache, "summary_allocator"):
        als.append(cache.summary_allocator)
    return [(list(a._ref), sorted(a._free)) for a in als]


def test_a_deferred_admission_leaves_tables_and_refcounts(served):
    """A pool with room for one prompt: the second admission is deferred with
    no program issued, and its retry finds its keys."""
    kind, eng = served
    eng.prefix_cache.clear()
    # all but a prompt's bucket of blocks held back (8 blocks a bucket of 64;
    # a cache by layer keeps one more to get, a windowed one a piece's window)
    pool = getattr(eng.cache, "pools", [eng.cache])[0].allocator
    room = {"windowed": 11, "grouped_state": 9,
            "grouped_recurrent": 9}.get(kind, 8)
    held = pool.alloc(pool.free_blocks - room)
    try:
        first = eng.add_request(prompt_of(40, seed=1), max_new_tokens=8)
        eng.step()
        assert first.state == "running", first.error
        second = eng.add_request(prompt_of(40, seed=2), max_new_tokens=2)
        eng.queue.remove(second)
        second.slot = eng.free_slots.pop()
        state, refs, calls = device_state(eng), refcounts(eng), \
            eng._stager.calls
        assert eng._admit(second) is False
        eng.free_slots.append(second.slot)
        second.slot = None
        second._defers += 1
        for got, want in zip(device_state(eng), state):
            np.testing.assert_array_equal(got, want)
        assert refcounts(eng) == refs and eng._stager.calls == calls
        span, kids = admit_span(second)
        assert span[4]["outcome"] == "deferred" and span[4]["key_passes"] == 1
        assert not [k for k in kids if k[0] == "engine.stage"]
        eng.queue.append(second)
        eng.run()
    finally:
        for b in held:
            pool.unref(b)
    assert first.state == second.state == "finished", second.error
    span, _kids = admit_span(second)
    assert span[4]["outcome"] == "admitted" and span[4]["key_passes"] == 0
    device_matches_host(eng)
    assert eng.health()["kv_block_invariants"] == "ok"


# -- the keys are the parent's ------------------------------------------------------

_ROOT = b"paddle-tpu-prefix-root"


def parent_keys(prompt, n_blocks, block_size, epoch, salt):
    """``PrefixCache._keys_for`` as it was before this PR."""
    keys, parent = [], _ROOT + epoch.to_bytes(8, "little") + salt
    for i in range(n_blocks):
        h = hashlib.blake2b(digest_size=16)
        h.update(parent)
        h.update(np.ascontiguousarray(
            prompt[i * block_size:(i + 1) * block_size],
            dtype=np.int64).tobytes())
        parent = h.digest()
        keys.append(parent)
    return keys


@pytest.mark.parametrize("salt", [b"", b"tenant@v3"])
@pytest.mark.parametrize("epoch", [0, 2])
@pytest.mark.parametrize("block_size", [8, 16, 32])
def test_keys_equal_the_parents(block_size, epoch, salt):
    pc = PrefixCache(BlockAllocator(4), block_size)
    pc.epoch = epoch
    prompt = prompt_of(block_size * 9 + 3, seed=block_size)
    want = parent_keys(prompt, 9, block_size, epoch, salt)
    assert pc._keys_for(prompt, 9, salt) == want
    memo = ChainKeys()
    assert pc._keys_for(prompt, 4, salt, memo) == want[:4]
    assert pc._keys_for(prompt, 9, salt, memo) == want
    assert memo.passes == 1
    # the root moved (a weight swap): the kept keys are dropped, not read
    pc.epoch = epoch + 1
    assert pc._keys_for(prompt, 9, salt, memo) \
        == parent_keys(prompt, 9, block_size, epoch + 1, salt)
    assert memo.passes == 2
    # ... and so does a tenant's salt
    assert pc._keys_for(prompt, 2, salt + b"#", memo) \
        == parent_keys(prompt, 2, block_size, epoch + 1, salt + b"#")
    assert memo.passes == 3


def test_a_requests_keys_are_the_parents_and_survive_its_lookups(served):
    kind, eng = served
    eng.prefix_cache.clear()
    prompt = prompt_of(67, seed=21)
    req = eng.add_request(prompt, max_new_tokens=1)
    assert req.prompt_ids.dtype == np.int64
    eng.run()
    assert req._keys.passes == 1
    epoch = eng.prefix_cache.epoch
    for bs, keys in req._keys.by_block.items():
        assert keys == parent_keys(prompt, len(prompt) // bs, bs, epoch, b"")
    assert BLOCK in req._keys.by_block
    if kind == "windowed":
        assert eng.cache.window in req._keys.by_block
    # what it registered, another request's own keys hit
    assert eng.prefix_probe(prompt) > 0
    # a weight swap moves the root: the kept keys are not read again
    eng.prefix_cache.bump_epoch()
    assert eng.prefix_cache.lookup(prompt, count=False,
                                   keys=req._keys)[0] == 0
    assert req._keys.passes == 2


# -- the host's count of the tail-prefill kernel's one-copy chunks ---------------

#: cell -> (KV heads, query heads, window, the bucket, the tail's real tokens,
#: the tail takes the kernel under the indexer's selection): the
#: long-document question behind its resident document, and the ide tail on a
#: full and on a window layer's pool — pools of the cells' block size and
#: row length, one layer, no model
CELL_SHAPES = {
    "longdoc": (4, 32, 0, 512, 200, True),
    "ide-full": (4, 32, 0, 256, 16, False),
    "ide-window": (4, 32, 1024, 256, 16, False),
}


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_a_resident_documents_chunks_are_runs_and_a_churned_pools_are_not(
        cell):
    """``prefill_work`` at the cells' shapes (block 16, rows of 32,768
    positions, a 30,720-token document): what the allocator hands out one
    after another is a run a 256-key chunk, so every item of the tail but
    those its own end or its window cuts counts in ``prefill_items_run``; the
    same admission into blocks a churned free list hands out counts the same
    items and no run."""
    from paddle_tpu.serving.paging import PagedKVCache

    hkv, heads, window, bucket, real, indexed = CELL_SHAPES[cell]
    doc, bs = 30720, 16
    pool = PagedKVCache(2, 1, 32768, hkv, 128, "bfloat16", block_size=bs,
                        num_blocks=2 * (doc + bucket) // bs + 1,
                        kernel="pallas", window=window)

    def admit(slot):
        assert pool.begin_sequence(slot, [], 0, doc + bucket, write=False)
        if window:                       # as the pieces before the tail did
            pool.release_behind(slot, doc, write=False)
        return pool.prefill_work(slot, bucket, doc, doc + real, heads,
                                 indexed=indexed)

    ts = 64 if indexed else 32
    tiles = -(-real // ts)
    items, rows, runs = admit(0)
    assert rows == tiles * ts
    if window:
        # a tile reads from its first row's oldest key, 29,697: chunks 116
        # (whose first block holds it: nothing of the chunk is released) to
        # 120, which the tail's own end cuts
        assert (items, runs) == (5, 4)
    else:
        # every tile reads the document's 120 chunks and the tail's own
        assert (items, runs) == (tiles * 121, tiles * 120)
    # the free list after a churn: the blocks of a released slot, shuffled
    pool.release_slot(0)
    free = list(pool.allocator._free)
    np.random.default_rng(0).shuffle(free)
    pool.allocator._free.clear()
    pool.allocator._free.extend(free)
    assert admit(1) == (items, rows, 0)
