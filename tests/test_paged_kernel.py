"""ISSUE 11: Pallas paged-attention kernels — parity vs the jnp
reference path, and the compiled engine kernel path end to end.

Op level (eager, interpret mode — the exact code tier-1 must exercise):
the flash-decoding decode kernel and the fused cached-prefix/causal-tail
prefill kernel against the ``gather_block_kv`` + masked-softmax oracle,
for MHA and GQA head layouts, including the masking semantics (garbage
past a slot's length / a query's causal horizon must be invisible).

Engine level (compiled): a paged ``kernel="pallas"`` engine produces
BITWISE the greedy outputs of the ``kernel="reference"`` engine (GPT and
GQA-Llama), with zero steady-state compile misses on the kernel path;
the run carries a RequestTracer whose span chain validates with the
per-step decode event schema intact (ISSUE 9 stays true with sampling
fused into the step).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import (
    GPTForCausalLM, LlamaForCausalLM, gpt_tiny, llama_tiny,
)
from paddle_tpu.ops.cached_attention import (
    block_prefill_attention, cached_attention, gather_block_kv,
)
from paddle_tpu.ops.pallas.paged_attention_kernel import (
    paged_decode_attention_kernel, paged_prefill_attention_kernel,
)
from paddle_tpu.serving import Engine, RequestTracer, validate_trace


# -- op-level parity (eager interpret mode) ---------------------------------

def _rand_pool(rs, nb, bs, hkv, d):
    return (jnp.asarray(rs.randn(nb, bs, hkv, d), jnp.float32),
            jnp.asarray(rs.randn(nb, bs, hkv, d), jnp.float32))


def _to_lanes(pool, lanes):
    """The serving pool's stored form: the minor dim in whole lanes, the
    pad lanes zero."""
    return jnp.pad(pool, [(0, 0)] * 3 + [(0, lanes - pool.shape[-1])])


def _ref_decode(q, kp, vp, tbl, lens):
    """gather_block_kv + cached_attention: the kernel="reference" path."""
    B, MB = tbl.shape
    k = paddle.to_tensor(np.asarray(gather_block_kv(kp, tbl)))
    v = paddle.to_tensor(np.asarray(gather_block_kv(vp, tbl)))
    out = cached_attention(paddle.to_tensor(np.asarray(q)), k, v,
                           paddle.to_tensor(np.asarray(lens)))
    return np.asarray(out.numpy())


def _ref_prefill(q, kp, vp, row, start):
    k = paddle.to_tensor(np.asarray(gather_block_kv(kp, row[None, :])))
    v = paddle.to_tensor(np.asarray(gather_block_kv(vp, row[None, :])))
    out = block_prefill_attention(
        paddle.to_tensor(np.asarray(q)), k, v,
        paddle.to_tensor(np.int32(start)))
    return np.asarray(out.numpy())


def _ones(n):
    return jnp.ones((n,), jnp.int32)


#: window ends at both edges of a 16-token block and of a 256-token chunk,
#: and at the row's last position
RAGGED_LENGTHS = (0, 15, 16, 255, 256, 1023)


class TestDecodeKernelParity:
    @pytest.mark.parametrize("hkv,h", [(4, 4), (2, 4)])  # MHA and GQA
    def test_matches_reference(self, hkv, h):
        rs = np.random.RandomState(0)
        NB, BS, D, B, MB = 13, 8, 16, 4, 4
        kp, vp = _rand_pool(rs, NB, BS, hkv, D)
        tbl = jnp.asarray(rs.randint(1, NB, (B, MB)), jnp.int32)
        lens = jnp.asarray([0, 7, 18, 31], jnp.int32)
        q = jnp.asarray(rs.randn(B, 1, h, D), jnp.float32)
        out = paged_decode_attention_kernel(q, kp, vp, tbl, lens, _ones(B),
                                            interpret=True)
        ref = _ref_decode(q, kp, vp, tbl, lens)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("hkv,h", [(4, 4), (2, 4)])
    def test_lane_padded_pool_matches_reference(self, hkv, h):
        """The pool as ``PagedKVCache`` stores it (minor dim 16 -> 128
        lanes, zeros): queries pad inside the wrapper, the softmax scale
        stays the head's own 1/sqrt(16), the output is head_dim wide."""
        rs = np.random.RandomState(0)
        NB, BS, D, B, MB = 13, 8, 16, 4, 4
        kp, vp = _rand_pool(rs, NB, BS, hkv, D)
        tbl = jnp.asarray(rs.randint(1, NB, (B, MB)), jnp.int32)
        lens = jnp.asarray([0, 7, 18, 31], jnp.int32)
        q = jnp.asarray(rs.randn(B, 1, h, D), jnp.float32)
        out = paged_decode_attention_kernel(
            q, _to_lanes(kp, 128), _to_lanes(vp, 128), tbl, lens, _ones(B),
            interpret=True)
        assert out.shape == q.shape
        np.testing.assert_allclose(
            np.asarray(out), _ref_decode(q, kp, vp, tbl, lens),
            rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("active", [(1, 1, 1, 1, 1, 1),
                                        (1, 0, 1, 0, 1, 1),
                                        (0, 0, 0, 1, 0, 0)],
                             ids=["all", "some", "one"])
    @pytest.mark.parametrize("hkv,h,d", [(4, 4, 64), (2, 8, 128),
                                         (2, 8, 64), (1, 8, 64)],
                             ids=["mha64in128", "gqa128", "rep4x64in128",
                                  "rep8x64in128"])
    def test_ragged_lengths_idle_slots_and_garbage_blocks(self, hkv, h, d,
                                                          active):
        """Six slots of 64 blocks of 16 (four chunks of 256 a row), idle
        slots between running ones.  The kernel sees a pool in which every
        block an idle slot's row names, every block of a running slot's row
        past its window and the tail of its last live block are NaN; the
        oracle sees the clean pool.  Active rows agree, the others are
        exactly zero, and no NaN comes out."""
        rs = np.random.RandomState(5)
        BS, MB, B, lanes = 16, 64, len(active), 128
        NB = B * MB + 1
        kp, vp = _rand_pool(rs, NB, BS, hkv, d)
        tbl = rs.permutation(NB - 1)[:B * MB].reshape(B, MB) + 1
        q = jnp.asarray(rs.randn(B, 1, h, d), jnp.float32)
        lens = jnp.asarray(RAGGED_LENGTHS, jnp.int32)
        bad = np.zeros((NB, BS), bool)
        for b, (ln, a) in enumerate(zip(RAGGED_LENGTHS, active)):
            first = ln // BS + 1 if a else 0     # first block wholly unseen
            bad[tbl[b, first:]] = True
            if a:
                bad[tbl[b, ln // BS], ln % BS + 1:] = True
        poison = jnp.asarray(np.where(bad, np.nan, 0.0)[:, :, None, None],
                             jnp.float32)
        tbl = jnp.asarray(tbl, jnp.int32)
        act = jnp.asarray(active, jnp.int32)
        out = np.asarray(paged_decode_attention_kernel(
            q, _to_lanes(kp, lanes) + poison, _to_lanes(vp, lanes) + poison,
            tbl, lens, act, interpret=True))
        assert out.shape == q.shape
        ref = _ref_decode(q, kp, vp, tbl, lens)
        on = np.asarray(active, bool)
        np.testing.assert_allclose(out[on], ref[on], rtol=1e-5, atol=1e-5)
        assert not out[~on].any()                # exactly zero, never NaN

    def test_positions_past_length_are_invisible(self):
        """Scribbling over pool positions beyond a slot's window must not
        change its context — the in-kernel mask is the only thing hiding
        them (the reference relies on the same contract)."""
        rs = np.random.RandomState(1)
        NB, BS, Hkv, D, B, MB = 9, 8, 2, 8, 2, 3
        kp, vp = _rand_pool(rs, NB, BS, Hkv, D)
        tbl = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)  # distinct
        lens = jnp.asarray([4, 11], jnp.int32)
        q = jnp.asarray(rs.randn(B, 1, 4, D), jnp.float32)
        out = paged_decode_attention_kernel(q, kp, vp, tbl, lens, _ones(B),
                                            interpret=True)
        # slot 0's window is 0..4 inside its first block: poison the
        # rest of that block and every later block it references
        blk0 = int(tbl[0, 0])
        kp2 = kp.at[blk0, 5:].set(999.0)
        vp2 = vp.at[blk0, 5:].set(-999.0)
        for j in range(1, MB):
            kp2 = kp2.at[int(tbl[0, j])].set(999.0)
            vp2 = vp2.at[int(tbl[0, j])].set(-999.0)
        out2 = paged_decode_attention_kernel(q, kp2, vp2, tbl, lens,
                                             _ones(B), interpret=True)
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(out2[0]))

    def test_length_zero_slot_attends_only_position_zero(self):
        rs = np.random.RandomState(2)
        NB, BS, Hkv, D = 5, 4, 2, 8
        kp, vp = _rand_pool(rs, NB, BS, Hkv, D)
        tbl = jnp.asarray([[1, 2]], jnp.int32)
        q = jnp.asarray(rs.randn(1, 1, 2, D), jnp.float32)
        out = paged_decode_attention_kernel(
            q, kp, vp, tbl, jnp.asarray([0], jnp.int32), _ones(1),
            interpret=True)
        # softmax over exactly one valid position == that position's V
        np.testing.assert_allclose(np.asarray(out[0, 0]),
                                   np.asarray(vp[1, 0]),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("active", [(1, 1, 1, 1, 1, 1),
                                        (1, 0, 1, 0, 1, 1),
                                        (0, 0, 0, 0, 0, 0)],
                             ids=["all", "some", "none"])
    def test_work_list_of_the_kv_shapes(self, active):
        """``n`` is the sum over active slots of ``lengths // chunk + 1``,
        the pairs come slot-major with a slot's chunks in order, and the
        chunk is what the shapes allow: 256 tokens at GPT-2 345M's pool
        (16 heads in 128 bf16 lanes), fewer where a token is wider, never
        more than a slot's row."""
        from paddle_tpu.ops.pallas.mla_attention_kernel import \
            decode_work_list
        from paddle_tpu.ops.pallas.paged_attention_kernel import \
            decode_chunk_tokens

        ct = decode_chunk_tokens(16, 64, 16, 128, 2)
        assert ct == 256
        assert decode_chunk_tokens(16, 64, 8, 128, 2) == 256
        assert decode_chunk_tokens(16, 512, 32, 128, 2) == 144
        assert decode_chunk_tokens(8, 4, 4, 128, 4) == 32
        assert decode_chunk_tokens(512, 4, 2, 128, 2) == 512  # one block
        lens = jnp.asarray(RAGGED_LENGTHS, jnp.int32)
        slot, chunk, n = decode_work_list(
            lens, jnp.asarray(active, jnp.int32), ct, 1024 // ct)
        per = [ln // ct + 1 if a else 0
               for ln, a in zip(RAGGED_LENGTHS, active)]
        assert int(n) == sum(per)
        assert list(np.asarray(slot[:int(n)])) == \
            [b for b, k in enumerate(per) for _ in range(k)]
        assert list(np.asarray(chunk[:int(n)])) == \
            [c for k in per for c in range(k)]


class TestPrefillKernelParity:
    @pytest.mark.parametrize("hkv,h", [(4, 4), (2, 4)])
    @pytest.mark.parametrize("start", [0, 16])
    def test_matches_reference(self, hkv, h, start):
        """Fused prefix+tail kernel vs gather + block_prefill_attention,
        with and without a cached prefix (start > 0 puts real shared
        blocks under the cross-attention half)."""
        rs = np.random.RandomState(3)
        NB, BS, D, MB, S = 11, 8, 16, 4, 16
        kp, vp = _rand_pool(rs, NB, BS, hkv, D)
        row = jnp.asarray(rs.randint(1, NB, (MB,)), jnp.int32)
        q = jnp.asarray(rs.randn(1, S, h, D), jnp.float32)
        out = paged_prefill_attention_kernel(q, kp, vp, row, start,
                                             interpret=True)
        ref = _ref_prefill(q, kp, vp, row, start)
        np.testing.assert_allclose(np.asarray(out), ref,
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("hkv,h", [(4, 4), (2, 4)])
    @pytest.mark.parametrize("start", [0, 16])
    def test_lane_padded_pool_matches_reference(self, hkv, h, start):
        rs = np.random.RandomState(3)
        NB, BS, D, MB, S = 11, 8, 16, 4, 16
        kp, vp = _rand_pool(rs, NB, BS, hkv, D)
        row = jnp.asarray(rs.randint(1, NB, (MB,)), jnp.int32)
        q = jnp.asarray(rs.randn(1, S, h, D), jnp.float32)
        out = paged_prefill_attention_kernel(
            q, _to_lanes(kp, 128), _to_lanes(vp, 128), row, start,
            interpret=True)
        assert out.shape == q.shape
        np.testing.assert_allclose(
            np.asarray(out), _ref_prefill(q, kp, vp, row, start),
            rtol=1e-5, atol=1e-5)

    def test_future_positions_are_invisible(self):
        """The absolute-position causal mask: keys past a query's own
        position (within the tail) must not leak into its context."""
        rs = np.random.RandomState(4)
        NB, BS, Hkv, D, MB, S, start = 7, 8, 2, 8, 3, 8, 8
        kp, vp = _rand_pool(rs, NB, BS, Hkv, D)
        row = jnp.asarray([1, 2, 3], jnp.int32)
        q = jnp.asarray(rs.randn(1, S, 2, D), jnp.float32)
        out = paged_prefill_attention_kernel(q, kp, vp, row, start,
                                             interpret=True)
        # poison every key position past the FIRST query (abs pos 8):
        # block 1 (the tail's first block) positions 1.., and all of
        # block 2 — query 0's context must not move
        kp2 = kp.at[2, 1:].set(777.0)
        kp2 = kp2.at[3].set(777.0)
        vp2 = vp.at[2, 1:].set(-777.0)
        vp2 = vp2.at[3].set(-777.0)
        out2 = paged_prefill_attention_kernel(q, kp2, vp2, row, start,
                                              interpret=True)
        np.testing.assert_array_equal(np.asarray(out[0, 0]),
                                      np.asarray(out2[0, 0]))

    @pytest.mark.parametrize("run", [False, True])
    @pytest.mark.parametrize("hkv,h", [(4, 4), (1, 8)])   # rep 1 and rep 8
    @pytest.mark.parametrize("start,real", [(0, 5), (256, 16), (240, 40),
                                            (16, None)])
    def test_real_length_pads_are_zero_and_poison_is_unseen(self, hkv, h,
                                                            start, real, run):
        """The prompt's real length in a 64-row bucket off the lane-padded
        pool, behind a prefix that ends on (256) and off (240) the 256-token
        chunk: the real rows are the reference's, the pad rows exactly zero,
        and a NaN in every position past the real length moves nothing —
        through a shuffled table, and where the slot's blocks lie one after
        another in the pool (``run``: a chunk wholly at or before the tile's
        last real row comes in one copy a side)."""
        rs = np.random.RandomState(5)
        NB, BS, D, MB, S = 41, 8, 16, 40, 64
        kp, vp = _rand_pool(rs, NB, BS, hkv, D)
        row = jnp.asarray(np.arange(1, NB) if run else
                          rs.permutation(np.arange(1, NB))[:MB], jnp.int32)
        q = jnp.asarray(rs.randn(1, S, h, D), jnp.float32)
        n = S if real is None else real
        ref = _ref_prefill(q, kp, vp, row, start)
        end = start + n
        for b, at in [(int(b), slice(None)) for b in row[-(-end // BS):]] \
                + ([(int(row[end // BS]), slice(end % BS, None))]
                   if end % BS else []):
            kp = kp.at[b, at].set(np.nan)
            vp = vp.at[b, at].set(np.nan)
        out = np.asarray(paged_prefill_attention_kernel(
            q, _to_lanes(kp, 128), _to_lanes(vp, 128), row, start,
            None if real is None else start + real, interpret=True))
        assert out.shape == q.shape
        np.testing.assert_allclose(out[0, :n], ref[0, :n], rtol=1e-5,
                                   atol=1e-5)
        assert not out[0, n:].any()


# -- compiled engine: kernel path end to end --------------------------------

PROMPT_LENGTHS = (5, 13, 21, 9, 25, 3)   # 25+6 fits max_seq=32


def _run_engine(model, kernel, tracer=None):
    eng = Engine(model, num_slots=4, max_seq=32, min_bucket=8,
                 block_size=8, kernel=kernel,
                 tracer=tracer)
    eng.warmup()
    warm = eng.metrics.compile_misses
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, (L,)).tolist() for L in PROMPT_LENGTHS]
    outs = eng.generate(prompts, max_new_tokens=6)
    return eng, warm, outs


@pytest.fixture(scope="module")
def gpt_runs():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    tracer = RequestTracer()
    pallas = _run_engine(m, "pallas", tracer=tracer)
    ref = _run_engine(m, "reference")
    return pallas, ref, tracer


class TestEngineKernelPath:
    def test_gpt_greedy_bitwise_matches_reference(self, gpt_runs):
        (p_eng, _, p_outs), (r_eng, _, r_outs), _ = gpt_runs
        assert p_eng.kernel == "pallas" and r_eng.kernel == "reference"
        assert p_outs == r_outs
        assert all(len(o) == 6 for o in p_outs)

    def test_zero_steady_state_misses_on_kernel_path(self, gpt_runs):
        (p_eng, warm, _), _, _ = gpt_runs
        assert p_eng.metrics.compile_misses == warm
        assert p_eng.health()["kv_block_invariants"] == "ok"
        assert p_eng.stats()["paging"]["kernel"] == "pallas"

    def test_llama_gqa_greedy_bitwise_matches_reference(self):
        paddle.seed(0)
        m = LlamaForCausalLM(llama_tiny())
        m.eval()
        assert m.config.n_kv_heads < m.config.num_attention_heads
        (p_eng, p_warm, p_outs) = _run_engine(m, "pallas")
        (_, _, r_outs) = _run_engine(m, "reference")
        assert p_outs == r_outs
        assert p_eng.metrics.compile_misses == p_warm

    def test_traced_kernel_run_chain_validates(self, gpt_runs):
        """ISSUE 9 flaky-guard: with sampling fused into the step, the
        traced run over the kernel path still records the same per-step
        decode event schema, the span chain validates, and tracing adds
        zero compile keys (the zero-miss test above covers the same
        traced engine)."""
        (p_eng, _, _), _, tracer = gpt_runs
        assert validate_trace(tracer) == []
        steps = [e for e in tracer.events if e["kind"] == "decode_step"]
        assert steps, "kernel-path run recorded no decode_step events"
        for e in steps:
            assert set(e) >= {"replica", "step", "slots", "n_active",
                              "dt_ms"}
            assert e["n_active"] == len(e["slots"]) > 0
        retired = [e for e in tracer.events if e["kind"] == "retired"]
        assert len(retired) == len(PROMPT_LENGTHS)

    def test_kernel_flag_validation(self):
        paddle.seed(0)
        m = GPTForCausalLM(gpt_tiny())
        with pytest.raises(ValueError):
            Engine(m, num_slots=2, max_seq=32, block_size=8, kernel="bogus")
        # no argument: the Pallas kernels
        eng = Engine(m, num_slots=2, max_seq=32)
        assert eng.kernel == eng.cache.kernel == "pallas"


# -- the latent (MLA) kernels and the grouped expert kernel -------------------

def _latent_case(rs, *, slots=4, heads=4, width=40, bs=8, mb=40, nb=200):
    """A pool in its stored form (``width`` in whole 128-lane rows, pad lanes
    zero), a table whose rows 1 and 3 share their first block, and queries
    padded like the pool.  ``mb * bs`` = 320 tokens is two chunks of 256."""
    from paddle_tpu.serving.paging import SCRATCH_BLOCK

    lanes = 128
    pool = np.zeros((nb, bs, lanes), np.float32)
    pool[:, :, :width] = rs.randn(nb, bs, width)
    tbl = np.full((slots, mb), SCRATCH_BLOCK, np.int32)
    ids = rs.permutation(nb - 1)[:slots * mb].reshape(slots, mb) + 1
    for b in (0, 1, 3):
        tbl[b] = ids[b]
    tbl[3, 0] = tbl[1, 0]                   # a shared prefix block
    q = np.zeros((slots, heads, lanes), np.float32)
    q[..., :width] = rs.randn(slots, heads, width)
    return jnp.asarray(pool), jnp.asarray(tbl), jnp.asarray(q)


def _prefill_case(rs, bucket, start, dtype="float32", *, heads=4, nope=16,
                  rope=8, rank=32, dv=16, bs=8):
    """``(q, lat, w_uk, w_uv, pool, row)``: a tail bucket of ``bucket`` rows
    behind ``start`` cached tokens of one slot — queries ``[q_nope |
    q_rope]``, the tail's latents (written to the slot's blocks of the pool,
    as ``write_prefill_latent`` leaves them) and the two up-projections."""
    n = (start + bucket) // bs
    ids = rs.permutation(n + 4)[:n] + 1             # block 0: scratch
    lat = rs.randn(start + bucket, rank + rope)
    pool = np.zeros((n + 5, bs, 128), np.float32)
    pool[ids, :, :rank + rope] = lat.reshape(n, bs, -1)
    row = np.zeros(n + 3, np.int32)
    row[:n] = ids
    q = rs.randn(bucket, heads, nope + rope)
    w_uk, w_uv = rs.randn(heads, nope, rank) * 0.2, rs.randn(heads, rank,
                                                             dv) * 0.2
    return (*(jnp.asarray(a, dtype) for a in (q, lat[start:], w_uk, w_uv,
                                               pool)), jnp.asarray(row))


class TestLatentKernels:
    @pytest.mark.parametrize("lengths", [(5, 130, 0, 300), (255, 256, 0, 7)])
    def test_decode_matches_its_oracle_on_ragged_lengths(self, lengths):
        """Slot 2 is idle on the scratch block (no work item, output zero);
        slot 3 crosses the chunk boundary; slots 1 and 3 share a block."""
        from paddle_tpu.ops.pallas import mla_attention_kernel as mk

        pool, tbl, q = _latent_case(np.random.RandomState(0))
        lens = jnp.asarray(lengths, jnp.int32)
        active = jnp.asarray([1, 1, 0, 1], jnp.int32)
        kw = dict(scale=0.2, dv=32)
        got = mk.mla_paged_decode(q, pool, tbl, lens, active, interpret=True,
                                  **kw)
        want = mk.mla_decode_reference(q, pool, tbl, lens, active, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=0)
        assert not np.asarray(got[2]).any()
        slot, chunk, n = mk.decode_work_list(lens, active, 256, 2)
        per = [ln // 256 + 1 if a else 0 for ln, a in zip(lengths, (1, 1, 0, 1))]
        assert int(n) == sum(per)
        assert list(np.asarray(slot[:int(n)])) == \
            [b for b, k in enumerate(per) for _ in range(k)]

    @pytest.mark.parametrize("start,length", [(0, 50), (96, 160), (256, 266)])
    def test_prefill_matches_its_oracle_below_the_prompts_length(
            self, start, length):
        """A 64-token tail bucket behind ``start`` cached tokens, both parts
        (the tail over itself up-projected, the prefix absorbed, merged)
        against one absorbed softmax over the slot's whole block row; the
        rows past the prompt's real length are pad: a tile of the absorbed
        kernel wholly of them is not computed and comes back zero (a piece
        of the flash pass: the test below)."""
        from paddle_tpu.ops.pallas import mla_attention_kernel as mk

        q, lat, w_uk, w_uv, pool, row = _prefill_case(
            np.random.RandomState(1), 64, start)
        got = mk.mla_prefill(q, lat, w_uk, w_uv, pool, row, start, length,
                             scale=0.2, interpret=True)
        want = mk.mla_prefill_oracle(q, w_uk, w_uv, pool, row, start, length,
                                     scale=0.2)
        real = length - start
        np.testing.assert_allclose(np.asarray(got[:real]),
                                   np.asarray(want[:real]), atol=2e-5, rtol=0)
        if real <= 32:
            q_lat = mk.absorb_queries(q[..., :16], q[..., 16:], w_uk, 128)
            o, lse = mk.mla_paged_prefill(q_lat, pool, row, start, length,
                                          scale=0.2, dv=32, interpret=True)
            assert not np.asarray(o[32:]).any()
            assert (np.asarray(lse[32:]) == mk.NEG_INF).all()

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("start", [0, 48])
    @pytest.mark.parametrize("bucket", [32, 64, 256, 1024])
    def test_composed_prefill_equals_one_absorbed_softmax(
            self, bucket, start, dtype):
        """Buckets from 32 to 1,024 rows (one to four pieces of the flash
        pass's walk), cold and behind a block-aligned cached prefix, a real
        length seven rows short of the bucket; bf16 operands against the
        float32 oracle on the same rounded numbers."""
        from paddle_tpu.ops.pallas import mla_attention_kernel as mk

        case = _prefill_case(np.random.RandomState(bucket + start), bucket,
                             start, dtype)
        q, lat, w_uk, w_uv, pool, row = case
        length = start + bucket - 7
        got = mk.mla_prefill(q, lat, w_uk, w_uv, pool, row, start, length,
                             scale=0.2, interpret=True)
        assert got.dtype == q.dtype and got.shape == (bucket, 4, 16)
        q, w_uk, w_uv, pool = (a.astype(jnp.float32)
                               for a in (q, w_uk, w_uv, pool))
        want = mk.mla_prefill_oracle(q, w_uk, w_uv, pool, row, start, length,
                                     scale=0.2)
        np.testing.assert_allclose(
            np.asarray(got[:bucket - 7], np.float32),
            np.asarray(want[:bucket - 7]),
            atol=2e-5 if dtype == "float32" else 3e-2, rtol=0)
        assert np.isfinite(np.asarray(got, np.float32)).all()

    @pytest.mark.parametrize("S,block,sub,real", [
        (256, 64, 32, 256),       # four blocks a head: pairs under, on and
        (256, 128, 32, 100),      # above the diagonal; blocks of pad rows
        (128, 128, 128, 128),     # one piece: the masked square alone
        (512, 256, 128, 300),
    ])
    def test_flash_pass_matches_a_plain_causal_softmax(self, S, block, sub,
                                                       real):
        """The up-projected pass alone, sizes pinned so that a head takes
        several blocks: output and log-sum-exp below the real length, zero
        and ``NEG_INF`` in the pieces wholly past it."""
        from paddle_tpu.ops.pallas import mla_attention_kernel as mk

        rs = np.random.RandomState(S + sub)
        H, nope, rope, dv = 2, 16, 8, 16
        q = jnp.asarray(rs.randn(S, H, nope + rope), jnp.float32)
        k = jnp.asarray(rs.randn(S, H, nope), jnp.float32)
        kr = jnp.asarray(rs.randn(S, rope), jnp.float32)
        v = jnp.asarray(rs.randn(S, H, dv), jnp.float32)
        o_t, lse = mk.mla_flash_prefill(
            q.transpose(1, 2, 0), k.transpose(1, 0, 2), kr,
            v.transpose(1, 2, 0), real, scale=0.2, block=block, sub=sub,
            interpret=True)
        s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k)
             + jnp.einsum("qhd,kd->hqk", q[..., nope:], kr)) * 0.2
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
        want = jnp.einsum("hqk,khd->hdq", jax.nn.softmax(s, axis=-1), v)
        np.testing.assert_allclose(np.asarray(o_t[..., :real]),
                                   np.asarray(want[..., :real]), atol=2e-5,
                                   rtol=0)
        np.testing.assert_allclose(
            np.asarray(lse[:, :real]),
            np.asarray(jax.nn.logsumexp(s, axis=-1)[:, :real]), atol=2e-5,
            rtol=0)
        dead = -(-real // sub) * sub
        assert not np.asarray(o_t[..., dead:]).any()
        assert (np.asarray(lse[:, dead:]) == mk.NEG_INF).all()

    @pytest.mark.parametrize("n1,n2", [(40, 24), (1, 300), (128, 1)])
    def test_merged_partials_are_one_softmax_over_both_regions(self, n1, n2):
        from paddle_tpu.ops.pallas import mla_attention_kernel as mk

        rs = np.random.RandomState(n1)
        s = jnp.asarray(rs.randn(6, 4, n1 + n2) * 3, jnp.float32)
        v = jnp.asarray(rs.randn(n1 + n2, 16), jnp.float32)
        parts = []
        for sl in (slice(0, n1), slice(n1, None)):
            parts += [jax.nn.softmax(s[..., sl], axis=-1) @ v[sl],
                      jax.nn.logsumexp(s[..., sl], axis=-1)]
        got = mk.merge_partials(*parts)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(jax.nn.softmax(s, axis=-1) @ v),
            atol=2e-6, rtol=0)
        # rows that neither part computed (pad rows: zero, NEG_INF) stay zero
        dead = jnp.full((6, 4), mk.NEG_INF), jnp.zeros((6, 4, 16))
        assert not np.asarray(mk.merge_partials(
            dead[1], dead[0], dead[1], dead[0])).any()


@pytest.mark.parametrize("rows,sizes", [
    (300, [0, 130, 5, 0, 100]),       # empty groups, rows in no group
    (256, [0, 0, 256, 0]),            # one expert gets every row
    (40, [3, 0, 9, 1]),               # fewer rows than a tile
    (256, [0, 0, 0, 0]),              # nothing routed here: a grid of none
])
def test_grouped_matmul_matches_the_loop_over_experts(rows, sizes):
    from paddle_tpu.ops.pallas import moe_kernel as gk

    rs = np.random.RandomState(2)
    lhs = jnp.asarray(rs.randn(rows, 64), jnp.float32)
    rhs = jnp.asarray(rs.randn(len(sizes), 64, 96), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = jax.jit(lambda a, b, c: gk.moe_grouped_matmul(
        a, b, c, interpret=True))(lhs, rhs, gs)
    want = gk.grouped_matmul_reference(lhs, rhs, gs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=0)
    assert not np.asarray(got[sum(sizes):]).any()
    _off, group, _tile, n = gk._visits(gs, -(-rows // gk.TILE_M), gk.TILE_M)
    visited = set(np.asarray(group[:int(n)]).tolist())
    assert visited == {g for g, s in enumerate(sizes) if s}   # no empty one
