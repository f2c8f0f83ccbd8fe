"""Overload-robust serving (ISSUE 8): priority classes with deferral
aging, preemption with cheap prefix-cache resume, SLO-aware shedding,
and the queued-deadline admission bugfix.

Host-side policy tests (victim selection, the wait estimator, shed and
backpressure context) never compile anything; the compiled tests share
two module-scope paged engines (2 buckets + decode each) so the file
pays for exactly two warmups.  Tier-1 critical: tools/collect_gate.py
fails CI if this file stops collecting or grows a ``slow`` mark.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (
    Engine, QueueFull, ShedReject,
    PRIORITY_HIGH, PRIORITY_LOW, PRIORITY_NORMAL, SamplingParams,
)


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def peng(gpt):
    """Shared compiled paged priority engine (aging effectively off so
    ordering tests control it explicitly); reused across tests with
    metrics asserted as deltas."""
    eng = Engine(gpt, num_slots=2, max_seq=32, min_bucket=16,
                 block_size=16,
                 max_preemptions=2, priority_aging_s=30.0)
    eng.warmup()
    return eng


def _full_logits(model, seq):
    with paddle.no_grad():
        out = model(paddle.to_tensor(np.asarray(seq, np.int64)[None]))
    return out.numpy()[0]


def _assert_greedy_chain(model, prompt, out_ids):
    """``out_ids`` must BE the no-cache greedy generation for ``prompt``
    — i.e. bitwise identity with an uninterrupted greedy run (one causal
    forward yields every step's reference logits)."""
    L = len(prompt)
    full = list(prompt) + [int(t) for t in out_ids]
    logits = _full_logits(model, full[:-1])
    for i, t in enumerate(out_ids):
        assert int(np.argmax(logits[L - 1 + i])) == int(t), (i, t)


class TestPriorityPolicy:
    """Host-only policy semantics: no engine here ever compiles."""

    def test_priority_normalization(self, gpt):
        eng = Engine(gpt, num_slots=1, max_seq=16, min_bucket=16)
        r = eng.add_request([1, 2], priority="high")
        assert r.priority == PRIORITY_HIGH
        assert eng.add_request([1, 2], priority="LOW").priority == \
            PRIORITY_LOW
        assert eng.add_request([1, 2]).priority == PRIORITY_NORMAL
        assert eng.add_request([1, 2], priority=7).priority == 7
        with pytest.raises(ValueError, match="unknown priority"):
            eng.add_request([1, 2], priority="urgent")
        with pytest.raises(ValueError):
            Engine(gpt, max_seq=16, max_preemptions=-1)
        with pytest.raises(ValueError):
            Engine(gpt, max_seq=16, priority_aging_s=0.0)

    def test_victim_policy(self, gpt):
        """Lowest base class first, least progress next, youngest last;
        budget-exhausted requests are immune; aging grants queue
        position but never preemption rights (base-class comparison)."""
        eng = Engine(gpt, num_slots=4, max_seq=16, min_bucket=16,
                     max_preemptions=2)

        def running(slot, prio, tokens, rid):
            r = eng.add_request([1, 2], priority=prio)
            eng.queue.remove(r)
            r.slot, r.state, r.request_id = slot, "running", rid
            r.output_ids = list(range(tokens))
            eng.running[slot] = r
            return r

        lo_old = running(0, PRIORITY_LOW, 3, 10)
        lo_new = running(1, PRIORITY_LOW, 3, 11)    # same progress, younger
        lo_far = running(2, PRIORITY_LOW, 5, 12)    # more progress
        nm = running(3, PRIORITY_NORMAL, 0, 13)
        cand_hi = eng.add_request([3, 4], priority="high")
        # lowest class, fewest tokens, youngest wins
        assert eng._pick_victim(cand_hi) is lo_new
        lo_new.preemptions = 2                      # budget exhausted
        assert eng._pick_victim(cand_hi) is lo_old
        for r in (lo_old, lo_far):
            r.preemptions = 2
        assert eng._pick_victim(cand_hi) is nm      # next class up
        nm.preemptions = 2
        assert eng._pick_victim(cand_hi) is None    # everyone immune
        # equal class never preempts, whatever the aging boost says
        cand_nm = eng.add_request([3, 4], priority="normal")
        cand_nm.t_enqueue -= 1e6                    # enormous aging boost
        nm.preemptions = 0
        assert eng._effective_priority(cand_nm, time.perf_counter()) > \
            PRIORITY_HIGH
        assert eng._pick_victim(cand_nm) is None
        # max_preemptions=0 disables the machinery outright
        eng.max_preemptions = 0
        assert eng._pick_victim(cand_hi) is None

    def test_estimator_and_shed(self, gpt):
        """Cold engines never shed (the estimator abstains); a loaded
        engine sheds deadline-carrying admissions with machine-readable
        depth/retry_after_s; deadline-less requests are never shed."""
        eng = Engine(gpt, num_slots=1, max_seq=32, min_bucket=16)
        assert eng.estimate_queue_wait_s() == 0.0   # no ITL history yet
        eng.add_request([1, 2, 3], max_new_tokens=16)
        eng.add_request([4, 5, 6], max_new_tokens=16)
        # cold abstention: even with a queue, no measurements = no shed
        rq = eng.add_request([7, 8], max_new_tokens=4, deadline_s=0.001)
        assert rq.state == "queued"
        eng.queue.remove(rq)
        eng.metrics.itl_s.extend([0.005] * 20)      # decode history
        wait = eng.estimate_queue_wait_s()
        assert wait > 0.001
        base_rej = eng.metrics.requests_rejected
        with pytest.raises(ShedReject) as ei:
            eng.add_request([7, 8], max_new_tokens=4, deadline_s=0.001)
        e = ei.value
        assert isinstance(e, QueueFull)             # one handler catches both
        assert e.depth == 2 and e.retry_after_s == pytest.approx(wait,
                                                                 abs=0.05)
        assert e.request.state == "rejected" and "shed" in e.request.error
        assert e.request.error_ctx == {"depth": 2,
                                       "retry_after_s": e.retry_after_s}
        assert eng.metrics.requests_shed == 1
        assert eng.metrics.requests_rejected == base_rej + 1
        st = eng.stats()
        assert st["overload"] == {"preemptions": 0, "shed": 1}
        # a generous deadline clears the estimate: admitted
        ok = eng.add_request([7, 8], max_new_tokens=4, deadline_s=60.0)
        assert ok.state == "queued"
        # no deadline -> never shed, however deep the backlog
        assert eng.add_request([9], max_new_tokens=4).state == "queued"
        # a higher-priority admission waits behind less backlog
        assert eng.estimate_queue_wait_s(PRIORITY_HIGH) < \
            eng.estimate_queue_wait_s(PRIORITY_LOW)

    def test_entitled_preemptor_never_shed(self, gpt):
        """Preemption entitlement trumps the backlog estimate: a
        deadline-carrying high-priority admission that would evict its
        way into a slot this step is never shed on the running backlog
        (the traffic preemption exists to protect), while a contended
        or victimless admission still sheds on the estimate."""
        eng = Engine(gpt, num_slots=1, max_seq=16, min_bucket=16,
                     max_preemptions=2)
        lo = eng.add_request([1, 2], priority="low")
        eng.queue.remove(lo)
        lo.slot, lo.state = eng.free_slots.pop(), "running"
        eng.running[lo.slot] = lo
        eng.metrics.itl_s.extend([0.05] * 20)   # deep decode history
        assert eng.estimate_queue_wait_s(PRIORITY_HIGH) > 0.001
        hi = eng.add_request([3, 4], max_new_tokens=4, deadline_s=0.001,
                             priority="high")
        assert hi.state == "queued"             # entitled: not shed
        # an equal-class contender already queued removes the
        # entitlement — back to the (hopeless) estimate: shed
        with pytest.raises(ShedReject):
            eng.add_request([5, 6], max_new_tokens=4, deadline_s=0.001,
                            priority="high")
        eng.queue.remove(hi)
        # an aged VICTIMLESS contender never blocks the entitlement
        # (mirrors _best_preempting_candidate: it can't win the
        # preemption pass, so it must not force a shed either)
        aged = eng.add_request([7, 8], priority="low")
        aged.t_enqueue -= 1e6                   # enormous aging boost
        assert eng._effective_priority(aged, time.perf_counter()) > \
            PRIORITY_HIGH
        assert eng._pick_victim(aged) is None   # low can't evict low
        still = eng.add_request([3, 4], max_new_tokens=4,
                                deadline_s=0.001, priority="high")
        assert still.state == "queued"          # entitled: not shed
        eng.queue.remove(aged)
        eng.queue.remove(still)
        # no victim (budget-exhausted running request is immune): shed
        lo.preemptions = eng.max_preemptions
        with pytest.raises(ShedReject):
            eng.add_request([5, 6], max_new_tokens=4, deadline_s=0.001,
                            priority="high")

    def test_queue_full_carries_retry_after(self, gpt):
        eng = Engine(gpt, num_slots=1, max_seq=16, min_bucket=16,
                     max_queue=1)
        eng.metrics.itl_s.extend([0.01] * 5)
        eng.add_request([1, 2], max_new_tokens=8)
        with pytest.raises(QueueFull) as qi:
            eng.add_request([3, 4], max_new_tokens=8)
        e = qi.value
        assert e.depth == 1 and e.retry_after_s is not None
        assert e.request.error_ctx == {"depth": 1,
                                       "retry_after_s": e.retry_after_s}
        assert "retry_after_s" in e.request.error

    def test_effective_priority_aging_ordering(self, gpt):
        """Deferral aging: +1 class per priority_aging_s of wait, so the
        queue selector eventually prefers an old low-priority request
        over fresh higher classes (no starvation)."""
        eng = Engine(gpt, num_slots=1, max_seq=16, min_bucket=16,
                     priority_aging_s=0.05)
        old_low = eng.add_request([1, 2], priority="low")
        old_low.t_enqueue -= 0.11                   # two aging intervals
        fresh_nm = eng.add_request([3, 4], priority="normal")
        now = time.perf_counter()
        assert eng._effective_priority(old_low, now) == PRIORITY_LOW + 2
        assert eng._effective_priority(fresh_nm, now) == PRIORITY_NORMAL
        assert eng.queue[eng._best_queued_index(now)] is old_low
        # without the age gap, class order rules and ties are FIFO
        old_low.t_enqueue = fresh_nm.t_enqueue
        assert eng.queue[eng._best_queued_index(now)] is fresh_nm
        eng.priority_aging_s = None                 # aging disabled
        old_low.t_enqueue -= 100.0
        assert eng.queue[eng._best_queued_index(
            time.perf_counter())] is fresh_nm


class TestPreemption:
    """The ISSUE 8 acceptance: preemption parity, stream restart, cheap
    resume, budget immunity — all with zero steady-state recompiles."""

    def test_preemption_parity_and_stream_restart(self, gpt, peng):
        """A request preempted mid-decode and resumed produces greedy
        output bitwise-identical to an uninterrupted run, its stream
        restarting from token 0 with the ``preempted`` marker — and the
        whole episode adds zero compile misses."""
        eng = peng
        warm = eng.metrics.compile_misses
        base_pre = eng.metrics.requests_preempted
        rs = np.random.RandomState(5)
        streamed = []

        def cb(t, r):
            streamed.append((r.request_id, r.preemptions, t))

        p1, p2 = (rs.randint(0, 128, (L,)).tolist() for L in (5, 6))
        a1 = eng.add_request(p1, max_new_tokens=8, priority="low",
                             stream_cb=cb)
        a2 = eng.add_request(p2, max_new_tokens=8, priority="low",
                             stream_cb=cb)
        eng.step()
        eng.step()                       # both mid-decode
        assert a1.state == a2.state == "running"
        p_hi = rs.randint(0, 128, (4,)).tolist()
        b = eng.add_request(p_hi, max_new_tokens=4, priority="high")
        eng.run()
        # victim: equal class and progress -> the youngest (a2)
        assert a2.preempted and a2.preemptions == 1
        assert not a1.preempted
        assert eng.metrics.requests_preempted - base_pre == 1
        # every request finished with full greedy output == uninterrupted
        for p, r in ((p1, a1), (p2, a2), (p_hi, b)):
            assert r.finished and len(r.output_ids) == r.max_new_tokens
            _assert_greedy_chain(gpt, p, r.output_ids)
        # stream contract: tokens flowed pre-kill under preemptions == 0,
        # then the replay restarted from token 0, marked, and the
        # replay-era stream IS the full final output
        pre = [t for rid, n, t in streamed
               if rid == a2.request_id and n == 0]
        replay = [t for rid, n, t in streamed
                  if rid == a2.request_id and n == 1]
        assert pre, "the victim streamed tokens before the preemption"
        assert replay == a2.output_ids
        # zero new compile keys: the resume reused the warmed buckets
        assert eng.metrics.compile_misses == warm
        assert eng.health()["kv_block_invariants"] == "ok"
        assert sorted(eng.free_slots) == [0, 1]

    def test_seeded_sampling_resumes_deterministically(self, gpt, peng):
        """A seeded-temperature victim replays the same tokens: the
        preemption re-seeds its RNG, so replay-from-prompt is bitwise
        deterministic for seeded sampling too."""
        eng = peng
        warm = eng.metrics.compile_misses
        rs = np.random.RandomState(6)
        p = rs.randint(0, 128, (5,)).tolist()
        sp = SamplingParams(temperature=1.0, seed=77)
        ref = eng.add_request(p, max_new_tokens=6, sampling=sp)
        eng.run()                        # uninterrupted seeded reference
        assert ref.finished
        vic = eng.add_request(p, max_new_tokens=6,
                              sampling=SamplingParams(temperature=1.0,
                                                      seed=77),
                              priority="low")
        filler = eng.add_request(rs.randint(0, 128, (4,)).tolist(),
                                 max_new_tokens=6, priority="low")
        eng.step()
        eng.step()
        hi = eng.add_request(rs.randint(0, 128, (3,)).tolist(),
                             max_new_tokens=3, priority="high")
        eng.run()
        assert vic.preempted or filler.preempted    # one was evicted
        assert all(r.finished for r in (vic, filler, hi))
        assert vic.output_ids == ref.output_ids
        assert eng.metrics.compile_misses == warm

    def test_device_key_state_resume_top_k_top_p(self, gpt, peng):
        """ISSUE 11 extension of the bitwise resume contract: sampling
        now runs ON DEVICE (per-slot jax.random key lanes in the
        compiled step), and a preempted request's resume re-seeds its
        key lane from the request seed at re-admission — so the full
        top-k/top-p seeded restriction replays bitwise too, not just
        plain temperature."""
        eng = peng
        warm = eng.metrics.compile_misses
        rs = np.random.RandomState(16)
        p = rs.randint(0, 128, (6,)).tolist()
        sp = dict(temperature=0.8, top_k=10, top_p=0.9, seed=314)
        ref = eng.add_request(p, max_new_tokens=6,
                              sampling=SamplingParams(**sp))
        eng.run()                        # uninterrupted seeded reference
        assert ref.finished
        vic = eng.add_request(p, max_new_tokens=6,
                              sampling=SamplingParams(**sp),
                              priority="low")
        filler = eng.add_request(rs.randint(0, 128, (5,)).tolist(),
                                 max_new_tokens=6, priority="low")
        eng.step()
        eng.step()
        hi = eng.add_request(rs.randint(0, 128, (3,)).tolist(),
                             max_new_tokens=3, priority="high")
        eng.run()
        assert vic.preempted or filler.preempted
        assert all(r.finished for r in (vic, filler, hi))
        assert vic.output_ids == ref.output_ids
        # on-device restriction actually bit: everything stays in-vocab
        assert all(0 <= t < 128 for t in vic.output_ids)
        assert eng.metrics.compile_misses == warm

    def test_preempt_for_blocks_cheap_resume(self, gpt):
        """The block-pool half of the tentpole: a high-priority
        admission the pool cannot serve evicts the low-priority victim's
        blocks; the victim's prompt blocks enter the prefix cache BEFORE
        release, so its resume prefills only the uncached tail bucket —
        measurably cheaper than its original prefill."""
        eng = Engine(gpt, num_slots=2, max_seq=32, min_bucket=16,
                     block_size=16, num_kv_blocks=4,
                     max_preemptions=2, priority_aging_s=30.0)
        eng.warmup()
        warm = eng.metrics.compile_misses
        rs = np.random.RandomState(7)
        pa = rs.randint(0, 128, (17,)).tolist()     # bucket 32: 2 blocks
        pb = rs.randint(0, 128, (17,)).tolist()
        A = eng.add_request(pa, max_new_tokens=6, priority="low")
        eng.step()
        eng.step()
        assert A.state == "running" and A.prefill_bucket == 32
        hits_before = eng.prefix_cache.stats()["hit_blocks"]
        B = eng.add_request(pb, max_new_tokens=4, priority="high")
        eng.run()
        # A was evicted for BLOCKS (a slot was free the whole time) and
        # resumed via a prefix hit: tail bucket 16, not the original 32
        assert A.preempted and A.preemptions == 1
        assert A.finished and A.prefill_bucket == 16
        assert B.finished and B.prefill_bucket == 32
        assert eng.prefix_cache.stats()["hit_blocks"] > hits_before
        for p, r in ((pa, A), (pb, B)):
            _assert_greedy_chain(gpt, p, r.output_ids)
        assert eng.metrics.compile_misses == warm
        assert eng.health()["kv_block_invariants"] == "ok"

    def test_preemption_budget_makes_request_immune(self, gpt, peng):
        """Past max_preemptions evictions a request runs to completion:
        later high-priority arrivals wait instead of starving it."""
        eng = peng
        base_pre = eng.metrics.requests_preempted
        rs = np.random.RandomState(8)
        a1 = eng.add_request(rs.randint(0, 128, (4,)).tolist(),
                             max_new_tokens=6, priority="low")
        a2 = eng.add_request(rs.randint(0, 128, (5,)).tolist(),
                             max_new_tokens=6, priority="low")
        eng.step()
        for r in (a1, a2):
            assert r.state == "running"
            r.preemptions = eng.max_preemptions     # budget spent
        hi = eng.add_request(rs.randint(0, 128, (3,)).tolist(),
                             max_new_tokens=2, priority="high")
        eng.run()
        assert eng.metrics.requests_preempted == base_pre   # nobody evicted
        assert all(r.finished for r in (a1, a2, hi))
        assert len(a1.output_ids) == 6 and len(a2.output_ids) == 6

    def test_priority_ordering_under_contention(self, gpt, peng):
        """With preemption off, classes only reorder the queue: the
        high-priority request takes the first slot that frees, ahead of
        the earlier-arrived low one."""
        eng = peng
        eng.max_preemptions, saved = 0, eng.max_preemptions
        try:
            rs = np.random.RandomState(9)
            a1 = eng.add_request(rs.randint(0, 128, (4,)).tolist(),
                                 max_new_tokens=2)
            a2 = eng.add_request(rs.randint(0, 128, (5,)).tolist(),
                                 max_new_tokens=8)
            eng.step()                   # both running; a1 finishes first
            lo = eng.add_request(rs.randint(0, 128, (3,)).tolist(),
                                 max_new_tokens=2, priority="low")
            hi = eng.add_request(rs.randint(0, 128, (6,)).tolist(),
                                 max_new_tokens=2, priority="high")
            while hi.state == "queued":
                eng.step()
            # the later-arrived high class leapfrogged the queued low
            assert lo.state == "queued"
            eng.run()
            assert all(r.finished for r in (a1, a2, lo, hi))
        finally:
            eng.max_preemptions = saved

    def test_aged_head_does_not_block_entitled_preemptor(self, gpt, peng):
        """Regression: an aged low-priority request at the effective
        head of the queue holds NO preemption rights — but it must not
        block the fresh high-priority request behind it from evicting
        the normal-priority victims IT is entitled to.  The high one
        preempts past the aged head; the head keeps its queue position
        for the next natural retirement."""
        eng = peng
        eng.priority_aging_s, saved = 0.01, eng.priority_aging_s
        try:
            rs = np.random.RandomState(10)
            n1 = eng.add_request(rs.randint(0, 128, (4,)).tolist(),
                                 max_new_tokens=8, priority="normal")
            n2 = eng.add_request(rs.randint(0, 128, (5,)).tolist(),
                                 max_new_tokens=8, priority="normal")
            eng.step()                   # both normals running
            aged_low = eng.add_request(rs.randint(0, 128, (3,)).tolist(),
                                       max_new_tokens=2, priority="low")
            aged_low.t_enqueue -= 1.0    # effective priority far above high
            hi = eng.add_request(rs.randint(0, 128, (6,)).tolist(),
                                 max_new_tokens=4, priority="high")
            now = time.perf_counter()
            assert eng._effective_priority(aged_low, now) > \
                eng._effective_priority(hi, now)
            eng.step()                   # hi preempts a normal, past the head
            assert hi.state == "running"
            assert aged_low.state == "queued"
            assert n1.preempted or n2.preempted
            eng.run()
            assert all(r.finished for r in (n1, n2, aged_low, hi))
        finally:
            eng.priority_aging_s = saved

    def test_queued_deadline_expiry_pays_no_prefill(self, gpt, peng):
        """ISSUE 8 satellite bugfix: a deadline that expires while the
        request is still QUEUED (here: during an earlier admission in
        the same step) retires it without touching the device — no
        prefill, no admission, no bucket counter movement."""
        eng = peng
        base_admit = eng.metrics.requests_admitted
        base_dl = eng.metrics.deadline_expired
        base_buckets = dict(eng.metrics.prefills_by_bucket)
        r1 = eng.add_request([1, 2, 3], max_new_tokens=2,
                             stream_cb=lambda t, r: time.sleep(0.03))
        r2 = eng.add_request([4, 5, 6], max_new_tokens=2,
                             deadline_s=0.01)
        eng.run()                        # r1's first-token cb outlives r2
        assert r1.finished
        assert r2.state == "failed" and "deadline" in r2.error
        assert r2.output_ids == []       # not one token, not one prefill
        assert eng.metrics.requests_admitted - base_admit == 1
        assert eng.metrics.deadline_expired - base_dl == 1
        got = dict(eng.metrics.prefills_by_bucket)
        got[r1.prefill_bucket] -= 1      # exactly r1's prefill, no other
        assert {k: v for k, v in got.items() if v} == \
            {k: v for k, v in base_buckets.items() if v}
        assert sorted(eng.free_slots) == [0, 1]
