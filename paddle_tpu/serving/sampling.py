"""Token sampling for the serving engine: on-device hot path + host oracle.

The hot path samples **inside the compiled decode/prefill step**
(:func:`device_sample`, state in :class:`DeviceSampler`): per-slot
temperature / top-k / top-p / greedy ride as ``[slots]`` arrays, per-slot
``jax.random`` key state is lifted into the program exactly like KV cache
state, and the step returns sampled token ids ``[slots] int32`` that feed
the next step's inputs device-side — no per-token logits pull, which is
what drives the sanitizer's ``serving_decode_host_transfers`` baseline
from 1.0 to 0.0 (ROADMAP item 2).

Top-k and top-p are two cut-offs in the order of the tempered logits, and
nothing sorts the vocabulary for them: each is the largest value for which
a count (tokens at or above it) or a mass (tokens above it) still reaches
its mark, found bit by bit in 32 fused passes over ``[N, V]``
(:func:`_largest_key`) — exact for any ``top_k`` and ``top_p``.  A call
whose live rows are all greedy computes no cut-off and draws nothing (one
argmax).  ``tests/test_device_sampling.py`` keeps the full sort every call
once paid as the oracle: same kept set, same tokens.

Speculative decoding rides the same lanes:
:meth:`DeviceSampler.accept_speculative` performs a whole round's
rejection-sampling acceptance in-graph (greedy: accept iff draft ==
target argmax, emit the argmax on rejection — bitwise-equal to plain
decoding; sampling: accept with ``min(1, p_t/p_d)``, resample the
normalized residual — marginally the target law at every position),
advancing the key lanes once per round and syncing both the target and
draft token lanes to the new pending token.

:func:`sample` is retained as the **host reference implementation** — the
parity oracle the on-device path is tested against (greedy must match
bitwise; seeded top-k/top-p statistically).  It is dtype-explicit:
all distribution math runs in float32, matching the compiled step's f32
logits, instead of the previous silent float64 upcast (which made the
"oracle" compute a different softmax than anything the system serves,
and pretended a precision jax only provides under ``jax_enable_x64``).
The final renormalization for ``rng.choice`` happens in float64 purely to
satisfy numpy's probability-sum check — by then the distribution is
already fixed in f32.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..ops.threshold_search import (largest_key as _largest_key,
                                     order_keys as _order_keys)

__all__ = ["SamplingParams", "sample", "device_sample", "DeviceSampler",
           "sampler_path", "host_prng_key"]

_NEG_INF = np.float32(-1e30)


@dataclass
class SamplingParams:
    """Per-request decoding strategy.

    ``temperature == 0`` → greedy argmax.  ``top_k > 0`` restricts
    sampling to the k highest-probability tokens; ``top_p < 1`` restricts
    it to the smallest nucleus of tokens whose cumulative probability
    reaches ``top_p`` (applied after top-k, on the tempered distribution).

    Tenancy (docs/SERVING.md "Multi-tenant serving"): ``adapter`` names
    a LoRA adapter loaded in the engine's :class:`~.adapters.AdapterPool`
    (None = the base model); ``grammar`` names a registered constrained-
    decoding grammar in its :class:`~.grammar.GrammarTable` (None =
    unconstrained).  Both are *data* — per-slot lane values, never trace
    constants — and both are journaled in the admit record so crash
    recovery replays the same tenant bitwise.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None
    adapter: Optional[str] = None
    grammar: Optional[str] = None

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        for f in ("adapter", "grammar"):
            v = getattr(self, f)
            if v is not None and not isinstance(v, str):
                raise ValueError(f"{f} must be a name (str) or None, "
                                 f"got {type(v).__name__}")


def _host_masked_logits(logits: np.ndarray,
                        params: SamplingParams) -> np.ndarray:
    """Tempered + top-k/top-p-masked logits, float32 throughout — the
    same restriction order as :func:`device_sample`."""
    z = logits / np.float32(params.temperature)
    if params.top_k:
        k = min(params.top_k, z.shape[0])
        kth = np.partition(z, -k)[-k]
        z = np.where(z >= kth, z, _NEG_INF)
    if params.top_p < 1.0:
        zmax = z.max()
        p = np.exp(z - zmax, dtype=np.float32)
        p /= p.sum(dtype=np.float32)
        order = np.argsort(-p, kind="stable")
        csum = np.cumsum(p[order], dtype=np.float32)
        # keep tokens while the cumulative mass BEFORE them is < top_p
        # (always keeps at least the most probable token)
        keep = (csum - p[order]) < np.float32(params.top_p)
        threshold = p[order][keep.sum() - 1]
        z = np.where(p >= threshold, z, _NEG_INF)
    return z


def sample(logits: np.ndarray, params: SamplingParams,
           rng: Optional[np.random.RandomState] = None) -> int:
    """Pick the next token id from a ``[vocab]`` logits row — the host
    reference (parity oracle) for the on-device sampler; float32 math."""
    logits = np.asarray(logits, dtype=np.float32).reshape(-1)
    if params.temperature == 0.0:
        return int(np.argmax(logits))
    z = _host_masked_logits(logits, params)
    z = z - z.max()
    p = np.exp(z, dtype=np.float32)
    p = p.astype(np.float64)          # np.choice's sum-to-1 check only
    p /= p.sum()
    rng = rng or np.random
    return int(rng.choice(p.shape[0], p=p))


def sampler_path(params) -> str:
    """Which way through :func:`device_sample` a step takes whose live
    rows hold ``params`` (an iterable of :class:`SamplingParams`), by the
    rule the program applies to its lanes: ``"greedy"`` (no live row
    samples: no cut-off, no draw) or ``"sampled"``."""
    return ("sampled" if any(p.temperature > 0 for p in params)
            else "greedy")


def _device_masked_logits(logits, temps, top_ks, top_ps):
    """Tempered + top-k/top-p-masked logits ``[N, V]`` — the traced
    mirror of :func:`_host_masked_logits`, vectorized per row.

    Both restrictions are cut-offs in the order of ``z``, and both are
    searched, not sorted for: the k-th largest value is the largest ``v``
    that ``k`` or more tokens reach (tokens tied with it all stay), and
    the nucleus keeps a token while the mass of the tokens *above* it is
    ``< top_p`` (always the most probable one), so its cut-off is the
    largest ``v`` with ``top_p`` or more mass above it.  Exact for any
    ``top_k`` and ``top_p``, in float32.  Rows with ``top_p >= 1`` skip
    the nucleus mask entirely — an f32 sum saturates at 1.0 under a
    peaked distribution, which would otherwise silently truncate the
    tail the host oracle keeps."""
    N, V = logits.shape
    z = logits / temps[:, None]
    keys = _order_keys(z)
    k = jnp.where(top_ks > 0, jnp.clip(top_ks, 1, V), V)
    kth = _largest_key(
        lambda v: jnp.sum(keys >= v[:, None], axis=-1) >= k, N)
    z = jnp.where(keys >= kth[:, None], z, _NEG_INF)
    p = jax.nn.softmax(z, axis=-1)
    cut = _largest_key(
        lambda v: jnp.sum(jnp.where(keys > v[:, None], p, 0.0),
                          axis=-1) >= top_ps, N)
    return jnp.where((keys > cut[:, None]) | (top_ps[:, None] >= 1.0),
                     z, _NEG_INF)


def host_prng_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s two ``uint32`` words, computed on the
    host: the seed's low word second and, where 64-bit types are on, its
    high word first (else 0: the seed has wrapped to 32 bits by then).
    ``tests/test_serving_admission.py`` holds it to JAX's own bitwise."""
    seed = int(seed)
    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], dtype=np.uint32)


def device_sample(logits, temps, top_ks, top_ps, keys, live=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sample one token per row, entirely on device (traced inside the
    compiled decode/prefill step).

    Args:
        logits: ``[N, V]`` float32 final-token logits.
        temps:  ``[N]`` float32 temperatures (``<= 0`` → greedy argmax
                of the raw logits, bitwise equal to the host oracle).
        top_ks: ``[N]`` int32 (``<= 0`` → unrestricted).
        top_ps: ``[N]`` float32 nucleus mass (``>= 1`` → unrestricted).
        keys:   ``[N, 2]`` uint32 per-row jax.random key state.
        live:   ``[N]`` bool, the rows whose token is delivered (None:
                all).  The other rows' lanes are stale, and choose
                nothing: a call whose live rows are all greedy computes
                no cut-off and draws nothing.

    Returns:
        ``(tokens [N] int32, new_keys [N, 2] uint32)`` — keys advance
        once per call for every row, outside any branch, so a re-seeded
        slot replays the same stream whoever shares its batch (the
        preempt/resume determinism contract).
    """
    logits = logits.astype(jnp.float32)
    greedy = temps <= 0.0
    sampled = ~greedy if live is None else live & ~greedy
    split = jax.vmap(jax.random.split)(keys)         # [N, 2, 2]
    new_keys, subkeys = split[:, 0], split[:, 1]
    top = jnp.argmax(logits, axis=-1)

    def draw():
        z = _device_masked_logits(logits, jnp.where(greedy, 1.0, temps),
                                  top_ks, top_ps)
        drawn = jax.vmap(jax.random.categorical)(subkeys, z)
        return jnp.where(greedy, top, drawn)

    tokens = jax.lax.cond(jnp.any(sampled), draw, lambda: top)
    return tokens.astype(jnp.int32), new_keys


class DeviceSampler:
    """Per-slot sampling state threaded through the compiled steps.

    Device state (lifted into programs like KV cache payloads): per-slot
    ``jax.random`` keys, temperature/top-k/top-p parameter lanes, and the
    last sampled token per slot (``tokens`` — the next decode step's
    input ids, read device-side so no host round-trip feeds the loop).
    Host side, the engine **stages** a slot at admission
    (:meth:`lane_rows`, written with the slot's table rows by the engine's
    one staging program; :meth:`stage_slot` writes the same rows a lane at
    a time): parameters are written into the lanes and the
    key lane is re-seeded from the request's seed — identically on first
    admission and on preempt-resume, which is what makes seeded replay
    bitwise deterministic (the old per-request ``RandomState`` contract,
    re-threaded through device key state).

    Constrained decoding (``grammar`` — a :class:`~.grammar.GrammarTable`
    or None): two more ``[slots] int32`` lanes carry each slot's grammar
    id and automaton state (the state *before* the next token).  Logits
    are grammar-masked BEFORE :func:`device_sample`, so the greedy branch
    argmaxes the masked row and seeded sampling draws from the masked
    law; the state lane advances in-graph right after sampling.  Grammar
    id 0 (unconstrained) masks nothing bitwise, so a sampler built with
    a table serves unconstrained slots identically to one without.
    """

    def __init__(self, num_slots: int, grammar=None):
        self.num_slots = int(num_slots)
        self.grammar = grammar
        self.keys = Tensor._wrap(
            jnp.zeros((self.num_slots, 2), dtype=jnp.uint32))
        self.temps = Tensor._wrap(
            jnp.zeros((self.num_slots,), dtype=jnp.float32))
        self.top_ks = Tensor._wrap(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))
        self.top_ps = Tensor._wrap(
            jnp.ones((self.num_slots,), dtype=jnp.float32))
        self.tokens = Tensor._wrap(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))
        self.grammar_ids = Tensor._wrap(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))
        self.grammar_states = Tensor._wrap(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))
        for t in (self.keys, self.temps, self.top_ks, self.top_ps,
                  self.tokens, self.grammar_ids, self.grammar_states):
            t.persistable = True

    # -- host-side staging (between steps; value-only, never a shape) ------

    def lanes(self) -> list:
        """The ``[slots, ...]`` lanes a slot is staged in, in the order of
        :meth:`lane_rows`: key, temperature, top-k, top-p, and with a
        grammar table the grammar id and the automaton's state."""
        out = [self.keys, self.temps, self.top_ks, self.top_ps]
        if self.grammar is not None:
            out += [self.grammar_ids, self.grammar_states]
        return out

    def lane_rows(self, params: SamplingParams, seed: int) -> list:
        """One slot's row of every lane for ``params`` and ``seed``, made on
        the host.  The key is bitwise ``jax.random.PRNGKey(seed)``
        (:func:`host_prng_key`); the grammar lanes are the grammar's id and
        the automaton's start state, so a replayed request walks the same
        path."""
        rows = [host_prng_key(seed), np.float32(params.temperature),
                np.int32(params.top_k), np.float32(params.top_p)]
        if self.grammar is not None:
            rows += [np.int32(self.grammar.gid_of(params.grammar)),
                     np.int32(0)]
        return rows

    def stage_slot(self, slot: int, params: SamplingParams,
                   seed: int) -> None:
        """Write one slot's sampling parameters and re-seed its key lane, a
        lane at a time (the speculative draft's sampler; the engine stages
        its own in one program with the slot's tables, ``serving/staging``).
        Admission and preempt-resume both stage from the same parameters and
        seed, so replay streams are reconstructible by construction."""
        for lane, row in zip(self.lanes(), self.lane_rows(params, seed)):
            lane._set_data(lane._value().at[slot].set(row))

    def reset(self) -> None:
        """Forget all slots (warmup scribbles over slot 0)."""
        self.keys._set_data(
            jnp.zeros((self.num_slots, 2), dtype=jnp.uint32))
        self.temps._set_data(
            jnp.zeros((self.num_slots,), dtype=jnp.float32))
        self.top_ks._set_data(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))
        self.top_ps._set_data(
            jnp.ones((self.num_slots,), dtype=jnp.float32))
        self.tokens._set_data(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))
        self.grammar_ids._set_data(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))
        self.grammar_states._set_data(
            jnp.zeros((self.num_slots,), dtype=jnp.int32))

    # -- traced sampling (inside the compiled steps) -----------------------

    @jax.named_scope("sampler.sample")
    def sample_slot(self, slot, logits_row):
        """Prefill-side: sample ONE slot's first token from its ``[V]``
        last-position logits.  ``slot`` may be traced; key and token
        lanes update through scatter writes, so one compiled prefill
        serves every slot."""
        s = jnp.asarray(slot, dtype=jnp.int32).reshape(())
        keys = self.keys._value()
        row = jnp.stack([
            jax.lax.dynamic_index_in_dim(t._value(), s, 0, keepdims=False)
            for t in (self.temps, self.top_ps)])
        top_k = jax.lax.dynamic_index_in_dim(
            self.top_ks._value(), s, 0, keepdims=False)
        key = jax.lax.dynamic_index_in_dim(keys, s, 0, keepdims=False)
        logits_row = logits_row.astype(jnp.float32)
        if self.grammar is not None:
            # grammar-mask BEFORE sampling (the greedy branch argmaxes
            # its input, so masking here constrains greedy too); id 0
            # rows select the original values through, bitwise
            gid = jax.lax.dynamic_index_in_dim(
                self.grammar_ids._value(), s, 0, keepdims=False)
            gst = jax.lax.dynamic_index_in_dim(
                self.grammar_states._value(), s, 0, keepdims=False)
            logits_row = self.grammar.mask_rows(logits_row, gid, gst)
        tok, new_key = device_sample(
            logits_row[None], row[0][None],
            top_k[None], row[1][None], key[None])
        self.keys._set_data(keys.at[s].set(new_key[0]))
        self.tokens._set_data(
            self.tokens._value().at[s].set(tok[0]))
        if self.grammar is not None:
            self.grammar_states._set_data(
                self.grammar_states._value().at[s].set(
                    self.grammar.advance(gid, gst, tok[0])))
        return tok[0]

    @jax.named_scope("sampler.sample")
    def sample_all(self, logits, active):
        """Decode-side: sample every slot from ``[slots, V]`` logits;
        advances every key lane and rewrites the token lane (idle slots
        sample garbage that is never delivered — their lanes re-seed at
        the next admission).  ``active`` is the step's ``[slots]`` mask of
        running slots: an idle slot keeps the lanes of the request that
        left it, and they must not choose the step's path
        (:func:`device_sample`'s ``live``)."""
        logits = logits.astype(jnp.float32)
        if self.grammar is not None:
            gids = self.grammar_ids._value()
            gsts = self.grammar_states._value()
            logits = self.grammar.mask_rows(logits, gids, gsts)
        toks, new_keys = device_sample(
            logits, self.temps._value(),
            self.top_ks._value(), self.top_ps._value(),
            self.keys._value(), live=active > 0)
        self.keys._set_data(new_keys)
        self.tokens._set_data(toks)
        if self.grammar is not None:
            self.grammar_states._set_data(
                self.grammar.advance(gids, gsts, toks))
        return toks

    def _masked_probs(self, logits):
        """Per-slot-masked sampling distributions for a ``[S, W, V]``
        verify window: each slot's temperature/top-k/top-p lanes applied
        to every window position (softmax of the masked, tempered
        logits — exactly the distribution :func:`device_sample` draws
        from, so acceptance ratios price the real proposal/target
        laws).  Grammar masking happens upstream, on the logits both
        models' windows share — see :meth:`accept_speculative`."""
        S, W, V = logits.shape
        temps = jnp.repeat(jnp.where(self.temps._value() <= 0.0, 1.0,
                                     self.temps._value()), W)
        z = _device_masked_logits(
            logits.reshape(S * W, V).astype(jnp.float32), temps,
            jnp.repeat(self.top_ks._value(), W),
            jnp.repeat(self.top_ps._value(), W))
        return jax.nn.softmax(z, axis=-1).reshape(S, W, V)

    def accept_speculative(self, target_logits, draft_logits,
                           draft_tokens, cap, draft_sampler
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Rejection-sampling acceptance of one speculative round,
        entirely in-graph (traced inside the compiled verify step — the
        zero-host-transfer decode invariant extends to speculation).

        Args:
            target_logits: ``[S, W, V]`` target-model logits over the
                verify window (position ``i`` scores the token AFTER
                input ``i``; W = k + 1).
            draft_logits:  ``[S, W, V]`` draft-model logits over the
                same window (recomputed in the verify step, so the
                acceptance ratio uses exactly the law the proposals
                were drawn from — and the draft KV for the window is
                complete even on full acceptance).
            draft_tokens:  ``[S, k]`` the round's draft proposals.
            cap:           ``[S]`` int32 — per-slot emission cap
                (token budget / cache capacity, computed host-side);
                the emission stream is truncated to it, which is
                distribution-preserving (every emitted position is
                marginally the target law).
            draft_sampler: the draft model's :class:`DeviceSampler`
                (its param lanes define the proposal distribution; its
                token lane is synced to the new pending token so the
                next round's first draft step feeds device-side).

        Returns:
            ``(emitted [S, W] int32, m [S] int32)`` — ``emitted[:m]``
            is the round's delivered stream (accepted draft prefix plus
            one bonus/resample token, ``1 <= m <= min(W, cap)``);
            entries past ``m`` are junk the host never reads.

        Greedy slots (temperature 0) accept a draft token iff it equals
        the target argmax and emit the target argmax on rejection — so
        every emitted token IS the target argmax and greedy speculative
        output is bitwise identical to non-speculative decoding.
        Sampling slots follow standard speculative rejection sampling
        (accept with ``min(1, p_t/p_d)``, resample the normalized
        residual ``max(p_t - p_d, 0)`` on rejection, plain target draw
        for the bonus position) — marginally the target distribution at
        every position.  Key lanes advance once per round; re-seeding a
        slot replays the identical round stream (the preempt-resume /
        crash-recovery determinism contract)."""
        S, W, V = target_logits.shape
        k = W - 1
        greedy = self.temps._value() <= 0.0                   # [S]
        target_logits = target_logits.astype(jnp.float32)
        draft_logits = draft_logits.astype(jnp.float32)
        if self.grammar is not None:
            # Grammar masks apply IDENTICALLY to the draft and target
            # laws at every window position — both renormalize on the
            # same legal support, so the acceptance proof (min(1,
            # pt/pd) accept + max(pt-pd, 0) residual, whose support is
            # a subset of pt's) is preserved verbatim.  Window state j
            # is the round-start lane state folded through the draft
            # proposals — exactly the states the draft sampler held
            # when it drew proposal j, so pd prices the law the
            # proposals actually came from.
            gids = self.grammar_ids._value()
            g_start = self.grammar_states._value()
            st = g_start
            wmask = []
            for j in range(W):
                wmask.append(self.grammar.mask._value()[gids, st])
                if j < k:
                    st = self.grammar.advance(gids, st,
                                              draft_tokens[:, j])
            gmask = jnp.stack(wmask, axis=1)              # [S, W, V]
            target_logits = jnp.where(gmask, target_logits, _NEG_INF)
            draft_logits = jnp.where(gmask, draft_logits, _NEG_INF)
        pt = self._masked_probs(target_logits)                # [S, W, V]
        pd = draft_sampler._masked_probs(draft_logits)        # [S, W, V]
        # position k carries no proposal: zero its draft mass so the
        # "residual" there is the plain target distribution (the bonus
        # draw) — one formula covers reject-resample AND bonus
        pd = pd.at[:, k, :].set(0.0)
        g = jnp.argmax(target_logits.astype(jnp.float32),
                       axis=-1).astype(jnp.int32)             # [S, W]
        # accept test per draft position
        pt_d = jnp.take_along_axis(
            pt[:, :k, :], draft_tokens[..., None], axis=2)[..., 0]
        pd_d = jnp.take_along_axis(
            pd[:, :k, :], draft_tokens[..., None], axis=2)[..., 0]
        keys = self.keys._value()
        split = jax.vmap(lambda kk: jax.random.split(kk, 2 + W))(keys)
        new_keys, ukeys, ckeys = split[:, 0], split[:, 1], split[:, 2:]
        u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(ukeys)
        ratio = pt_d / jnp.maximum(pd_d, jnp.float32(1e-30))
        accept = jnp.where(greedy[:, None],
                           draft_tokens == g[:, :k],
                           u < jnp.minimum(ratio, 1.0))       # [S, k]
        n_acc = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                        axis=1)                               # [S]
        # replacement token per position: residual resample (sampling)
        # or target argmax (greedy); identical target/draft laws leave
        # an all-zero residual — fall back to the target law itself
        res = jnp.maximum(pt - pd, 0.0)
        res = jnp.where(
            (jnp.sum(res, axis=-1) <= 0.0)[..., None], pt, res)
        rep = jax.vmap(jax.vmap(jax.random.categorical))(
            ckeys, jnp.log(res)).astype(jnp.int32)            # [S, W]
        rep = jnp.where(greedy[:, None], g, rep)
        # emission stream: accepted draft prefix, then the replacement
        d_pad = jnp.concatenate(
            [draft_tokens.astype(jnp.int32),
             jnp.zeros((S, 1), dtype=jnp.int32)], axis=1)
        idx = jnp.arange(W, dtype=jnp.int32)[None, :]
        emitted = jnp.where(idx < n_acc[:, None], d_pad, rep)
        m = jnp.clip(n_acc.astype(jnp.int32) + 1, 1,
                     jnp.maximum(cap.astype(jnp.int32), 1))
        pend = jnp.take_along_axis(emitted, (m - 1)[:, None],
                                   axis=1)[:, 0]
        self.keys._set_data(new_keys)
        self.tokens._set_data(pend)
        # the draft chains off the same pending token next round
        draft_sampler.tokens._set_data(pend)
        if self.grammar is not None:
            # fold the automaton over the round's ACTUAL emissions
            # (accepted prefix + replacement, truncated to m) and sync
            # BOTH samplers' state lanes — next round's draft steps and
            # verify window start from the same state, in lockstep
            st = g_start
            for j in range(W):
                nxt = self.grammar.advance(gids, st, emitted[:, j])
                st = jnp.where(j < m, nxt, st)
            self.grammar_states._set_data(st)
            draft_sampler.grammar_states._set_data(st)
        return emitted, m
