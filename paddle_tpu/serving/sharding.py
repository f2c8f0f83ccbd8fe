"""Tensor-parallel sharded serving — mesh plumbing and state placement.

``Engine(mesh=serving_mesh(mp))`` turns the single-chip engine into a
model-parallel one without touching a single compiled step body.  The
pieces and why they compose (docs/SERVING.md "Sharded serving"):

- **Weights shard over the ``model`` axis for free.**  The flagship
  models are already built from the Megatron-TP layers
  (``ColumnParallelLinear`` / ``RowParallelLinear`` /
  ``VocabParallelEmbedding``), whose parameters carry ``PartitionSpec``
  annotations and whose forwards ``mark_sharding`` their activations.
  Both are inert without a mesh; :meth:`ServingShard.place_model` places
  every parameter under its spec and :meth:`ServingShard.context`
  installs the serving mesh as the global mesh for the scope of each
  compiled call, so the SAME model code the single-chip engine traces
  becomes a GSPMD tensor-parallel program.

- **The KV pool shards by ``kv_heads``.**  The pool is one 4-D buffer
  per layer and side with kv_heads at dim 2 (``[blocks, block_size,
  kv_heads, lane_dim]``), and attention is head-batched: every
  contraction is independent per head, so a shard holding
  ``kv_heads/mp`` whole heads (GQA groups stay local — ``kv_heads % mp
  == 0`` is validated up front) runs ``decode_attention`` with ZERO
  cross-shard traffic.  Only the per-layer TP collectives (row-parallel
  out-proj/fc2) cross chips.

- **Everything host-side stays replicated metadata.**  The block
  allocator, prefix cache, scheduler, journal, and the
  :class:`DeviceSampler` param/key/token lanes describe ONE logical
  decision stream driving all shards — the lanes, block tables, and
  length vectors are placed replicated (``P()``) so every shard holds
  the same values and the compiled steps read them without collectives.

- **The executable-cache key space is UNCHANGED.**  ``to_static``'s
  program cache keys on shape/dtype only, never sharding — a sharded
  engine compiles exactly the manifest's program set per mesh shape
  (``tools/shape_manifest.json`` gains one section per mesh-shape key),
  and zero steady-state recompiles carries over verbatim.

- **Mesh size 1 degenerates exactly.**  ``_filter_spec`` drops size-1
  axes, so every placement is ``P()`` and every constraint a no-op —
  ``Engine(mesh=serving_mesh(1))`` is bitwise the unsharded engine.

Placement is write-through (``_set_data`` on the existing tensors), so
it must be re-applied wherever host-side code replaces device arrays
wholesale: after ``warmup()``'s state reset and after
``update_weights``'s state-dict write — :meth:`ServingShard.place_state`
/ :meth:`ServingShard.place_model` are idempotent re-pinning calls, not
one-shot constructors.

CPU tier-1 verifies all of this on a host-device mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count``), the same trick
the TP training tests use.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, PartitionSpec as P

from ..distributed import mesh as mesh_mod
from ..distributed.sharding_spec import (
    MODEL_AXIS, _divisible, _filter_spec, place_array,
)

__all__ = ["ServingShard", "serving_mesh", "mesh_shape_key",
           "viable_ladder", "degrade_step", "KV_POOL_SPEC",
           "KV_LAYER_SPEC"]

#: The pool is one 4-D buffer per layer and side, ``[blocks, block_size,
#: kv_heads, lane_dim]``, kv_heads at dim 2.  Heads split over the model
#: axis, every other dim (and the block tables / lengths / sampler lanes)
#: replicated.
KV_LAYER_SPEC = P(None, None, MODEL_AXIS, None)
#: The speculative draft's dense cache only (``[slots, layers, max_seq,
#: kv_heads, head_dim]``, kv_heads at dim 3).
KV_POOL_SPEC = P(None, None, None, MODEL_AXIS, None)


def serving_mesh(model_parallel: int,
                 devices: Optional[Sequence] = None) -> Mesh:
    """A one-axis serving mesh ``{"model": mp}`` over ``devices``
    (default: the first ``mp`` of ``jax.devices()``).

    The serving mesh deliberately carries ONLY the model axis: batch
    ("data"/"sharding") and sequence ("sep") constraints inside the
    model forwards filter to no-ops, so a serving step is pure TP —
    the fleet provides data parallelism as shard *groups*, one engine
    per group, each on its own disjoint mesh.
    """
    mp = int(model_parallel)
    if mp < 1:
        raise ValueError(f"serving_mesh: model_parallel must be >= 1, "
                         f"got {model_parallel}")
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    if len(devices) < mp:
        raise ValueError(
            f"serving_mesh: model_parallel={mp} needs {mp} devices, "
            f"have {len(devices)} (on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count before jax import)")
    return mesh_mod.build_mesh({MODEL_AXIS: mp}, devices[:mp])


def viable_ladder(kv_heads: int, num_heads: int,
                  max_mp: Optional[int] = None) -> list:
    """The ascending list of viable model-parallel degrees for a model:
    every ``mp`` with ``mp | kv_heads`` AND ``mp | num_heads`` (the same
    two divisibility rules :class:`ServingShard` enforces), optionally
    capped at ``max_mp``.  ``1`` is always viable — the degraded-mode
    floor is the unsharded engine.

    This is the **viability ladder** degraded serving walks down: when a
    shard group loses devices, the fleet rebuilds it at the LARGEST
    rung that still fits on the survivors (:func:`degrade_step`)."""
    kv, nh = int(kv_heads), int(num_heads)
    if kv < 1 or nh < 1:
        raise ValueError(f"viable_ladder: kv_heads={kv_heads} and "
                         f"num_heads={num_heads} must be >= 1")
    top = min(kv, nh) if max_mp is None else int(max_mp)
    return [mp for mp in range(1, top + 1)
            if kv % mp == 0 and nh % mp == 0]


def degrade_step(kv_heads: int, num_heads: int,
                 survivors: int) -> Optional[int]:
    """The largest viable ``mp'`` that fits on ``survivors`` devices —
    the degraded-rebuild target after a shard group loses devices.
    ``None`` when not even ``mp'=1`` fits (zero survivors): the group
    is dead until hardware returns."""
    ladder = viable_ladder(kv_heads, num_heads, max_mp=survivors)
    return ladder[-1] if ladder else None


def mesh_shape_key(mesh: Optional[Mesh]) -> Optional[str]:
    """Canonical string for a mesh's SHAPE (``"model=2"``) — the key the
    journal records per admission, recovery validates against, and the
    shape manifest sections on.  Device identities are deliberately NOT
    part of the key: recovery replays bitwise onto any mesh of the same
    shape (a restart rarely gets the same physical chips)."""
    if mesh is None:
        return None
    return ",".join(f"{name}={mesh.shape[name]}"
                    for name in mesh.axis_names)


class ServingShard:
    """One engine's sharding plan: the mesh, its shape key, and the
    idempotent placement of every piece of lifted device state."""

    def __init__(self, mesh: Mesh, *, kv_heads: int, num_heads: int):
        if MODEL_AXIS not in mesh.shape:
            raise ValueError(
                f"Engine(mesh=...) needs a '{MODEL_AXIS}' axis, got "
                f"axes {tuple(mesh.axis_names)} (build it with "
                f"serving.sharding.serving_mesh)")
        self.mesh = mesh
        self.mp = int(mesh.shape[MODEL_AXIS])
        self.key = mesh_shape_key(mesh)
        if self.mp > 1 and int(kv_heads) % self.mp != 0:
            raise ValueError(
                f"model axis size {self.mp} must divide kv_heads "
                f"{kv_heads}: the KV pool shards whole GQA groups so "
                f"decode attention stays shard-local")
        if self.mp > 1 and int(num_heads) % self.mp != 0:
            raise ValueError(
                f"model axis size {self.mp} must divide "
                f"num_attention_heads {num_heads}")

    @contextmanager
    def context(self):
        """Install the serving mesh as the GLOBAL mesh for the scope of
        one compiled call and restore whatever was there.  The model
        forwards' ``mark_sharding`` and the TP layers read the global
        mesh — the save/restore keeps a sharded engine from leaking its
        mesh into co-resident engines (fleet shard groups each carry a
        DIFFERENT device subset) or the training stack."""
        prev = mesh_mod.get_global_mesh()
        mesh_mod.set_global_mesh(self.mesh)
        try:
            yield
        finally:
            mesh_mod.set_global_mesh(prev)

    # -- placement (idempotent, write-through) ----------------------------

    def _pin(self, t, spec: P = P()) -> None:
        """(Re-)place one state tensor under ``spec`` on this mesh,
        writing through ``_set_data`` so the compiled steps' lifted
        state keeps pointing at the same Tensor objects."""
        arr = t._value()
        fspec = _filter_spec(spec, self.mesh)
        if not _divisible(arr.shape, fspec, self.mesh):
            fspec = P()
        t._set_data(place_array(arr, self.mesh, fspec))

    def place_model(self, model) -> None:
        """Place every parameter/buffer under its Megatron-TP spec
        (unannotated ones replicate).  Re-run after any state-dict
        write-through (``update_weights``): ``_set_data`` with a host
        array resets placement to single-device."""
        from ..distributed.fleet.meta_parallel.tensor_parallel import (
            place_parameters,
        )
        with self.context():
            place_parameters(model, self.mesh)

    def place_cache(self, cache) -> None:
        """The pool's layer buffers shard on the kv_heads dim; lengths and
        block tables replicate — they are host-driven metadata every
        shard must agree on."""
        self._pin(cache.lengths)
        for buf in (*cache.k, *cache.v):
            self._pin(buf, KV_LAYER_SPEC)
        self._pin(cache.block_tables)
        cache.mesh = self.mesh           # paged kernels run per head shard

    def place_draft_cache(self, cache) -> None:
        """The speculative draft's dense cache shards by ITS kv_heads when
        divisible; ``_pin`` falls back to replicated otherwise (a draft
        is small by construction — replicating it is the documented
        degradation, not an error)."""
        self._pin(cache.lengths)
        self._pin(cache.k, KV_POOL_SPEC)
        self._pin(cache.v, KV_POOL_SPEC)

    def place_sampler(self, sampler) -> None:
        """All sampling lanes replicate: one logical decision stream
        drives all shards (the lanes are values, never shapes).  The
        tenancy lanes (grammar id/state) ride the same placement, as do
        the grammar DFA tables — tiny, read-only, identical per shard."""
        for lane in (sampler.keys, sampler.temps, sampler.top_ks,
                     sampler.top_ps, sampler.tokens,
                     sampler.grammar_ids, sampler.grammar_states):
            self._pin(lane)
        if sampler.grammar is not None:
            self._pin(sampler.grammar.trans)
            self._pin(sampler.grammar.mask)

    def place_adapters(self, pool) -> None:
        """Adapter factors shard over the model axis exactly like the
        weights they modify: a column target (out-dim sharded) shards
        ``B``'s out dim, a row target (in-dim sharded) shards ``A``'s
        in dim; the other factor and the slot id lane replicate.
        Re-run after every ``load``/``unload`` — their ``_set_data``
        writes land host arrays (same write-through contract as
        ``update_weights``/``place_model``)."""
        for bank in pool.banks.values():
            if bank.kind == "column":
                self._pin(bank.A)
                self._pin(bank.B, P(None, None, MODEL_AXIS))
            else:
                self._pin(bank.A, P(None, MODEL_AXIS, None))
                self._pin(bank.B)
        self._pin(pool.adapter_ids)

    def place_state(self, engine) -> None:
        """(Re-)place every piece of lifted device state the compiled
        steps close over — the target cache and sampler plus, with
        speculation on, the draft model/cache/sampler and the proposals
        lane, and, with tenancy on, the adapter lanes.  Called at
        construction and again after ``warmup()``'s reset (which
        replaces the arrays with fresh host zeros)."""
        self.place_cache(engine.cache)
        self.place_sampler(engine.sampler)
        pool = getattr(engine, "adapter_pool", None)
        if pool is not None:
            self.place_adapters(pool)
        spec = getattr(engine, "spec", None)
        if spec is not None:
            self.place_model(spec.model)
            self.place_draft_cache(spec.cache)
            self.place_sampler(spec.sampler)
            self._pin(spec.proposals)
