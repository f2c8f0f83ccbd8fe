"""The model families the paged engine serves, stated once for the tests: a
library like ``chip_programs.py`` (not collected).

A family is one entry of :data:`FAMILIES`: its benchmark reference and
adapter, its tiny configuration file, its model and the cache it states, the
token stream its tests draw, what its engine is built with, what it refuses.
``tests/test_<family>.py`` names its family (``FAMILY = FAMILIES[...]``) and
imports the fixtures and the common cases below, which are collected there
once a family; ``test_serving_admission.py`` (one engine a kind of cache) and
``test_obs_spans.py`` (the programs at a cell's widths, compiled for the
described chip: an entry's ``chip``) read the same entries.  Adding a family
to the tests is an entry here, a file that names it, and the cases only it
has.
"""
import dataclasses
import functools
import importlib
import json
import os
import sys

import numpy as np
import pytest
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle                                   # noqa: E402
from paddle_tpu import inference                              # noqa: E402
from paddle_tpu.serving import group_cache                    # noqa: E402
from paddle_tpu.serving.kv_cache import (                     # noqa: E402
    CacheGroup, CacheSpec, cache_spec_of)

BLOCK = 8                 # the tests' block
STRIDE = 16               # and snapshot stride: a tiny prompt passes several


@dataclasses.dataclass(frozen=True)
class Family:
    """One family.  ``name`` is the benchmark's (``benchmarks/references/
    <name>.py``, ``benchmarks/adapters/<name>.py``,
    ``tests/benchmark_tests/tiny_<name>.json``); ``module`` the program's
    (``paddle_tpu.models.<module>``), with the names of its model class and of
    its tiny preset."""
    name: str
    module: str
    model: str
    preset: str
    kind: str                        # of cache: test_serving_admission's word
    preset_kw: dict = dataclasses.field(default_factory=dict)
    #: a short prompt's length, a long one's and the pieces the long one is
    #: prefilled in (several where a group keeps a window); None: the kind
    #: is another's, and ``test_serving_admission.py`` serves none of its own
    prompts: tuple = (21, 100, 1)
    seed: int = 0                    # of the benchmark's weights
    tokens: tuple = (0, 512)         # the tests' stream: how many, drawn below
    want: int = 0                    # of which the reference's forward reads
    atol: float = 1e-4               # the float32 forward against it
    spec: object = None              # () -> the CacheSpec the tiny model states
    sides: tuple = ()                # and its (first group's) sides, written out
    kernel: str = "pallas"           # the engine factory's defaults
    buckets: tuple = ()              # () warms every bucket
    refuses: dict = None             # what -> what the refusal says
    moe: object = None               # (models module) -> the expert layer
    bf16: tuple = ()                 # test_bf16_engine_serves_within_a_tolerance
    scarce: tuple = ()               # test_admission_waits_for_blocks
    chip: dict = None                # the deployment test_obs_spans compiles

    @functools.cached_property
    def ref(self):
        from benchmarks.harness.manifest import load_module
        return load_module("references", self.name)

    @functools.cached_property
    def adapter(self):
        from benchmarks.harness.manifest import load_module
        return load_module("adapters", self.name)

    @functools.cached_property
    def models(self):
        return importlib.import_module("paddle_tpu.models." + self.module)

    def tiny_model(self, **kw):
        """The program's tiny preset, its weights the program's own."""
        m = self.models
        return getattr(m, self.model)(
            getattr(m, self.preset)(**{**self.preset_kw, **kw}))

    def chip_model(self, **kw):
        """The model at the widths of :attr:`chip` (``kw`` over them), in
        bfloat16, its weights the program's own."""
        m = self.models
        config = {**self.chip["config"], **kw}
        paddle.seed(0)
        model = getattr(m, self.model)(getattr(
            m, self.model.replace("ForCausalLM", "Config"))(**config))
        if "dtype" not in config:
            model.to(dtype="bfloat16")
        return model

    def tiny_config(self, **kw) -> dict:
        with open(os.path.join(ROOT, "tests", "benchmark_tests",
                               f"tiny_{self.name}.json")) as f:
            return dict(json.load(f), **kw)

    def seeded(self, dtype: str = "float32", **kw):
        """``(model, tree, d)``: the program's model holding the benchmark's
        seeded weights in ``dtype``; ``tree`` is what the reference reads."""
        from benchmarks.adapters import _load
        from benchmarks.harness import weights

        cfg = self.tiny_config(torch_dtype=dtype, **kw)
        d = self.ref.dims(cfg)
        tree = weights.make(self.ref.weight_shapes(cfg), self.seed,
                            jnp.dtype(dtype))
        paddle.seed(0)
        model = self.adapter.build_model(cfg)
        model.eval()
        _load.load(model, self.adapter, tree, d)
        return model, tree, d

    def reference_logits(self, tree, d, tokens, **kw):
        """The reference's full forward; ``kw`` goes to its ``hidden``."""
        ref = self.ref
        h = ref.hidden(tree, jnp.asarray(tokens), d, **kw)
        return np.asarray(ref.logits_rows(
            {k: tree[k] for k in ref.HEAD_KEYS}, h, d))

    def engine(self, model, kernel=None, buckets=None, **kw):
        """The family's warmed engine: three slots of 128 positions, blocks
        of 8, a snapshot (where a group keeps state) every 16 positions."""
        kw = dict(dict(num_slots=3, max_seq=128, min_bucket=8,
                       block_size=BLOCK, kernel=kernel or self.kernel), **kw)
        eng = with_stride(inference.create_engine, model, **kw)
        buckets = self.buckets if buckets is None else buckets
        eng.warmup(buckets=list(buckets) or None)
        return eng

    def served_gap(self, tree, d, prompt, out, **kw) -> float:
        """How far under the reference's best logit, at its position, the
        worst served token's lies."""
        out = np.asarray(out)
        seq = np.concatenate([prompt, out])
        lg = self.reference_logits(tree, d, seq, **kw)[len(prompt) - 1:-1]
        return float((lg.max(-1) - np.take_along_axis(
            lg, out[:, None], axis=-1)[:, 0]).max())

    def greedy_matches(self, tree, d, prompt, out, margin=1e-4):
        """The served tokens are the reference's first choice wherever its
        best two logits are apart."""
        seq = np.concatenate([prompt, np.asarray(out)])
        lg = self.reference_logits(tree, d, seq)[len(prompt) - 1:-1]
        top2 = np.sort(lg, axis=-1)[:, -2:]
        sure = top2[:, 1] - top2[:, 0] > margin
        assert sure.sum() >= len(out) // 2
        np.testing.assert_array_equal(np.asarray(out)[sure],
                                      lg.argmax(-1)[sure])


def with_stride(build, *args, **kw):
    """``build(*args, **kw)`` with the snapshot stride at the tests' 16 (a
    pool reads the constant when it is built)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(group_cache, "SNAPSHOT_STRIDE", STRIDE)
        return build(*args, **kw)


def compiled_steps(model, cache, counts=None):
    """``(prefill, decode)`` through ``cache`` as the engine builds its own
    steps (``to_static``): one program a tail bucket and one decode program,
    where a Python loop of eager calls would dispatch every operator of every
    layer, interpreted kernels included, one by one.

    ``prefill(slot, ids, start, length)`` writes the tail ``ids`` (padded to
    its bucket) of a sequence ``length`` long behind ``start`` cached tokens
    and returns the logits of the row the engine samples from;
    ``decode(step, active)`` advances the ``active`` slots by their token of
    ``step`` ``[slots, 1]`` and returns every slot's logits and what the
    context counted a layer (``counts``: the name of its list)."""
    from paddle_tpu import jit as jit_mod
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.serving.paging import PagedCacheContext

    def prefill_step(ids, slot, length, start):
        ctx = PagedCacheContext(cache, "prefill", slot=slot, length=length,
                                start=start)
        out = model(ids, cache_ctx=ctx)
        cache.set_length(slot, length)
        assert ctx.narrowed and tuple(out.shape)[:2] == (1, 1)
        return out

    def decode_step(step, act):
        ctx = PagedCacheContext(cache, "decode", active=act)
        out = model(step, cache_ctx=ctx)
        cache.advance(act)
        rows = getattr(ctx, counts) if counts else [(jnp.int32(0),)]
        return out, Tensor._wrap(jnp.stack([jnp.stack(r) for r in rows]))

    prefill_fn = jit_mod.to_static(prefill_step)
    decode_fn = jit_mod.to_static(decode_step)

    def prefill(slot, ids, start, length):
        with no_grad():
            out = prefill_fn(paddle.to_tensor(np.asarray(ids)[None]),
                             *(paddle.to_tensor(np.int32(x))
                               for x in (slot, length, start)))
        return np.asarray(out._value())[0, 0]

    def decode(step, active):
        with no_grad():
            out, rows = decode_fn(paddle.to_tensor(step),
                                  paddle.to_tensor(active))
        return np.asarray(out._value()), \
            [tuple(int(x) for x in r) for r in np.asarray(rows._value())]

    return prefill, decode


_KV = ((2, 16), (2, 16))
_BY_LAYER = ("{} caches K and V by groups of layers, some only inside a "
             "window and cannot serve with ")
_STATE = ("{} keeps a state of fixed size a slot in some layers, snapshots of "
          "it for the prefix cache and cannot serve with ")

FAMILIES = {f.name: f for f in (
    Family(
        "gpt", "gpt", "GPTForCausalLM", "GPTConfig", "paged",
        preset_kw=dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=4, max_position_embeddings=128,
                       hidden_dropout_prob=0.0,
                       attention_probs_dropout_prob=0.0),
        # GPT-2 345M's widths (16 heads x 64, vocabulary 50,304, bf16; one
        # layer), 32 slots, block 16, a 513-block pool: XLA:TPU stores a
        # buffer whose minor dim is 64 with the block dim minor-most and
        # converts it to the Pallas kernel's row-major, lane-padded operand
        # and back in every program; the pool's per-layer buffers in whole
        # lanes are the operand, written in place.
        chip=dict(
            config=dict(vocab_size=50304, hidden_size=1024,
                        num_hidden_layers=1, num_attention_heads=16,
                        max_position_embeddings=1024,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0),
            engine=dict(num_slots=32, max_seq=1024, min_bucket=32,
                        num_kv_blocks=513),
            buffers=[(513, 16, 16, 128)] * 2,
            programs={"decode": None, "prefill": 32},
            pallas_calls={"decode": 1, "prefill": 1},
            moves=(513 * 16 * 16 * 128 * 2, ()),
            temp=lambda cache, program: cache.layer_nbytes())),
    Family(
        "llama", "llama", "LlamaForCausalLM", "llama_tiny", "gqa128",
        prompts=None,
        # ROADMAP R-a, repaired in PR 30: a rotary decoder of 32 query / 8 KV
        # heads x 128 in bfloat16.  Until then the rotary returned float32
        # queries (``q * cos`` promotes) and the first prefill bucket died in
        # ``warmup()`` with ``RESOURCE_EXHAUSTED ... vmem ... 17.09M and limit
        # 16.00M``; and the prefill program's block scatter converted each K/V
        # layer buffer to a layout of XLA's own and back (8 heads of bfloat16
        # do not fill a sublane tile).  Both programs compile for the
        # described v5e, the bucket-1024 prefill within the kernel's VMEM.
        chip=dict(
            config=dict(vocab_size=8192, hidden_size=4096,
                        intermediate_size=14336, num_hidden_layers=1,
                        num_attention_heads=32, num_key_value_heads=8,
                        max_position_embeddings=4096),
            engine=dict(num_slots=8, max_seq=4096, min_bucket=512),
            buffers=[(2049, 16, 8, 128)] * 2,
            programs={"decode": None, "prefill": 1024},
            kernels={"decode": {"paged_decode_attention": (1, 1)},
                     "prefill": {"paged_prefill_attention": (1, 1),
                                 "kv_block_write": (1, 9)}},
            absent={"decode": ("kv_block_write",)},
            moves=(2049 * 16 * 8 * 128 * 2, ()))),
    Family(
        "deepseek_v3", "deepseek_v3", "DeepseekV3ForCausalLM",
        "deepseek_v3_tiny", "latent", seed=2 ** 31 + 26, tokens=(45, 512),
        spec=lambda: CacheSpec.latent(3, 32 + 8), sides=((1, 40),),
        refuses={"mesh": "no kv_heads axis to shard",
                 "speculation": "no latent form"},
        # JoyAI-LLM-Flash's widths (latent 512 + 64 stored in 640 lanes, 32
        # heads, experts of 768 with 32 of 256 held; one dense and one expert
        # layer, vocabulary cut to 8,192), 32 slots, block 16, a 2,049-block
        # pool of ONE buffer a layer: one attention call a layer (two in a
        # prefill: ``mla_flash_prefill``, the tail over itself, and
        # ``mla_paged_prefill`` in the branch a cached prefix takes), two
        # grouped products in the expert layer.
        chip=dict(
            config=dict(vocab_size=8192, num_hidden_layers=2,
                        held_experts=(0, 32), max_position_embeddings=1024,
                        dtype="bfloat16"),
            engine=dict(num_slots=32, max_seq=1024, min_bucket=256),
            buffers=[(2049, 16, 1, 640)] * 2,
            programs={"decode": None, "prefill": 256},
            pallas_calls={"decode": 2 + 2, "prefill": 2 * 2 + 2},
            kernels={"decode": {"mla_paged_decode": (1, 2),
                                "moe_grouped_matmul": (1, 2)},
                     "prefill": {"mla_flash_prefill": (1, 2),
                                 "mla_paged_prefill": (1, 2),
                                 "moe_grouped_matmul": (1, 2)}},
            moves=(2049 * 16 * 640 * 2, ()))),
    Family(
        "keye_vl2", "keye_vl2", "KeyeVL2ForCausalLM", "keye_vl2_tiny",
        "indexed", seed=2 ** 31 + 30, tokens=(72, 512),
        spec=lambda: CacheSpec.indexed(3, 2, 16, 8, 24),
        sides=((2, 16), (2, 16), (1, 8)),
        refuses={"mesh": "serving mesh", "speculation": "speculation="},
        # Keye-VL-2.0-30B-A3B's widths (32 query / 4 KV heads of 128, indexer
        # 16 x 64 with ``topk`` 2,048, experts of 768 with 16 of 128 held; two
        # layers, vocabulary cut to 8,192), 16 slots of 8,192 positions,
        # block 16, an 8,193-block pool of THREE buffers a layer (K, V and
        # the indexer's key): the dense and the indexed branch each beside
        # ``kv_block_write`` (four KV heads of bfloat16 do not fill a sublane
        # tile: an XLA scatter of a tail's blocks converts the whole buffer
        # there and back).  Nothing of the smallest buffer's size (the
        # indexer's key) moves; no context-long row is sorted (the context is
        # as long as the cut vocabulary); no float32 array of the bucket by
        # the context exists in decode, none of the context by the context
        # anywhere.
        chip=dict(
            config=dict(vocab_size=8192, num_hidden_layers=2,
                        held_experts=(0, 16), max_position_embeddings=8192,
                        dtype="bfloat16"),
            engine=dict(num_slots=16, max_seq=8192, min_bucket=512),
            buffers=[(8193, 16, 4, 128)] * 4 + [(8193, 16, 1, 128)] * 2,
            programs={"decode": None, "prefill": 512},
            kernels={"decode": {"paged_decode_attention": (1, 9),
                                "dsa_index_scores": (1, 9),
                                "dsa_sparse_decode": (1, 9),
                                "moe_grouped_matmul": (1, 9)},
                     "prefill": {"paged_prefill_attention": (1, 9),
                                 "dsa_index_scores": (1, 9),
                                 "dsa_sparse_prefill": (1, 9),
                                 "kv_block_write": (1, 9),
                                 "moe_grouped_matmul": (1, 9)}},
            moves=(8193 * 16 * 128 * 2, ()),
            temp=lambda cache, program: cache.layer_nbytes(),
            sorts=8192,
            forbid={"decode": (r"f32\[(\d+,)*512,8192\]",
                               r"f32\[(\d+,)*8192,8192\]"),
                    "prefill": (r"f32\[(\d+,)*8192,8192\]",)})),
    Family(
        "evabyte", "evabyte", "EvaByteForCausalLM", "evabyte_tiny",
        "windowed", prompts=(21, 70, 3),        # a window of 32: 32, 64, 70
        seed=2 ** 31 + 34, tokens=(120, 64), buckets=(8, 16, 32),
        spec=lambda: CacheSpec.windowed(2, 4, 16, 32, 4),
        sides=((4, 16), (4, 16)),
        refuses={"mesh": "serving mesh", "speculation": "speculation="},
        bf16=(dict(id="bf16", config={}, buckets=(16, 32), seed=5,
                   prompt=75, new=30, tol=0.02),),
        # summary blocks for one sequence's life only (3 usable; 2 windows)
        scarce=(dict(id="summary", sizes=dict(num_summary_blocks=4),
                     buckets=(8, 32), prompt=70, new=20, deferred=None),),
        # EvaByte's widths (32 heads of 128 with as many KV heads, SwiGLU of
        # 11,008, windows of 2,048 in chunks of 16, vocabulary 320; two
        # layers), 16 slots of 32,768 positions, block 16, a 641-block exact
        # group and a 33-block summary group: the decode program
        # (``eva_paged_decode`` and no other attention kernel: no fork a
        # layer), the bucket-512 prefill program (the tail's write, the window
        # it may close published) and the publishing program hold no move of
        # either group's layer buffers' size — nor of a projection's weights
        # (4096 x 4096 x 2 B = 32 MiB, under both), which stored input-major
        # were copied in every program — and alias every buffer they write:
        # the exact group in decode, both in prefill, the summary group in
        # the publishing program.
        chip=dict(
            config=dict(num_hidden_layers=2, dtype="bfloat16"),
            engine=dict(num_slots=16, max_seq=32768, min_bucket=512,
                        num_kv_blocks=641, num_summary_blocks=33),
            buffers=[(641, 16, 32, 128)] * 4 + [(33, 128, 32, 128)] * 4,
            programs={"decode": None, "prefill": 512, "publish": None},
            kernels={"decode": {"eva_paged_decode": (1, 9)},
                     "prefill": {"eva_paged_prefill": (1, 9)}},
            absent={p: ("paged_decode_attention",)
                    for p in ("decode", "prefill", "publish")},
            moves=(4096 * 4096 * 2, ()),
            alias=lambda cache, program: sum(
                int(b._value().nbytes) for b in
                cache.buffers() * (program != "publish")
                + cache.summary_buffers() * (program != "decode")),
            temp=lambda cache, program: cache.layer_nbytes(),
            says={"decode": ("eva.attend", "kv.write"),
                  "prefill": ("eva.attend", "eva.summarise", "kv.write"),
                  "publish": ("eva.summarise",)})),
    Family(
        "mellum", "mellum", "MellumForCausalLM", "mellum_tiny",
        "grouped_window", prompts=(21, 100, 3),           # pieces of 48
        seed=2 ** 31 + 38, tokens=(128, 512), atol=2e-4,
        buckets=(8, 16, 32),
        spec=lambda: CacheSpec.by_layer([CacheGroup((3,), _KV, 0),
                                         CacheGroup((0, 1, 2), _KV, 24)]),
        sides=_KV,
        refuses={
            "mesh": _BY_LAYER.format("MellumForCausalLM")
            + r"a serving mesh of more than one device \(the groups' "
              r"tables are not sharded\)",
            "speculation": _BY_LAYER.format("MellumForCausalLM")
            + r"speculation= \(the verify window has no by-layer form\)"},
        moe=lambda models: importlib.import_module(
            "paddle_tpu.models.keye_vl2").KeyeVL2MoE,
        # two query heads a KV head (the decode kernel's row-at-a-time form)
        # and eight (its matmul form)
        bf16=tuple(dict(id=f"heads{h}", config=dict(num_attention_heads=h),
                        buckets=(32,), seed=3, prompt=30, new=40, tol=0.05)
                   for h in (4, 16)),
        # one group sized for one sequence's life only; ``deferred``: the
        # group that says so in ``stats()["swa"]["deferred_by_group"]``
        scarce=(dict(id="full", sizes=dict(num_kv_blocks=14,
                                           num_window_blocks=40),
                     buckets=(32,), prompt=30, new=60, deferred=0),
                dict(id="window", sizes=dict(num_kv_blocks=60,
                                             num_window_blocks=8),
                     buckets=(32,), prompt=30, new=60, deferred=1)),
        # Mellum2-12B-A2.5B's widths (32 query / 4 KV heads of 128, hidden
        # 2304, experts of 896 with 8 of 64 held, a window of 1,024; three
        # sliding layers and one full layer, vocabulary cut to 8,192), 32
        # slots of 32,768 positions, block 16, a 2,305-block full group (for
        # ONE layer) and a 385-block window group (for three): the decode
        # program — one program for both kinds of layer, four calls of the one
        # decode kernel — and the bucket-256 and bucket-2,048 prefill programs
        # (``kv_block_write`` and ``paged_prefill_attention`` a layer, under a
        # window on three of them).  Nothing of either group's buffers' shape
        # moves, nor of a q projection's weights' (the expert layer's rows of
        # a 2,048 bucket, ``[16384, 2304]`` and ``[16384, 1792]``, are larger
        # than both and are its own to order: eight full-group layer buffers
        # bound that program's temporaries, one the others').  Each layer's
        # attention kernel is its layer's own device time (PR 36's map).
        chip=dict(
            config=dict(vocab_size=8192, num_hidden_layers=4,
                        held_experts=(0, 8), max_position_embeddings=32768,
                        dtype="bfloat16"),
            engine=dict(num_slots=32, max_seq=32768, min_bucket=256,
                        num_kv_blocks=2305, num_window_blocks=385),
            buffers=[(2305, 16, 4, 128)] * 2 + [(385, 16, 4, 128)] * 6,
            buckets=[256, 512, 1024, 2048],            # two windows at most
            programs={"decode": None, "prefill": 256, "prefill-2048": 2048},
            kernels={"decode": {"paged_decode_attention": (4, 4),
                                "moe_grouped_matmul": (4, 99)},
                     "prefill": {"paged_prefill_attention": (4, 4),
                                 "kv_block_write": (4, 99),
                                 "moe_grouped_matmul": (4, 99)}},
            moves=(385 * 16 * 4 * 128 * 2,
                   ("[2305,16,4,128]", "[385,16,4,128]", "[4096,2304]",
                    "[2304,4096]")),
            temp=lambda cache, program: cache.pools[0].layer_nbytes() * (
                8 if program.endswith("2048") else 1),
            says={p: ("kv.write", "moe.experts")
                  for p in ("decode", "prefill", "prefill-2048")},
            attention_layers=(0, 1, 2, 3))),
    Family(
        "lfm2_moe", "lfm2", "Lfm2ForCausalLM", "lfm2_tiny", "grouped_state",
        seed=2 ** 31 + 40, tokens=(160, 500), want=128, atol=2e-5,
        kernel="reference", buckets=(16, 32, 64),
        spec=lambda: CacheSpec.by_layer([
            CacheGroup((2,), _KV, 0),
            CacheGroup((0, 1, 3), ((2, 64),), 0, True)]),
        sides=_KV,
        refuses={
            "mesh": _STATE.format("Lfm2ForCausalLM")
            + r"a serving mesh of more than one device \(the state "
              r"and its snapshot pool are not sharded\)",
            "speculation": _STATE.format("Lfm2ForCausalLM")
            + r"speculation= \(the verify window has no state form\)"},
        moe=lambda models: models.Lfm2MoE,
        # LFM2-24B-A2B's widths (hidden 2048, 32 query / 8 KV heads of 64, a
        # 3-tap filter, dense SwiGLU of 11,776, experts of 1,536 with 8 of 64
        # held; conv, conv, attention, conv: two dense and two expert layers,
        # vocabulary cut to 8,192), 64 slots of 16,384 positions, block 16, a
        # 2,305-block K/V group (for ONE layer, 64-wide heads in 128 lanes),
        # a state array ``[3, 2, 64, 2048]`` and a snapshot pool ``[3, 2,
        # 4096, 2048]``: nothing of the state array's size or more moves that
        # has a pool's, the state's or the snapshot pool's shape (a 2,048
        # bucket's own rows, ``[2048, 23552]`` float32, are larger than the
        # snapshot pool and are the program's to order: three snapshot pools
        # bound its temporaries, one the others'); all three are aliased
        # whole, but that a decode step takes no snapshot: the pool is no
        # operand of it.  The decode kernel at (8 KV heads, 4 query heads
        # each, 64 in 128 lanes) fits the default scoped VMEM (the compile
        # asks for no more); the conv operator's work lies under its layer's
        # scope.
        chip=dict(
            config=dict(vocab_size=8192, num_hidden_layers=4,
                        held_experts=(0, 8), max_position_embeddings=16384,
                        dtype="bfloat16"),
            engine=dict(num_slots=64, max_seq=16384, min_bucket=256,
                        num_kv_blocks=2305, num_state_snapshots=4096),
            buffers=[(2305, 16, 8, 128)] * 2
            + [(3, 2, 64, 2048), (3, 2, 4096, 2048)],
            buckets=[256, 512, 1024, 2048, 4096, 8192, 16384],
            programs={"decode": None, "prefill": 256, "prefill-2048": 2048},
            kernels={"decode": {"paged_decode_attention": (1, 1),
                                "moe_grouped_matmul": (4, 4)},
                     "prefill": {"paged_prefill_attention": (1, 1),
                                 "kv_block_write": (1, 2),
                                 "moe_grouped_matmul": (4, 4)}},
            moves=(3 * 2 * 64 * 2048 * 2,
                   ("[2305,16,8,128]", "[3,2,64,2048]", "[3,2,4096,2048]",
                    "[2,64,2048]", "[2,4096,2048]")),
            alias=lambda cache, program: cache.nbytes() - (
                cache.states[0].snapshots._value().nbytes
                if program == "decode" else 0),
            temp=lambda cache, program:
                cache.states[0].snapshots._value().nbytes * (
                    3 if program.endswith("2048") else 1),
            says={"decode": ("kv.write", "state.write", "conv.mix"),
                  "prefill": ("kv.write", "state.write", "conv.mix",
                              "[3,2,4096,2048]")},
            forbid={"decode": (r"\[3,2,4096,2048\]",)},
            attention_layers=(2,),
            scoped=[f"{i}/conv/{part}" for i in (0, 1, 3) for part in (
                "in_proj", "out_proj", "conv.mix", "state.write")])),
    Family(
        "kimi_linear", "kimi_linear", "KimiLinearForCausalLM",
        "kimi_linear_tiny", "grouped_recurrent",
        seed=2 ** 31 + 44, tokens=(160, 500), want=128, atol=2e-5,
        kernel="reference", buckets=(16, 32, 64),
        spec=lambda: CacheSpec.by_layer([
            CacheGroup((3,), ((1, 40),), 0),
            CacheGroup((0, 1, 2), ((3, 96), (2, 16, 16)), 0, True, 16, 64)],
            kind="latent"),
        sides=((1, 40),),
        refuses={
            "mesh": _STATE.format("KimiLinearForCausalLM")
            + r"a serving mesh of more than one device \(the state "
              r"and its snapshot pool are not sharded\)",
            "speculation": _STATE.format("KimiLinearForCausalLM")
            + r"speculation= \(the verify window has no state form\)"},
        moe=lambda models: importlib.import_module(
            "paddle_tpu.models.deepseek_v3").DeepseekV3MoE,
        # Kimi-Linear-48B-A3B's widths (hidden 2304; KDA 32 heads of 128
        # behind a 4-tap convolution; latent attention 512 + 64 stored in 640
        # lanes under 32 heads of 128 + 64, nothing rotated; dense SwiGLU of
        # 9,216, experts of 1,024 with 16 of 256 held and one shared; KDA,
        # KDA, KDA, MLA: one dense and three expert layers, vocabulary cut to
        # 8,192), 64 slots of 32,768 positions, block 16, a 2,305-block
        # latent group (for ONE layer), and the state group's two sides: the
        # shift side ``[3, 3, 64, 12288]`` with its snapshot pool ``[3, 3,
        # 128, 12288]`` and, a buffer a layer, the recurrent side ``[64, 32,
        # 128, 128]`` float32 with its snapshot pool ``[128, 32, 128, 128]``
        # (268 MB a layer).  Nothing of the shift state's size or more moves
        # that has a pool's, a state's or a snapshot pool's shape; all are
        # aliased whole, but that a decode step takes no snapshot: neither
        # snapshot pool is an operand of it.  The decode step's state kernel
        # (``kda_decode_step``, one call a KDA layer) and the prefill's scan
        # (``kda_chunk_prefill``) are under their layer's ``kda`` scope.
        chip=dict(
            config=dict(vocab_size=8192, num_hidden_layers=4,
                        held_experts=(0, 16), max_position_embeddings=32768,
                        dtype="bfloat16"),
            engine=dict(num_slots=64, max_seq=32768, min_bucket=256,
                        num_kv_blocks=2305, num_state_snapshots=128),
            buffers=[(2305, 16, 1, 640), (3, 3, 64, 12288),
                     (3, 3, 128, 12288)] + [(64, 32, 128, 128)] * 3
            + [(128, 32, 128, 128)] * 3,
            programs={"decode": None, "prefill": 256, "prefill-4096": 4096},
            kernels={"decode": {"mla_paged_decode": (1, 1),
                                "kda_decode_step": (3, 3),
                                "moe_grouped_matmul": (6, 6)},
                     "prefill": {"mla_paged_prefill": (1, 1),
                                 "mla_flash_prefill": (1, 1),
                                 "kda_chunk_prefill": (3, 3),
                                 "moe_grouped_matmul": (6, 6)}},
            moves=(3 * 3 * 64 * 12288 * 2,
                   ("[2305,16,1,640]", "[3,3,64,12288]", "[3,3,128,12288]",
                    "[64,32,128,128]", "[128,32,128,128]")),
            alias=lambda cache, program: cache.nbytes() - (
                cache.states[0].snapshots._value().nbytes
                + sum(int(b._value().nbytes)
                      for b in cache.states[0].recurrent_snapshots)
                if program == "decode" else 0),
            says={"decode": ("kv.write", "state.write", "kda.mix",
                             "kda.step"),
                  "prefill": ("kv.write", "state.write", "kda.mix",
                              "kda.scan", "[128,32,128,128]")},
            forbid={"decode": (r"\[128,32,128,128\]",
                               r"\[3,3,128,12288\]")},
            scoped=[f"{i}/kda/{part}" for i in (0, 1, 2) for part in (
                "q_proj", "o_proj", "kda.mix", "state.write")])),
)}


#: kind of cache -> the family that states it
BY_KIND = {f.kind: f for f in FAMILIES.values()}


def _refusals(family):
    """what -> (the engine's keyword, what the refusal says)."""
    from paddle_tpu.serving.sharding import serving_mesh
    from paddle_tpu.serving.spec_decode import SpecConfig

    draft = family.tiny_model()
    return {"mesh": (dict(mesh=serving_mesh(2)), family.refuses["mesh"]),
            "speculation": (
                dict(speculation=SpecConfig(draft_model=draft, k=2)),
                family.refuses["speculation"])}


# -- the fixtures a family's file imports --------------------------------------

@pytest.fixture(scope="module")
def family(request):
    return request.module.FAMILY


@pytest.fixture(scope="module")
def f32(family):
    return family.seeded()


@pytest.fixture(scope="module")
def tokens(family):
    n, below = family.tokens
    return np.random.default_rng(7).integers(0, below, (n,), dtype=np.int32)


@pytest.fixture(scope="module")
def want(family, f32, tokens):
    """The reference's one full forward of the stream (of its first
    ``family.want`` tokens)."""
    _model, tree, d = f32
    return family.reference_logits(tree, d,
                                   tokens[:family.want or len(tokens)])


def pytest_generate_tests(metafunc):
    """A common case that takes ``bf16`` or ``scarce`` is collected once an
    entry of its family's table of that name."""
    for table in ("bf16", "scarce"):
        if table in metafunc.fixturenames:
            cases = getattr(metafunc.module.FAMILY, table)
            metafunc.parametrize(table, cases, ids=[c["id"] for c in cases])


# -- the common cases: a family's file imports those it has --------------------

def test_full_forward_equals_the_reference(family, f32, tokens, want):
    model, _tree, _d = f32
    ids = tokens[None, :len(want)]
    got = np.asarray(model(paddle.to_tensor(ids))._value())[0]
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, atol=family.atol, rtol=0)


def test_the_model_states_its_cache_and_keeps_its_dtype(family):
    paddle.seed(0)
    model = family.tiny_model(dtype="bfloat16")
    spec = cache_spec_of(model)
    assert spec == family.spec() and spec.sides == family.sides
    assert {str(p.dtype) for p in model.parameters()} == {"bfloat16"}


def test_bf16_engine_serves_within_a_tolerance(family, bf16):
    """bf16 weights and pools through ``create_engine``: every greedy token's
    reference logit (the reference computes in float32 from the same bf16
    weights) lies close under the reference's best."""
    model, tree, d = family.seeded("bfloat16", **bf16["config"])
    eng = family.engine(model, buckets=bf16["buckets"])
    held = [*eng.cache.buffers(),
            *getattr(eng.cache, "summary_buffers", list)()]
    assert {str(b.dtype) for b in held} == {"bfloat16"}
    prompt = np.random.default_rng(bf16["seed"]).integers(
        0, family.tokens[1], (bf16["prompt"],), dtype=np.int32)
    h = eng.add_request(prompt, max_new_tokens=bf16["new"])
    eng.run()
    tree32 = {k: v.astype(jnp.float32) for k, v in tree.items()}
    gap = family.served_gap(tree32, d, prompt, h.output_ids)
    assert gap < bf16["tol"], gap
    assert eng.health()["kv_block_invariants"] == "ok"


@pytest.mark.parametrize("what", ["mesh", "speculation"])
def test_the_cache_refuses_what_it_has_no_form_for(family, what):
    paddle.seed(0)
    kw, says = _refusals(family)[what]
    with pytest.raises(ValueError, match=says):
        inference.create_engine(family.tiny_model(), num_slots=2, max_seq=64,
                                min_bucket=16, block_size=BLOCK, **kw)


def test_the_shares_layer_outputs_add_up_to_the_uncut_layer(family):
    """16 experts, 4 shares of 4: each share routes over all 16 (a selection
    bias, where the family has one, in the choice only), normalises over the
    4 chosen and computes its own; the four outputs sum to the reference's
    layer with every expert held (the router and the bias, which every chip
    holds alike, are counted once: they add no term of their own)."""
    from benchmarks.harness import weights

    ref = family.ref
    cfg = family.tiny_config(num_experts=16, held_experts=[0, 16])
    d = ref.dims(cfg)
    tree = weights.make(ref.weight_shapes(cfg), family.seed, jnp.float32)
    lw = ref.layer_weights(tree, 1, d)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(40, 64)),
                    jnp.float32)
    whole = np.asarray(ref.experts(x, lw, d, False))
    parts = []
    for share in range(4):
        held = (4 * share, 4 * share + 4)
        paddle.seed(0)
        layer = family.moe(family.models)(family.adapter.program_config(
            dict(cfg, held_experts=list(held))))
        layer.gate._set_data(lw["moe.router"])
        if "moe.bias" in lw:
            layer.expert_bias._set_data(lw["moe.bias"])
        layer.experts_gate_up._set_data(jnp.concatenate(
            [lw["moe.w_gate"], lw["moe.w_up"]], axis=2)[held[0]:held[1]])
        layer.experts_down._set_data(lw["moe.w_down"][held[0]:held[1]])
        y = np.asarray(layer(x[None])[0])
        np.testing.assert_allclose(
            y, np.asarray(ref.experts(x, lw, d, False, held=held)),
            atol=2e-5)
        parts.append(y)
    np.testing.assert_allclose(sum(parts), whole, atol=5e-5)
    assert all(np.abs(p).max() > 1e-4 for p in parts)    # each share adds


def test_admission_waits_for_blocks(family, f32, tokens, scarce):
    """With one group sized for one sequence's life only, the second request
    is deferred, not failed, and is served when the first retires."""
    model, _tree, _d = f32
    eng = family.engine(model, buckets=scarce["buckets"], num_slots=2,
                        **scarce["sizes"])
    n = scarce["prompt"]
    a = eng.add_request(tokens[:n], max_new_tokens=scarce["new"])
    b = eng.add_request(tokens[10:n + 10], max_new_tokens=scarce["new"])
    eng.step()
    assert len(eng.running) == 1 and len(eng.queue) == 1
    eng.run()
    assert a.finished and b.finished and not a.error and not b.error
    st = eng.stats()
    assert st["failures"]["failed"] == 0
    if scarce["deferred"] is not None:
        by = list(st["swa"]["deferred_by_group"])
        assert by.pop(scarce["deferred"]) > 0 and by == [0]
    assert eng.health()["kv_block_invariants"] == "ok"
