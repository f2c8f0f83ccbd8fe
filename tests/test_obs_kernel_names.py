"""The names the Pallas kernels carry in a program compiled for the chip that
is described here (what the accepted roofline readers match) and the one plan
mark a traced flash call leaves.  Split from ``test_obs_spans.py`` along its
sections; the engines' whole programs, and the tail-prefill kernel's fit in
scoped VMEM, stay there."""
import re

from paddle_tpu.obs import spans

from chip_programs import (attention_layer_program,  # noqa: F401
                           custom_call_lines, kernel_lines, load_patterns,
                           moves_around_kernels, one_chip, pool_programs)

NAME, START, END, PARENT, ATTRS, SID = range(6)


def rows_since(t):
    return spans.snapshot(since=t)


# -- the kernels' names, compiled for the chip that is described here ---------

def test_flash_kernels_are_named_and_the_accepted_patterns_still_match(
        one_chip):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import ATTN_SCOPE_PALLAS
    from paddle_tpu.ops.pallas import flash_attention_kernel as fk

    def train(q, k, v):
        def loss(q, k, v):
            with jax.named_scope(ATTN_SCOPE_PALLAS):
                o = fk.flash_attention_fused(q, k, v, causal=True)
            return (o.astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def infer(q, k, v):
        with jax.named_scope(ATTN_SCOPE_PALLAS):
            return fk.flash_attention_fused(q, k, v, causal=True)

    x = jax.ShapeDtypeStruct((2, 1024, 16, 64), jnp.bfloat16,
                             sharding=one_chip)
    kc = load_patterns("flash_attention")
    lines = kernel_lines(train, x, x, x)
    names = [ln.split(" = ")[0] for ln in lines]
    assert [re.sub(r"\.\d+$", "", n) for n in names] == [
        "%" + fk.FWD_NAME, "%" + fk.BWD_DKV_NAME, "%" + fk.BWD_DQ_NAME]
    fwd = [ln for ln in lines if any(re.search(p, ln) for p in kc.FORWARD)]
    bwd = [ln for ln in lines if any(re.search(p, ln) for p in kc.BACKWARD)]
    assert len(fwd) == 1 and len(bwd) == 2 and not set(fwd) & set(bwd)
    (only,) = kernel_lines(infer, x, x, x)
    assert any(re.search(p, only) for p in kc.FORWARD)
    assert not any(re.search(p, only) for p in kc.BACKWARD)


def test_a_traced_flash_call_leaves_one_plan_mark():
    """``attention.flash_plan``: once per trace of the forward, the sizes
    the plan chose for the call's shape and the tiles its walk visits — the
    engagement share of the causal skip (100 % visited: it did nothing)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention_kernel as fk

    def train(q, k, v):
        def loss(q, k, v):
            o = fk.flash_attention_fused(q, k, v, causal=True, interpret=True)
            return (o.astype(jnp.float32) ** 2).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    x = jax.ShapeDtypeStruct((1, 1024, 16, 64), jnp.bfloat16)  # the cell's
    t = spans.clock()
    with spans.span("jit.trace", fn="train") as outer:
        jax.eval_shape(train, x, x, x)
    marks = [r for r in rows_since(t) if r[NAME] == "attention.flash_plan"]
    assert len(marks) == 1
    (mark,) = marks
    assert mark[PARENT] == outer.sid and mark[START] == mark[END]
    plan = fk.flash_plan(1024, 64, 2)
    assert mark[ATTRS] == {
        "seq": 1024, "head_dim": 64, "causal": 1, "block_q": plan.block_q,
        "sub": plan.sub, "tiles_total": plan.tiles_total,
        "tiles_visited": plan.tiles_visited,
        "tiles_masked": plan.tiles_masked,
        # the operands' form: [head_dim, S] heads, here of three tensors
        "layout": "feature_major", "fused_qkv": 0}
    assert mark[ATTRS]["tiles_visited"] <= 0.75 * mark[ATTRS]["tiles_total"]
    # a call that is not causal walks every tile and says so
    t = spans.clock()
    jax.eval_shape(lambda q, k, v: fk.flash_attention_fused(
        q, k, v, causal=False, interpret=True), x, x, x)
    (mark,) = [r for r in rows_since(t) if r[NAME] == "attention.flash_plan"]
    assert mark[ATTRS]["tiles_visited"] == mark[ATTRS]["tiles_total"]
    assert mark[ATTRS]["tiles_masked"] == 0 and mark[ATTRS]["causal"] == 0
    # a caller that holds a fused projection says so, and nothing else moves
    qkv = jax.ShapeDtypeStruct((1, 1024, 16, 192), jnp.bfloat16)
    t = spans.clock()
    jax.eval_shape(lambda x: jax.grad(lambda x: fk.flash_attention_fused_qkv(
        x, causal=True, interpret=True).astype(jnp.float32).sum())(x), qkv)
    (fused,) = [r for r in rows_since(t) if r[NAME] == "attention.flash_plan"]
    assert fused[ATTRS] == {**marks[0][ATTRS], "fused_qkv": 1}


def test_no_xla_op_stands_between_the_projections_and_the_flash_kernels(
        one_chip, monkeypatch):
    """One layer's attention at the train cell's call (GPT-2 345M's widths,
    B 16, S 1,024, bf16; :func:`attention_layer_program`), forward and
    backward, compiled for the described v5e: the kernels take heads as
    ``[head_dim, S]`` blocks of the qkv projection's output as XLA:TPU lays
    it out and write the out projection's input and the qkv gradient the
    same way, so no ``copy`` / ``transpose`` / fusion of a head tensor's
    size stands between a projection's matmul and a kernel (one joining
    dq, dk, dv would be allowed: there is none, ``bwd_dq`` completes
    ``bwd_dkv``'s array in place), lse is a lane-dense row a head and delta
    never leaves the kernels."""
    from paddle_tpu.ops.pallas import flash_attention_kernel as fk

    compiled = attention_layer_program(one_chip, monkeypatch)
    hlo = compiled.as_text()
    head = 16 * 16 * 1024 * 64
    lines = custom_call_lines(compiled)
    kc = load_patterns("flash_attention")
    fwd = [ln for ln in lines if any(re.search(p, ln) for p in kc.FORWARD)]
    bwd = [ln for ln in lines if any(re.search(p, ln) for p in kc.BACKWARD)]
    # the yardstick's reader halves the backward count: two kernels a layer
    assert len(lines) == 3 and len(fwd) == 1 and len(bwd) == 2
    assert [re.sub(r"\.\d+$", "", ln.split(" = ")[0]) for ln in lines] == [
        "%" + fk.FWD_NAME, "%" + fk.BWD_DKV_NAME, "%" + fk.BWD_DQ_NAME]
    moves = moves_around_kernels(hlo, lambda n: "pallas_flash" in n, head)
    assert moves == []
    # q, k and v are one operand, read three times; dq, dk and dv one result
    operands = re.findall(r"(\w+\[[\d,]*\])\S* (%[\w.-]+)",
                          fwd[0].split("custom-call(")[1].split(
                              "), custom_call_target")[0])
    assert len(operands) == 3 and len(set(operands)) == 1
    assert operands[0][0] == "bf16[16,3072,1024]"
    assert all(ln.split(" = ")[1].startswith("bf16[16,3072,1024]")
               for ln in bwd)
    assert 'output_to_operand_aliasing={{}: (6, {})}' in next(
        ln for ln in hlo.splitlines() if fk.BWD_DQ_NAME + "." in ln
        and "tpu_custom_call" in ln)
    # no [.., S, 1] float32 array: lse is [B*H, 1, S], delta stays in VMEM
    assert not re.search(r"f32\[[\d,]*1024,1\]", hlo)
    assert "f32[256,1,1024]" in fwd[0]


def test_paged_kernels_are_named_and_decode_is_still_told_by_its_operands(
        one_chip, pool_programs):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import paged_attention_kernel as pk

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slots, heads, hd, bs, blocks, mb = 32, 16, 64, 16, 2049, 64
    pool = sds((blocks, bs, heads, 128), jnp.bfloat16)   # hd in whole lanes
    kc = load_patterns("paged_decode")
    (decode,) = kernel_lines(
        lambda q, k, v, t, n, a: pk.paged_decode_attention_kernel(
            q, k, v, t, n, a),
        sds((slots, 1, heads, hd), jnp.bfloat16), pool, pool,
        sds((slots, mb), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.int32))
    assert decode.startswith("%paged_decode_attention.")
    assert any(re.search(p, decode) for p in kc.PATTERNS)
    # the table and the lengths lead: a dynamic grid bound would come first
    assert re.match(r"%\S+ = \S+ custom-call\(s32\[32,64\]\S* %\S+ "
                    r"s32\[32\]\S* %\S+ ", decode)
    (prefill,) = kernel_lines(
        lambda q, k, v, row, st: pk.paged_prefill_attention_kernel(
            q, k, v, row, st),
        sds((1, 256, heads, hd), jnp.bfloat16), pool, pool,
        sds((mb,), jnp.int32), sds((), jnp.int32))
    assert prefill.startswith("%paged_prefill_attention.")
    assert not any(re.search(p, prefill) for p in kc.PATTERNS)
    # in the engine's own decode program: one match a layer, nothing else
    _eng, compiled = pool_programs("paged", "decode", num_hidden_layers=2)
    told = [ln for ln in custom_call_lines(compiled)
            if any(re.search(p, ln) for p in kc.PATTERNS)]
    assert len(told) == 2
    assert all(ln.startswith("%paged_decode_attention.") for ln in told)


def test_the_streamed_ces_forward_is_one_kernel_under_its_scope(one_chip):
    """The fused loss at the train cell's widths (rows 16,384, hidden 1,024,
    vocabulary 50,304, bf16), forward and with its backward, compiled for the
    described v5e as a TPU traces it: the forward's statistics are one
    custom call, named, under ``loss.streamed_ce`` forward — where
    ``ce_device_ms`` looks, by the parts of an instruction's scope — with no
    ``while`` beside it; the backward keeps its loop; the whole fits the chip
    many times over.  The same call over a data mesh of the host's four
    chips sits in a ``shard_map``, a shard's rows a chip."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.obs import hlo_cost
    from paddle_tpu.ops import fused
    from paddle_tpu.ops.pallas import streamed_ce_kernel as ck

    N, H, V = 16384, 1024, 50304
    assert ck.supports(N, H, V, jnp.bfloat16)
    assert ck.plan(N, H, V, 2) == (ck.ROW_TILE, ck.VOCAB_TILE, ck.VOCAB_SUB)

    def loss(h, w, lbl):
        with jax.named_scope(fused.CE_SCOPE):
            return fused._flce(h, w, lbl, lbl >= 0, 2048, jnp.bfloat16,
                               False).sum()

    def step(h, w, lbl):
        return jax.value_and_grad(loss, argnums=(0, 1))(h, w, lbl)

    def shapes(rows, sharding):
        return (jax.ShapeDtypeStruct((rows, H), jnp.bfloat16,
                                     sharding=sharding(P("data", None))),
                jax.ShapeDtypeStruct((V, H), jnp.bfloat16,
                                     sharding=sharding(P())),
                jax.ShapeDtypeStruct((rows,), jnp.int32,
                                     sharding=sharding(P("data"))))

    for fn, loops in ((loss, 0), (step, 1)):
        compiled = jax.jit(fn).lower(*shapes(N, lambda _s: one_chip)).compile()
        (kernel,) = custom_call_lines(compiled)
        name = kernel.split(" = ")[0].lstrip("%")
        assert re.sub(r"\.\d+$", "", name) == ck.FWD_NAME
        assert not any(re.search(p, kernel)
                       for p in load_patterns("flash_attention").PATTERNS)
        hlo = compiled.as_text()
        got = hlo_cost.scope_map(hlo)["instructions"]
        assert got[name] == (fused.CE_SCOPE + "/" + ck.FWD_NAME, "fwd")
        assert fused.CE_SCOPE in got[name][0].split("/")   # the reader's rule
        whiles = [got[r[1]] for r in hlo_cost.instructions(hlo)
                  if r[2] == "while"]
        assert whiles == [(fused.CE_SCOPE, "bwd")] * loops
        mem = compiled.memory_analysis()
        # no block of logits: the forward alone holds nothing, the step the
        # backward's one float32 block (134 MB) and its bf16 copy
        assert mem.temp_size_in_bytes < (1 << 20 if fn is loss else 256 << 20)

    chips = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2").devices
    mesh = mesh_mod.hybrid_mesh(dp=4, devices=chips)
    mesh_mod.set_global_mesh(mesh)
    try:
        compiled = jax.jit(step).lower(*shapes(
            4 * N, lambda spec: NamedSharding(mesh, spec))).compile()
    finally:
        mesh_mod.set_global_mesh(None)
    (kernel,) = custom_call_lines(compiled)
    assert kernel.startswith("%" + ck.FWD_NAME)
    assert f"bf16[{N},{H}]" in kernel and f"bf16[{V},{H}]" in kernel
