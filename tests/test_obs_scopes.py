"""Whom a device operation belongs to: the rule that reads a scope out of an
HLO instruction's op_name (``obs.hlo_cost.scope_of`` / ``scope_map``), the
scopes ``Layer`` calls and the tape's backward open while ``to_static``
traces, the registry that hands a program's map out on request, and the
engine's programs compiled for the described v5e."""
import gc
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import obs
from paddle_tpu.jit import trace as jit_trace
from paddle_tpu.obs import hlo_cost, spans

from chip_programs import custom_call_lines, one_chip, pool_programs  # noqa: F401,E501

NAME, ATTRS = 0, 4


# -- the rule, on hand-written op_names ----------------------------------------

@pytest.mark.parametrize("op_name,want", [
    # the leading jit(<program>)/ and the trailing primitive go
    ("jit(f)/layer.attn/dot_general", ("layer.attn", "fwd")),
    ("jit(f)/a/b/c/mul", ("a/b/c", "fwd")),
    # each transform is unwrapped; transpose( anywhere means backward
    ("jit(f)/jvp(layer.attn)/dot_general", ("layer.attn", "fwd")),
    ("jit(f)/transpose(jvp(layer.ffn))/mul", ("layer.ffn", "bwd")),
    ("jit(f)/vmap(jvp(a))/b/mul", ("a/b", "fwd")),
    ("jit(f)/a/transpose(jvp())/mul", ("a", "bwd")),
    ("jit(f)/jvp(a/b)/c/transpose(d)/mul", ("a/b/c/d", "bwd")),
    # the tape's backward sweep: transpose(<the forward's path>)
    ("jit(s)/transpose(gpt/layers/3/attn)/transpose(jvp())/mul",
     ("gpt/layers/3/attn", "bwd")),
    ("jit(s)/transpose()/add", ("", "bwd")),
    # each structural part
    ("jit(f)/jvp(layer.ffn)/while", ("layer.ffn", "fwd")),
    ("jit(f)/transpose(jvp(layer.ffn))/while/body/closed_call/inner.loop/"
     "dot_general", ("layer.ffn/inner.loop", "bwd")),
    ("jit(f)/x/while/cond/lt", ("x", "fwd")),
    ("jit(f)/sampler.sample/cond/branch_1_fun/while/body/lt",
     ("sampler.sample", "fwd")),
    ("jit(f)/jvp(loss.ce)/jit(log_softmax)/reduce_sum", ("loss.ce", "fwd")),
    ("jit(f)/a/pjit(inner)/b/add", ("a/b", "fwd")),
    ("jit(f)/a/checkpoint/b/custom_jvp_call/c/exp", ("a/b/c", "fwd")),
    ("jit(f)/a/custom_vjp_call/remat/b/exp", ("a/b", "fwd")),
    # what is no name of [A-Za-z0-9_.] goes (an einsum's spec)
    ("jit(s)/gpt/layers/3/attn/jvp(attention.xla_sdpa)/bhqk,bkhd->bqhd/"
     "transpose", ("gpt/layers/3/attn/attention.xla_sdpa", "fwd")),
    # a call's op_name ends in no primitive
    ("jit(s)/gpt/model.embed/embeddings/jvp(jit(_take))",
     ("gpt/model.embed/embeddings", "fwd")),
    # a Pallas kernel's name= stands before its primitive: a scope's part
    ("jit(decode_step)/m/layers/0/attn/jit(_decode_call)/"
     "paged_decode_attention/pallas_call",
     ("m/layers/0/attn/paged_decode_attention", "fwd")),
    # the inliner's join of whole paths: the last one
    ("jit(f)/m/layers/0/attn/jit(searchsorted)/jit(f)/m/layers/0/attn/"
     "jit(searchsorted)/jit(f)/m/layers/0/attn/jit(searchsorted)/while/"
     "body/lt", ("m/layers/0/attn", "fwd")),
    ("jit(f)/m/layers/0/attn/jit(inner)/m/layers/0/attn/while/body/add",
     ("m/layers/0/attn", "fwd")),
    # nothing left, and names JAX did not build under the program
    ("jit(f)/add", ("", "fwd")),
    ("reduce_sum", ("", "fwd")),
    ("sk[4]", ("", "fwd")),
])
def test_the_rule_on_one_op_name(op_name, want):
    assert hlo_cost.scope_of(op_name) == want


HAND_HLO = '''HloModule jit_hand_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(hand_step)/a/0/x/mul"}
  %add.1 = f32[8]{0} add(%mul.1, %p), metadata={op_name="jit(hand_step)/a/0/x/add"}
  ROOT %neg.1 = f32[8]{0} negate(%add.1), metadata={op_name="jit(hand_step)/b/neg"}
}

%body.2 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %g = f32[8]{0} get-tuple-element(%t), index=1
  %fusion.7 = f32[8]{0} fusion(%g), kind=kLoop, calls=%fused_computation.1
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%g, %fusion.7)
}

%cond.2 (t: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%t.1, %t.1), direction=LT, metadata={op_name="jit(hand_step)/transpose(jvp(loss))/while/cond/lt"}
}

ENTRY %main.9 (w: f32[8]) -> f32[8] {
  %w = f32[8]{0} parameter(0), metadata={op_name="w[0]"}
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%w)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  %copy.5 = f32[8]{0} copy(%w), metadata={op_name="w[0]"}
  %while.4 = (s32[], f32[8]{0}) while(%copy-done.1), condition=%cond.2, body=%body.2
  %lonely.1 = f32[8]{0} negate(%copy.5)
  ROOT %out.1 = f32[8]{0} add(%copy.5, %copy.5), metadata={op_name="jit(hand_step)/transpose(jvp(loss))/add"}
}
'''


def test_the_map_over_hand_written_hlo():
    m = hlo_cost.scope_map(HAND_HLO)
    assert m["module"] == "jit_hand_step"
    got = m["instructions"]
    # every computation's instructions are mapped: a fusion's, a body's
    assert got["mul.1"] == ("a/0/x", "fwd") and got["neg.1"] == ("b", "fwd")
    assert got["lt.1"] == ("loss", "bwd")
    # a fusion without an op_name: the most frequent scope of what it calls
    assert got["fusion.7"] == ("a/0/x", "fwd")
    # a while without one: its body's and its condition's instructions vote
    # (the fusion's reading was taken first: innermost computations first)
    assert got["while.4"] in (("a/0/x", "fwd"), ("loss", "bwd"))
    # a copy named after its parameter, and a weight moved ahead of its use,
    # read the first instruction that consumes them
    assert got["copy.5"] == ("loss", "bwd")
    assert got["copy-done.1"] == got["while.4"] == got["copy-start.1"]
    # nothing to go by: unscoped
    assert got["lonely.1"] == (hlo_cost.UNSCOPED, "fwd")


def test_instructions_are_told_with_their_callees_and_operands():
    rows = {r[1]: r for r in hlo_cost.instructions(HAND_HLO)}
    assert rows["while.4"][0] == "main.9" and rows["while.4"][2] == "while"
    assert sorted(rows["while.4"][4]) == ["body.2", "cond.2"]
    assert rows["fusion.7"][4] == ["fused_computation.1"]
    assert rows["out.1"][3].endswith("/add") and rows["out.1"][5] == [
        "copy.5", "copy.5"]
    assert rows["mul.1"][0] == "fused_computation.1"


# -- a tiny GPT train step on the CPU ------------------------------------------

@pytest.fixture(scope="module")
def tiny_step():
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    @paddle.jit.to_static
    def scoped_train_step(x, y):
        loss = model.compute_loss(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 100, (2, 16)).astype("int64"))
    t = spans.clock()
    scoped_train_step(ids, ids)
    return scoped_train_step, ids, model, t


def by_opcode(hlo, opcodes):
    return [r for r in hlo_cost.instructions(hlo) if r[2] in opcodes]


def test_the_train_steps_instructions_have_owners(tiny_step):
    step, _ids, _model, _t = tiny_step
    prog = step.last_program()
    m = prog.scope_map()
    assert m["module"] == prog.module == "jit_scoped_train_step"
    assert prog.scope_map() is m                       # kept with the text
    got, hlo = m["instructions"], prog.compiled_stats()["hlo"]
    named = {r[1]: r[3] for r in hlo_cost.instructions(hlo) if r[3]}
    # every instruction the CE built maps to its scope, both directions
    ce = {got[n] for n, op in named.items() if "loss.streamed_ce" in op}
    assert ce == {("loss.streamed_ce", "fwd"), ("loss.streamed_ce", "bwd")}
    in_loops = {got[n] for n, op in named.items()
                if "loss.streamed_ce" in op and "/while/body/" in op}
    assert in_loops == ce                 # the loops' bodies, as traced
    # AdamW's to the optimizer's
    adamw = {got[n] for n, op in named.items() if "optimizer.adamw" in op}
    assert adamw == {("optimizer.adamw", "fwd")}
    # a matmul of block 1 to the layers that built it, forward and backward
    dots = {got[r[1]] for r in by_opcode(hlo, ("dot",))}
    for want in (("gpt/layers/1/attn/qkv_proj", "fwd"),
                 ("gpt/layers/1/attn/qkv_proj", "bwd"),
                 ("gpt/layers/1/mlp/fc2", "fwd"),
                 ("gpt/layers/0/mlp/fc1", "bwd")):
        assert want in dots, (want, sorted(dots))
    # what is no Layer has its scope too
    scopes = {s for s, _d in got.values()}
    assert "gpt/model.embed/embeddings/word_embeddings" in scopes
    assert "gpt/model.head/final_ln" in scopes
    assert all(re.fullmatch(r"[A-Za-z0-9_./]*", s) for s in scopes)


def test_asking_for_a_map_is_a_span_and_a_hit_builds_nothing(tiny_step):
    step, ids, _model, t_miss = tiny_step
    prog = step.last_program()

    def rows(t):
        return [r for r in spans.snapshot(since=t)
                if r[NAME] == "jit.scope_map"]

    t = spans.clock()
    prog._text._map = None                # as before anybody asked
    for _ in range(3):
        step(ids, ids)                    # hits of the program cache
    assert rows(t) == [] and prog._text._map is None
    assert [r[NAME] for r in spans.snapshot(since=t)] == []
    m = prog.scope_map()
    (row,) = rows(t)
    assert row[ATTRS]["module"] == "jit_scoped_train_step"
    assert row[ATTRS]["instructions"] == len(m["instructions"])
    assert 0 < row[ATTRS]["scoped"] <= row[ATTRS]["instructions"]
    prog.scope_map()
    assert len(rows(t)) == 1              # built once


def test_a_hit_touches_neither_a_clock_nor_the_registry(tiny_step,
                                                        monkeypatch):
    step, ids, _model, _t = tiny_step

    def refuse(*_a, **_k):
        raise AssertionError("a program-cache hit reached this")

    class Closed:
        add = __contains__ = __iter__ = refuse

    monkeypatch.setattr(jit_trace, "_built", Closed())
    monkeypatch.setattr(jit_trace.CompiledProgram, "text", refuse)
    monkeypatch.setattr(spans, "clock", refuse)
    step(ids, ids)


def test_the_registry_keeps_no_program_alive_and_the_text_outlives_it():
    import jax

    @paddle.jit.to_static
    def short_lived_program(x):
        with jax.named_scope("short.scope"):
            return x * 2 + 1

    x = paddle.to_tensor(np.ones((3,), "float32"))
    short_lived_program(x)
    prog = short_lived_program.last_program()
    assert prog in jit_trace._built
    (m,) = obs.scope_maps({"jit_short_lived_program", "jit_never_built"})
    assert m is prog.scope_map()
    assert ("short.scope", "fwd") in m["instructions"].values()
    del prog, short_lived_program
    gc.collect()
    assert "jit_short_lived_program" not in {
        p.module for p in jit_trace._built}
    jax.clear_caches()                    # as a driver does between phases
    (again,) = obs.scope_maps({"jit_short_lived_program"})
    assert again is m                     # the text, kept for a while
    assert obs.scope_maps({"jit_never_built"}) == []


def test_a_program_that_never_ran_is_compiled_when_asked():
    @paddle.jit.to_static
    def only_traced(x):
        return x + 1

    x = paddle.to_tensor(np.ones((2,), "float32"))
    prog = only_traced.get_concrete_program(x)
    assert prog._text is None
    prog._last_arg_arrays = [x._value()]
    assert prog.scope_map()["module"] == "jit_only_traced"


# -- Layer calls as scopes -----------------------------------------------------

def test_an_eager_layer_call_opens_no_scope(monkeypatch):
    import jax

    from paddle_tpu.core import tensor as tensor_mod

    layer = paddle.nn.Linear(4, 4)
    assert tensor_mod._trace_hook is None

    def refuse(_name):
        raise AssertionError("an eager call opened a scope")

    monkeypatch.setattr(jax, "named_scope", refuse)
    layer(paddle.to_tensor(np.ones((2, 4), "float32")))


def test_a_layer_is_called_by_the_name_its_parent_holds_it_under():
    from paddle_tpu.nn import Layer, LayerList, Linear

    class Block(Layer):
        def __init__(self):
            super().__init__()
            self.proj = Linear(4, 4)

        def forward(self, x):
            return self.proj(x)

    class Net(Layer):
        def __init__(self, shared):
            super().__init__()
            self.h = LayerList([Block(), Block()])
            self.add_sublayer("odd name/1", Linear(4, 4))
            self.late = LayerList()
            self.late.append(shared)

        def forward(self, x):
            for block in self.h:
                x = block(x)
            return self.late[0](self._sub_layers["odd name/1"](x))

    shared = Linear(4, 4)
    net = Net(shared)
    assert net.h[1]._scope_name == "h/1"              # the list is no call
    assert net.h[1].proj._scope_name == "proj"
    assert net._sub_layers["odd name/1"]._scope_name == "odd_name_1"
    assert shared._scope_name == "late/0"
    other = Net(shared)                   # held twice: the name given last
    other.tail = shared
    assert shared._scope_name == "tail"
    other.late[0] = shared
    assert shared._scope_name == "late/0"

    @paddle.jit.to_static
    def run_net(x):
        return net(x)

    run_net(paddle.to_tensor(np.ones((2, 4), "float32")))
    scopes = {s for s, _d in
              run_net.last_program().scope_map()["instructions"].values()}
    # nobody holds the net: its class names it; forward() called directly
    # would stay in the caller's scope
    assert {"Net/h/0/proj", "Net/h/1/proj", "Net/odd_name_1",
            "Net/late/0"} <= scopes


# -- the engine's programs, compiled for the chip that is described here -------

TRIVIAL = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
CONTROL = ("while", "conditional", "call")


@pytest.mark.parametrize("program,kernel", [
    ("decode", "paged_decode_attention"),
    ("prefill", "paged_prefill_attention")])
def test_engine_programs_on_the_chip_have_owners_and_their_kernels_names(
        pool_programs, program, kernel):
    """The decode and bucket-32 prefill programs at GPT-2 345M's widths, two
    layers, compiled for the described v5e: of the instructions that run as
    device events (those of the entry, of loop bodies and of branches, not a
    fusion's or a reducer's inner ones; no ``parameter``, ``constant``,
    ``tuple``, ``get-tuple-element``, ``bitcast``) under 10 % are unscoped,
    and the outer ``Layer`` scopes changed no kernel's instruction name:
    XLA:TPU names a Pallas custom call after the LAST part of its op_name,
    the ``pallas_call``'s ``name=``, which is what the accepted roofline
    readers match."""
    _eng, compiled = pool_programs("paged", program, num_hidden_layers=2)
    hlo = compiled.as_text()
    rows = hlo_cost.instructions(hlo)
    got = hlo_cost.scope_map(hlo)["instructions"]
    inner = {c for r in rows if r[2] not in CONTROL for c in r[4]}
    events = [r for r in rows if r[0] not in inner and r[2] not in TRIVIAL]
    unscoped = [r[1] for r in events if got[r[1]][0] == hlo_cost.UNSCOPED]
    assert len(events) > 100
    assert len(unscoped) < 0.10 * len(events), unscoped
    kernels = [ln.split(" = ")[0] for ln in custom_call_lines(compiled)]
    assert [re.sub(r"\.\d+$", "", n) for n in kernels] == ["%" + kernel] * 2
    for i, n in enumerate(sorted(kernels)):
        assert got[n.lstrip("%")] == (
            f"GPTForCausalLM/gpt/layers/{i}/attn/{kernel}", "fwd")
    scopes = {s for s, _d in got.values()}
    for want in ("GPTForCausalLM/gpt/model.embed/embeddings/word_embeddings",
                 "GPTForCausalLM/gpt/model.head/final_ln",
                 "GPTForCausalLM/model.head", "sampler.sample",
                 "GPTForCausalLM/gpt/layers/1/attn/kv.write",
                 "GPTForCausalLM/gpt/layers/0/mlp/fc1"):
        assert want in scopes, want
