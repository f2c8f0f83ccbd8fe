"""The chip that is described here, and programs compiled for it: what the
tests that read a compiled program's text share (``test_obs_spans.py``: the
kernels' names, the pool's form; ``test_obs_scopes.py``: whom an instruction
belongs to).  The deployments themselves are ``families.py``'s.  No chip is
attached: the TPU's compiler is installed and compiles for a v5e that is
described."""
import re

import numpy as np
import pytest

import paddle_tpu as paddle


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001 — no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def kernel_lines(fn, *shapes):
    """The ``tpu_custom_call`` instructions of ``fn`` compiled for the
    described chip."""
    import jax

    return custom_call_lines(jax.jit(fn).lower(*shapes).compile())


def custom_call_lines(compiled):
    """The ``tpu_custom_call`` instructions of a compiled program, printed
    as a profiler trace names its events: operands with their shapes."""
    from jax._src.lib import xla_client as xc

    opts = xc._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_metadata = False
    opts.print_backend_config = False
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()
            if "tpu_custom_call" in ln]


def load_patterns(kernel):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "kernel_costs",
        kernel + ".py")
    spec = importlib.util.spec_from_file_location("kc_" + kernel, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def pool_programs(one_chip):
    """``pool_programs(kind, program, **config) -> (engine, compiled)``: the
    paged engine of the family that states that kind of cache, at the widths
    of its ``chip`` entry in ``families.py`` (``config`` over them), and one
    of its programs (``decode``, ``prefill`` or ``prefill-<bucket>``,
    ``publish``) built as ``to_static`` builds it and compiled for the
    described v5e, the kernels as the chip runs them.  An engine is built
    once a module and a program compiled once."""
    from families import BY_KIND
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.models import (deepseek_v3, held_experts, keye_vl2,
                                   kimi_linear, lfm2)
    from paddle_tpu.serving import Engine

    engines, compiled = {}, {}

    def get(kind, program, **config):
        family, built = BY_KIND[kind], (kind, *sorted(config.items()))
        if built not in engines:
            eng = engines[built] = Engine(
                family.chip_model(**config), block_size=16, kernel="pallas",
                **family.chip["engine"])
            for pool in getattr(eng.cache, "pools", [eng.cache]):
                pool._interpret = False   # the kernels as the chip runs them
            eng._build_steps()
        eng = engines[built]
        if (built, program) in compiled:
            return eng, compiled[built, program]
        bucket = family.chip["programs"][program]
        if program == "decode":
            fn, args = eng._decode_fn, [np.zeros((eng.num_slots,), np.int32)]
        elif program == "publish":
            fn, args = eng._publish_fn, [np.int32(0), np.int32(0)]
        else:
            fn, args = eng._prefill_fn, [np.zeros((1, bucket), np.int64),
                                         np.int32(0), np.int32(1), np.int32(0)]
            assert eng.cache.begin_sequence(0, [], 0, bucket)
        try:
            with pytest.MonkeyPatch.context() as mp, no_grad():
                for module in (deepseek_v3, held_experts, keye_vl2, kimi_linear,
                               lfm2):
                    mp.setattr(module, "_interpret", lambda: False)
                compiled[built, program] = compile_for(
                    one_chip, fn._fn, [paddle.to_tensor(a) for a in args])
        finally:
            if bucket:
                eng.cache.release_slot(0)
        return eng, compiled[built, program]

    yield get
    engines.clear()
    compiled.clear()


def compile_for(one_chip, fn, args):
    """``fn(*args)`` built as ``to_static`` builds a program (state found,
    written state donated) and compiled for the described chip from the
    shapes of its arguments and state: nothing runs."""
    import jax

    from paddle_tpu.jit.trace import CompiledProgram, _flatten_io

    leaves = []
    args_tree = _flatten_io(list(args), leaves)
    prog = CompiledProgram(fn, args_tree, _flatten_io({}, leaves))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    prog.build(leaves)
    sd, sk = prog._split_state([k.current() for k in prog.state_keys])
    return prog.jitted_donate.lower(
        [on_chip(t._value()) for t in leaves], [on_chip(a) for a in sd],
        [on_chip(a) for a in sk]).compile()


def attention_layer_program(one_chip, monkeypatch, batch=16, seq=1024):
    """One layer's attention path at GPT-2 345M's widths (``ln1`` → fused
    qkv projection → flash attention → out projection; 16 heads x 64, bf16),
    forward and backward at ``[batch, seq]`` — the train cell's call —
    built as ``to_static`` builds a step and compiled for the described
    v5e with the Pallas kernels on the path, as on the chip (the dispatch
    asks the backend; here the test answers for it)."""
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt import GPTDecoderLayer
    from paddle_tpu.ops import pallas as pallas_pkg

    monkeypatch.setattr(pallas_pkg, "use_pallas", lambda: True)
    paddle.seed(0)
    layer = GPTDecoderLayer(GPTConfig(
        vocab_size=50304, hidden_size=1024, num_hidden_layers=1,
        num_attention_heads=16, max_position_embeddings=seq,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    layer.to(dtype="bfloat16")

    def attention_step(x):
        y = layer.attn(layer.ln1(x))
        loss = (y.astype("float32") ** 2).sum()
        loss.backward()
        return loss

    x = paddle.to_tensor(np.zeros((batch, seq, 1024), np.float32)).astype(
        "bfloat16")
    return compile_for(one_chip, attention_step, [x])


_ARRAY = re.compile(r"[a-z][a-z0-9]*\[[\d,]*\]\{[^}]*\}")


def moves_around_kernels(hlo: str, is_kernel, size: int) -> list:
    """The entry computation's instructions that stand between a matmul and
    a kernel of an optimized module: neither a matmul's fusion nor a kernel
    (``is_kernel(name)``), with a result of ``size`` elements or more, and
    next to a kernel — one of its operands' producers or one of its results'
    consumers, looked for through ``bitcast`` / ``get-tuple-element`` and
    through the ``copy-start`` / ``copy-done`` by which XLA moves a tensor
    as it is laid out from one memory space to another.  What only reduces
    a kernel's result (a bias gradient) has no such result and is not among
    them."""
    from paddle_tpu.obs import hlo_cost

    rows = hlo_cost.instructions(hlo)
    entry = re.search(r"^ENTRY %?([\w.-]+)", hlo, re.M).group(1)
    elems, same_layout = {}, set()
    for ln in hlo.splitlines():
        m = hlo_cost._DEF.match(ln)
        if m:
            elems[m.group(1).lstrip("%")] = max(
                [int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
                 for _dtype, dims in hlo_cost._SHAPE.findall(m.group(2))]
                or [0])
            if m.group(3) == "copy-start":
                to, source = [re.sub(r"S\(\d+\)", "", t) for t in
                              _ARRAY.findall(m.group(2))[:2]]
                if to == source:
                    same_layout.add(m.group(1).lstrip("%"))
    with_matmul = {comp for comp, _n, opcode, *_ in rows
                   if opcode == "convolution"}
    inst = {name: (opcode, callees, [o for o in operands if o != name])
            for comp, name, opcode, _op, callees, operands in rows
            if comp == entry}
    users = {}
    for name, (_opcode, _callees, operands) in inst.items():
        for o in operands:
            users.setdefault(o, []).append(name)

    def see_through(name):
        opcode, _callees, operands = inst[name]
        return (opcode in ("bitcast", "get-tuple-element")
                or opcode == "copy-start" and name in same_layout
                or opcode == "copy-done" and operands[0] in same_layout)

    def producers(name):
        for o in inst[name][2]:
            if o in inst:
                yield from (producers(o) if see_through(o) else [o])

    def consumers(name):
        for u in users.get(name, ()):
            yield from (consumers(u) if see_through(u) else [u])

    def plain(name):
        opcode, callees, _ = inst[name]
        return not (is_kernel(name) or opcode == "parameter"
                    or any(c in with_matmul for c in callees))

    found = set()
    for k in (n for n in inst if is_kernel(n)):
        found |= {n for n in list(producers(k)) + list(consumers(k))
                  if plain(n) and elems.get(n, 0) >= size}
    return sorted(found)
