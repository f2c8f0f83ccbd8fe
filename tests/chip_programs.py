"""The chip that is described here, and programs compiled for it: what the
tests that read a compiled program's text share (``test_obs_spans.py``: the
kernels' names, the pool's form; ``test_obs_scopes.py``: whom an instruction
belongs to).  No chip is attached: the TPU's compiler is installed and
compiles for a v5e that is described."""
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


def engine_program(one_chip, program, layers=1):
    """``(engine, compiled)``: GPT-2 345M's widths (16 heads x 64, vocab
    50304, bf16; ``layers`` layers, 32 slots, block 16, a 513-block pool),
    the paged engine's decode or bucket-32 prefill program built as
    ``to_static`` builds it and compiled for the described v5e, the kernels
    as the chip runs them."""
    import jax

    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.jit.trace import CompiledProgram, _flatten_io
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import Engine

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=50304, hidden_size=1024, num_hidden_layers=layers,
        num_attention_heads=16, max_position_embeddings=1024,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0))
    model.to(dtype="bfloat16")
    eng = Engine(model, num_slots=32, max_seq=1024, min_bucket=32,
                 block_size=16, num_kv_blocks=513,
                 kernel="pallas")
    eng.cache._interpret = False          # the kernels as the chip runs them
    eng._build_steps()
    if program == "decode":
        fn, args = eng._decode_fn, [np.zeros((32,), np.int32)]
    else:
        fn, args = eng._prefill_fn, [np.zeros((1, 32), np.int64), np.int32(0),
                                     np.int32(1), np.int32(0)]
        assert eng.cache.begin_sequence(0, [], 0, 32)
    leaves = []
    args_tree = _flatten_io([paddle.to_tensor(a) for a in args], leaves)
    prog = CompiledProgram(fn._fn, args_tree, _flatten_io({}, leaves))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    with no_grad():
        prog.build(leaves)
        sd, sk = prog._split_state([k.current() for k in prog.state_keys])
        compiled = prog.jitted_donate.lower(
            [on_chip(t._value()) for t in leaves], [on_chip(a) for a in sd],
            [on_chip(a) for a in sk]).compile()
    return eng, compiled
