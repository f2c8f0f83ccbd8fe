"""Test config: force the CPU backend with 8 virtual devices BEFORE jax import
so distributed/sharding tests exercise a multi-chip mesh without TPU hardware
(mirrors the reference's single-host multi-process test strategy,
SURVEY.md §4)."""
import os

# Force CPU for tests unless explicitly overridden (PADDLE_TPU_TEST_PLATFORM).
_plat = os.environ.get("PADDLE_TPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _plat
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# Numerics tests compare against float64 numpy: keep matmuls in true f32
# (production default is TPU-fast bf16-accumulate; SURVEY.md §7 "f32 shadow
# paths for tests").
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")

# The CPU suite runs WITHOUT a persistent compilation cache, whatever the
# environment says.  An explicit decision (PR 19's finding): executables
# DESERIALIZED from the cache are not bitwise-equivalent to freshly
# compiled ones on the XLA:CPU backend — with a warm cache the test_sentry
# rollback-parity suite failed 6/8 runs (digest mismatches that flipped
# run-to-run, plus one `free(): invalid pointer` abort in the
# deserialization path), and 8/8 passed cold.  Because cache warmth
# depends on what compiled earlier, the failures masqueraded for two PRs
# as "order-sensitive" cross-file state leaks.  Every bitwise invariant
# this suite pins (rollback parity, sharded-vs-single-chip serving,
# resharded resume, spec-decode acceptance) is hostage to that, so a
# JAX_COMPILATION_CACHE_DIR exported by whoever runs the suite (it is
# meant for chip runs, see paddle_tpu.core.chip.place_compile_cache) is
# dropped here, before jax reads it, and with it from every child
# process the tests start.  tests/test_isolation.py pins the contract.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test (skipped unless PADDLE_TPU_RUN_SLOW=1 or "
        "--runslow)")


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="run tests marked slow")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow") or \
            os.environ.get("PADDLE_TPU_RUN_SLOW") == "1":
        return
    skip = pytest.mark.skip(reason="slow; use --runslow or "
                            "PADDLE_TPU_RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


# -- a file's tests stay on one worker ------------------------------------
# The suite's expensive things are made once a file: a ``scope="module"``
# fixture (four engine warm-ups in ``fleet_chaos``), and the programs a
# process compiles for the file's model.  xdist's ``--dist load`` hands a
# file's tests out one by one, so every worker that drew one built the
# fixture and compiled the programs again (PR 43's finding: 7,334 summed
# seconds where one worker a file needs under 4,500).  How the suite is
# spread is therefore the suite's decision, made here whatever ``--dist`` the
# command names: a file goes to one worker, the longest file first, by the
# seconds ``tests/file_seconds.json`` records (``tools/junit_seconds.py``
# writes it from a run's junit file; a file it does not know goes first).

@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    import json

    from xdist.scheduler import LoadFileScheduling

    with open(os.path.join(os.path.dirname(__file__),
                           "file_seconds.json")) as f:
        seconds = json.load(f)

    class LongestFileFirst(LoadFileScheduling):
        def _assign_work_unit(self, node):
            longest = max(self.workqueue,
                          key=lambda path: seconds.get(path, float("inf")))
            self.workqueue.move_to_end(longest, last=False)
            super()._assign_work_unit(node)

    return LongestFileFirst(config, log)


# -- the global mesh ends with the module that set it ---------------------
# ``paddle_tpu.distributed.mesh``'s global mesh is process state: a file that
# sets one (``set_global_mesh``, ``fleet.init``, a hybrid topology) and does
# not take it back left it to whichever file the xdist worker ran next.
# ``test_degraded_serving.py::TestCrossMeshRecovery`` failed that way by the
# order of files (PR 25: once in three whole runs; PR 30: reproduced with a
# leaked ``hybrid_mesh(dp=2, mp=4)``): its unsharded engines trace the model's
# ``mark_sharding`` under the leaked 8-device mesh while the file's own model
# lies on 2 devices.

@pytest.fixture(autouse=True, scope="module")
def _global_mesh_ends_with_its_module():
    from paddle_tpu.distributed import mesh as mesh_mod

    before = mesh_mod.get_global_mesh()
    yield
    mesh_mod.set_global_mesh(before)


# -- shared serving chaos fixtures (test_fleet.py + test_tracing.py) -------
# The ISSUE 6 chaos scenario (a scoped fault plan killing 1 of 3 paged
# replicas mid-decode, supervision ejecting + rebuilding it) is the most
# expensive serving fixture in tier-1: four paged-engine warmups.  It
# runs once per MODULE that uses it (two: test_fleet.py asserts the failover
# semantics, test_tracing.py (ISSUE 9) runs the request-lifecycle
# trace-chain validator over a run of its own) and is dropped with the
# module: held for the session, its three engines' pools (3.07 MB of live
# arrays) failed whichever later test on the same xdist worker counts the
# process's live bytes (``benchmark_tests/test_bench_drivers.py``'s train
# run: ``live_bytes_after_free``), by the order the files happened to land.


@pytest.fixture(scope="session")
def serving_model():
    """The shared tiny GPT model serving fixtures build engines over."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def fleet_chaos(serving_model):
    """Run the chaos scenario once: a 3-replica paged fleet with a
    shared RequestTracer, a scoped fault plan killing replica 1's
    decode (both retry attempts) mid-stream, supervision ejecting +
    rebuilding it.  Returns the healed fleet plus the run's artifacts
    (including the tracer) for the assertion tests."""
    import numpy as np
    from paddle_tpu.distributed.fault_tolerance import ServingFaultPlan
    from paddle_tpu.serving import Fleet, RequestTracer

    max_new = 4
    plan = ServingFaultPlan().add("serving.r1.decode", at_call=2, times=2)
    tracer = RequestTracer()
    fleet = Fleet(serving_model, num_replicas=3, num_slots=2, max_seq=32,
                  min_bucket=16, block_size=16,
                  eject_after_failures=2, max_redispatch=2,
                  fault_plan=plan, tracer=tracer)
    fleet.warmup()
    warm = {rep.engine.name: rep.engine.metrics.compile_misses
            for rep in fleet.replicas}
    original_r1 = fleet.replicas[1].engine
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, (L,)).tolist()
               for L in (5, 9, 4, 7, 11, 3)]
    terminals, streamed = [], []
    reqs = []
    for i, p in enumerate(prompts):
        reqs.append(fleet.submit(
            p, max_new_tokens=max_new,
            # the first two are pinned onto the doomed replica so it is
            # guaranteed to hold in-flight streams when the fault fires
            replica=1 if i < 2 else None,
            stream_cb=lambda t, r: streamed.append(
                (r.request_id, r.redispatches, t)),
            done_cb=lambda r: terminals.append(r.request_id)))
    fleet.run()
    return {"fleet": fleet, "prompts": prompts, "reqs": reqs,
            "terminals": terminals, "streamed": streamed, "warm": warm,
            "original_r1": original_r1, "tracer": tracer,
            "max_new": max_new}
