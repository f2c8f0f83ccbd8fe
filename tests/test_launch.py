"""paddle_tpu.distributed.launch: multi-process DP equivalence and the
failure watcher (reference: distributed/launch/main.py, elastic/manager.py
watch+restart)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "tests", "assets", "launch_dp_train.py")


def _run(args, env_extra, timeout=300):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_", "XLA_FLAGS", "JAX_"))}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # several workers per node are a CPU-only shape, and the launcher
    # reads that from the workers' environment (it never imports jax)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    return subprocess.run(args, env=env, timeout=timeout,
                          capture_output=True, text=True)


@pytest.mark.slow
class TestLaunchDP:
    def test_two_process_dp_matches_single(self, tmp_path):
        single_out = str(tmp_path / "single.json")
        r = _run([sys.executable, SCRIPT],
                 {"PADDLE_TEST_OUT": single_out})
        assert r.returncode == 0, r.stderr[-2000:]
        multi_out = str(tmp_path / "multi.json")
        r = _run([sys.executable, "-m", "paddle_tpu.distributed.launch",
                  "--nproc_per_node", "2", SCRIPT],
                 {"PADDLE_TEST_OUT": multi_out})
        assert r.returncode == 0, r.stderr[-2000:]
        single = json.load(open(single_out))
        multi = json.load(open(multi_out))
        np.testing.assert_allclose(multi, single, rtol=1e-5, atol=1e-7)

    def test_watcher_restarts_failed_worker(self, tmp_path):
        marker = str(tmp_path / "died")
        out = str(tmp_path / "out.json")
        r = _run([sys.executable, "-m", "paddle_tpu.distributed.launch",
                  "--nproc_per_node", "2", "--max_restarts", "1", SCRIPT],
                 {"PADDLE_TEST_OUT": out,
                  "PADDLE_TEST_FAIL_MARKER": marker})
        assert "restart 1/1" in r.stderr, r.stderr[-2000:]
        assert r.returncode == 0, r.stderr[-2000:]
        assert os.path.exists(out)

    def test_watcher_gives_up_after_max_restarts(self, tmp_path):
        r = _run([sys.executable, "-m", "paddle_tpu.distributed.launch",
                  "--nproc_per_node", "1", "--max_restarts", "1", SCRIPT],
                 {"PADDLE_TEST_ALWAYS_FAIL": "1"})
        assert r.returncode == 3
        assert "giving up" in r.stderr


class TestLaunchCLI:
    def test_module_entrypoint_help(self):
        r = _run([sys.executable, "-m", "paddle_tpu.distributed.launch",
                  "--help"], {})
        assert r.returncode == 0
        assert "nproc_per_node" in r.stdout

    def test_several_workers_per_node_refused_unless_cpu(self):
        """A chip belongs to one process: without JAX_PLATFORMS=cpu in
        the workers' environment, --nproc_per_node 2 would start two
        processes that each claim every local chip."""
        for platforms in ("", "tpu,cpu"):
            r = _run([sys.executable, "-m", "paddle_tpu.distributed.launch",
                      "--nproc_per_node", "2", SCRIPT],
                     {"JAX_PLATFORMS": platforms}, timeout=60)
            assert r.returncode != 0
            assert "ONE controller per host" in r.stderr, r.stderr[-800:]


class TestElasticLaunch:
    """--elastic_coordinator drives launch through the ElasticManager
    (reference: launch --elastic_server; here a FileCoordinator dir)."""

    def test_single_node_elastic_completes(self, tmp_path):
        import os
        import subprocess
        import sys
        import textwrap

        script = tmp_path / "train.py"
        script.write_text(textwrap.dedent("""
            import os
            print("RANK", os.environ.get("PADDLE_TRAINER_ID"),
                  "WORLD", os.environ.get("PADDLE_TRAINERS_NUM"))
        """))
        coord = str(tmp_path / "coord")
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--elastic_coordinator", coord,
             "--np", "1", str(script)],
            env=env, capture_output=True, text=True, timeout=240, cwd=repo)
        assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])

    @pytest.mark.slow
    def test_two_launchers_rendezvous_via_coordinator(self, tmp_path):
        """Two launch processes (simulated nodes) discover each other
        through the FileCoordinator, agree on the rank-0-derived master,
        and both complete (exercises the multi-node master derivation)."""
        import subprocess
        import textwrap

        script = tmp_path / "train.py"
        script.write_text(textwrap.dedent("""
            import os, sys
            rank = os.environ.get("PADDLE_TRAINER_ID")
            world = os.environ.get("PADDLE_TRAINERS_NUM")
            master = os.environ.get("PADDLE_MASTER")
            print("OK", rank, world, master, flush=True)
        """))
        coord = str(tmp_path / "coord")
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"

        def start(port):
            e = dict(env)
            return subprocess.Popen(
                [sys.executable, "-m", "paddle_tpu.distributed.launch",
                 "--nproc_per_node", "1", "--elastic_coordinator", coord,
                 "--np", "2", "--host", "127.0.0.1",
                 "--start_port", str(port), str(script)],
                env=e, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True, cwd=repo)

        a = start(6270)
        b = start(6280)
        try:
            out_a, err_a = a.communicate(timeout=240)
            out_b, err_b = b.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            a.kill(); b.kill()
            raise
        assert a.returncode == 0, (out_a[-800:], err_a[-1500:])
        assert b.returncode == 0, (out_b[-800:], err_b[-1500:])
        # both rounds agreed on ONE master derived from the rank-0 host
        masters = set()
        for out in (out_a, out_b):
            for line in out.splitlines():
                if line.startswith("OK"):
                    masters.add(line.split()[-1])
        assert len(masters) == 1, masters
