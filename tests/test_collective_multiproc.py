"""Real 2-process eager collectives + elastic kill/resume end-to-end
(reference: unittests/test_collective_base.py:33 subprocess runners and
test_fleet_elastic_manager.py recovery; r4 VERDICT #5)."""
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ASSETS = os.path.join(REPO, "tests", "assets")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _worker_env(rank, world, port, extra=None):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_", "XLA_FLAGS", "JAX_"))}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    endpoints = ",".join(f"127.0.0.1:{port + i}" for i in range(world))
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_TRAINER_ENDPOINTS": endpoints,
        "PADDLE_CURRENT_ENDPOINT": f"127.0.0.1:{port + rank}",
        "PADDLE_MASTER": f"127.0.0.1:{port}",
    })
    env.update(extra or {})
    return env


def test_functional_collectives_two_processes():
    """all_reduce / broadcast / all_gather / alltoall / reduce / ppermute
    across two REAL processes (jax.distributed + gloo CPU collectives) in
    eager mode — one subprocess pair runs every collective."""
    script = os.path.join(ASSETS, "collective_2proc.py")
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, script], env=_worker_env(r, 2, port),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for r in range(2)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\n{out[-800:]}\n{err[-2500:]}"
        assert "COLLECTIVE_2PROC_OK" in out, out[-800:]
    # every collective ran on both ranks
    for rc, out, err in outs:
        line = [ln for ln in out.splitlines()
                if ln.startswith("COLLECTIVE_2PROC_OK")][0]
        ops = line.split()[-1].split(",")
        assert set(ops) == {"all_reduce", "broadcast", "all_gather",
                            "alltoall", "reduce", "ppermute"}, ops


class TestElasticResume:
    def _launch(self, nproc, env_extra, elastic_coord=None, timeout=420):
        script = os.path.join(ASSETS, "elastic_resume_train.py")
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "XLA_FLAGS", "JAX_"))}
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_PLATFORMS"] = "cpu"     # several workers per node: CPU only
        env.update(env_extra)
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nproc_per_node", str(nproc), "--max_restarts", "2"]
        if elastic_coord:
            cmd += ["--elastic_coordinator", elastic_coord, "--np", "1"]
        cmd.append(script)
        return subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=timeout)

    def test_kill_resume_loss_continuity(self, tmp_path):
        """A worker hard-dies mid-training; the watcher restarts the
        generation; training resumes from the checkpoint and the loss
        history equals an uninterrupted run's (reference: checkpoint-based
        recovery, §5.3/5.4)."""
        # uninterrupted reference
        ref_out = str(tmp_path / "ref.json")
        r = self._launch(1, {
            "PADDLE_TEST_CKPT_DIR": str(tmp_path / "ckpt_ref"),
            "PADDLE_TEST_OUT": ref_out})
        assert r.returncode == 0, r.stderr[-2500:]
        # killed-and-resumed run
        out = str(tmp_path / "resumed.json")
        r = self._launch(1, {
            "PADDLE_TEST_CKPT_DIR": str(tmp_path / "ckpt_kill"),
            "PADDLE_TEST_OUT": out,
            "PADDLE_TEST_KILL_STEP": "5",
            "PADDLE_TEST_KILL_MARKER": str(tmp_path / "died")})
        assert r.returncode == 0, r.stderr[-2500:]
        assert os.path.exists(str(tmp_path / "died")), "kill never fired"
        ref = json.load(open(ref_out))
        got = json.load(open(out))
        assert len(got) == len(ref)
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-8)

    @pytest.mark.slow
    def test_kill_resume_two_proc_elastic_coordinator(self, tmp_path):
        """Same, but 2 workers under the FileCoordinator elastic path:
        rank-1 dies, membership regenerates, training resumes from the
        checkpoint with loss continuity vs an uninterrupted 2-proc run."""
        ref_out = str(tmp_path / "ref.json")
        r = self._launch(2, {
            "PADDLE_TEST_CKPT_DIR": str(tmp_path / "ckpt_ref"),
            "PADDLE_TEST_OUT": ref_out})
        assert r.returncode == 0, r.stderr[-2500:]
        out = str(tmp_path / "resumed.json")
        r = self._launch(2, {
            "PADDLE_TEST_CKPT_DIR": str(tmp_path / "ckpt_kill"),
            "PADDLE_TEST_OUT": out,
            "PADDLE_TEST_KILL_STEP": "4",
            "PADDLE_TEST_KILL_MARKER": str(tmp_path / "died")},
            elastic_coord=str(tmp_path / "coord"))
        assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-2500:])
        assert os.path.exists(str(tmp_path / "died"))
        ref = json.load(open(ref_out))
        got = json.load(open(out))
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-8)
