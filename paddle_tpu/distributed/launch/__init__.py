"""``python -m paddle_tpu.distributed.launch`` — multi-process launcher with
failure watching and restart.

Reference parity: python/paddle/distributed/launch/main.py:18 (the `launch`
CLI: collective mode, --nproc_per_node/--master/--nnodes, per-worker env +
log files, proc watching) and fleet/elastic/manager.py:131 (watch loop,
restart on worker failure).

TPU-native notes: one launched process is one JAX *controller* that owns the
host's local chips (multi-controller SPMD) — a chip belongs to one process,
so on a TPU host ``--nproc_per_node`` is 1 and the controller's mesh spans
the chips; several workers per node are for CPU runs and must say so
(``JAX_PLATFORMS=cpu``).  The launcher itself never imports jax: a parent
that touched the backend would hold the chips its workers need.  The
launcher's env contract
(PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ENDPOINTS /
PADDLE_MASTER / PADDLE_CURRENT_ENDPOINT) is what
``init_parallel_env`` (parallel.py) feeds into
``jax.distributed.initialize`` — the TCPStore/NCCL-id rendezvous of the
reference becomes JAX's coordinator service.  The watcher implements the
elastic manager's restart semantics: if any local worker dies, the whole
local set is torn down and relaunched with the same ranks (up to
--max_restarts), which is exactly the recovery a fixed-topology TPU pod
supports.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

__all__ = ["launch", "main"]


def _parse(argv: Optional[List[str]] = None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="multi-process distributed launcher")
    p.add_argument("--nproc_per_node", type=int,
                   default=int(os.environ.get("PADDLE_NPROC_PER_NODE", "1")))
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--master", type=str, default=None,
                   help="coordinator host:port (default 127.0.0.1:<free>)")
    p.add_argument("--ips", type=str, default=None,
                   help="comma-separated node hostnames, node_rank order "
                        "(required for --nnodes > 1)")
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("--max_restarts", type=int, default=None,
                   help="restarts after worker failure before giving up "
                        "(default: 0 for plain launch, 3 for elastic)")
    p.add_argument("--max_relaunches", type=int,
                   default=int(os.environ.get(
                       "PADDLE_TPU_MAX_RELAUNCHES", "100")),
                   help="cap on worker-REQUESTED relaunches (exit code "
                        "101: preemption commit / hang watchdog) — these "
                        "do not consume the --max_restarts fault budget")
    p.add_argument("--start_port", type=int,
                   default=int(os.environ.get("PADDLE_START_PORT", "6170")))
    p.add_argument("--elastic_coordinator", type=str,
                   default=os.environ.get("PADDLE_ELASTIC_COORDINATOR"),
                   help="shared directory for elastic membership "
                        "(FileCoordinator; reference: --elastic_server "
                        "etcd url)")
    p.add_argument("--np", type=str, default=None,
                   help='elastic node count, "N" or "min:max" '
                        "(with --elastic_coordinator)")
    p.add_argument("--job_id", type=str,
                   default=os.environ.get("PADDLE_ELASTIC_JOB_ID", "default"),
                   help="elastic job id namespacing the coordinator")
    p.add_argument("--elastic_timeout", type=float,
                   default=float(os.environ.get(
                       "PADDLE_ELASTIC_TIMEOUT", "0") or 0) or None,
                   help="seconds membership may sit between min_np and "
                        "max_np before launching anyway (default 120; "
                        "chaos drills shrink it so a host kill settles "
                        "in test time)")
    p.add_argument("--lease_ttl", type=float,
                   default=float(os.environ.get(
                       "PADDLE_ELASTIC_LEASE_TTL", "0") or 0) or None,
                   help="node lease ttl seconds (default 60; a dead "
                        "host's membership lapses after this)")
    p.add_argument("--host", type=str,
                   default=os.environ.get("POD_IP"),
                   help="this node's address for elastic membership")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Worker:
    def __init__(self, rank: int, cmd: List[str], env: dict,
                 log_path: Optional[str]):
        self.rank = rank
        self.cmd = cmd
        self.env = env
        self.log_path = log_path
        self.proc: Optional[subprocess.Popen] = None
        self._log_f = None

    def start(self):
        if self.log_path:
            self._log_f = open(self.log_path, "ab")
            out = self._log_f
        else:
            out = None
        self.proc = subprocess.Popen(self.cmd, env=self.env, stdout=out,
                                     stderr=subprocess.STDOUT if out else None)

    def poll(self):
        return self.proc.poll() if self.proc else None

    def terminate(self):
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if self._log_f:
            self._log_f.close()
            self._log_f = None


def _pinned_to_cpu(env) -> bool:
    """True when ``JAX_PLATFORMS`` in ``env`` names the CPU and nothing
    else (what a worker's jax will read; the launcher stays off jax)."""
    platforms = [p.strip().lower()
                 for p in env.get("JAX_PLATFORMS", "").split(",") if p.strip()]
    return bool(platforms) and all(p == "cpu" for p in platforms)


def _build_workers(args, master: str) -> List[_Worker]:
    n_local = args.nproc_per_node
    if n_local > 1 and not _pinned_to_cpu(os.environ):
        raise SystemExit(
            f"--nproc_per_node {n_local}: every worker would claim all of "
            "this host's TPU chips, and a chip belongs to one process.  On "
            "a TPU host start ONE controller per host (--nproc_per_node 1; "
            "its mesh spans the local chips).  Several workers per node "
            "are for CPU runs only: export JAX_PLATFORMS=cpu to say so.")
    world = n_local * args.nnodes
    if args.nnodes > 1:
        if not args.ips:
            raise SystemExit(
                "--nnodes > 1 requires --ips host0,host1,... so every "
                "node's endpoints are addressable")
        hosts = [h.strip() for h in args.ips.split(",")]
        if len(hosts) != args.nnodes:
            raise SystemExit(
                f"--ips lists {len(hosts)} hosts for --nnodes {args.nnodes}")
    else:
        hosts = [master.split(":")[0]]
    endpoints = []
    for node in range(args.nnodes):
        for i in range(n_local):
            endpoints.append(
                f"{hosts[node]}:{args.start_port + node * n_local + i}")
    workers = []
    for i in range(n_local):
        rank = args.node_rank * n_local + i
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_LOCAL_RANK": str(i),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
            "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
            "PADDLE_MASTER": master,
        })
        cmd = [sys.executable, args.training_script] + \
            list(args.training_script_args)
        log = (os.path.join(args.log_dir, f"workerlog.{rank}")
               if args.log_dir else None)
        workers.append(_Worker(rank, cmd, env, log))
    return workers


def _launch_elastic(args) -> int:
    """Membership-driven launch loop (reference: elastic manager.watch
    driving the launcher; fleet/elastic/manager.py:570).  Each round:
    wait for a launchable membership, regenerate ranks, start workers,
    then restart on membership change / ELASTIC_EXIT_CODE, exit on
    completion or error."""
    import socket

    from ..fleet.elastic import (
        ElasticManager, ElasticStatus, FileCoordinator, LauncherInterface)

    host = args.host or socket.gethostname()
    curr = f"{host}:{args.start_port}"
    coord = FileCoordinator(args.elastic_coordinator)
    mk = {}
    if args.elastic_timeout is not None:
        mk["elastic_timeout"] = args.elastic_timeout
    if args.lease_ttl is not None:
        mk["lease_ttl"] = args.lease_ttl
    manager = ElasticManager(coord, job_id=args.job_id,
                             np=args.np or str(args.nnodes),
                             curr_host=curr, **mk)
    if args.max_restarts is not None:
        # 0 is a real request: a deterministic crash should error out,
        # not burn the default 3-fault budget
        manager.max_faults = args.max_restarts

    class _Launcher(LauncherInterface):
        def __init__(self):
            self.workers = []

        def launch(self):
            for w in self.workers:
                w.start()

        def watch(self):
            alive = False
            for w in self.workers:
                rc = w.poll()
                if rc is None:
                    alive = True
                elif rc != 0:
                    return rc
            return None if alive else 0

        def stop(self):
            for w in self.workers:
                w.terminate()

    current = {"launcher": None}

    def _teardown(sig, _frame):
        if current["launcher"] is not None:
            current["launcher"].stop()
        manager.exit()
        coord.close()
        sys.exit(128 + sig)

    old_int = signal.signal(signal.SIGINT, _teardown)
    old_term = signal.signal(signal.SIGTERM, _teardown)
    round_idx = 0
    try:
        while True:
            if not manager.wait(timeout=manager.elastic_timeout * 4):
                print("[launch] elastic: membership never became "
                      "launchable", file=sys.stderr)
                return 1
            env_updates = manager.sync()
            if env_updates is None:
                # this host fell out of the regenerated membership (lease
                # lapse during churn): hold as a standby — the heartbeat
                # re-registers when a slot frees up
                time.sleep(max(manager.lease_ttl / 3.0, 0.05))
                continue
            os.environ.update(env_updates)
            # rebuild worker topology from the regenerated ranks
            hosts = env_updates["PADDLE_TRAINER_ENDPOINTS"].split(",")
            args.nnodes = len(hosts)
            args.node_rank = int(env_updates["PADDLE_TRAINER_ID"])
            args.ips = ",".join(h.split(":")[0] for h in hosts)
            # every node must agree on the jax.distributed coordinator:
            # derive it purely from SHARED membership state — the rank-0
            # endpoint plus a membership-epoch offset (a fresh port per
            # membership avoids colliding with a half-dead coordinator,
            # like the static restart path; local counters would desync
            # nodes that joined in different rounds)
            if args.master:
                round_master = args.master
            else:
                import zlib

                h0, p0 = hosts[0].rsplit(":", 1)
                epoch = zlib.crc32(
                    env_updates["PADDLE_TRAINER_ENDPOINTS"].encode())
                round_master = f"{h0}:{int(p0) + 10000 + epoch % 97}"
            round_idx += 1
            launcher = _Launcher()
            current["launcher"] = launcher
            launcher.workers = _build_workers(args, round_master)
            manager.run(launcher)
            try:
                status = manager.watch()
            finally:
                launcher.stop()
                current["launcher"] = None
            if status == ElasticStatus.COMPLETED:
                return 0
            if status == ElasticStatus.ERROR:
                return 1
            if status in (ElasticStatus.RESTART, ElasticStatus.HOLD):
                print(f"[launch] elastic: {status}; resyncing membership",
                      file=sys.stderr)
                continue
            return 0
    finally:
        signal.signal(signal.SIGINT, old_int)
        signal.signal(signal.SIGTERM, old_term)
        manager.exit()
        coord.close()


def launch(argv: Optional[List[str]] = None) -> int:
    """Run the launcher; returns the exit code (0 = all workers OK)."""
    args = _parse(argv)
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    master = args.master or f"127.0.0.1:{_free_port()}"

    if args.elastic_coordinator:
        return _launch_elastic(args)

    if args.max_restarts is None:
        args.max_restarts = 0      # plain launch: no implicit restarts
    restarts = 0
    relaunches = 0
    while True:
        workers = _build_workers(args, master)
        for w in workers:
            w.start()

        def _forward(sig, _frame):
            for w in workers:
                w.terminate()
            sys.exit(128 + sig)

        old_int = signal.signal(signal.SIGINT, _forward)
        old_term = signal.signal(signal.SIGTERM, _forward)
        failed = None
        try:
            # watch loop (reference: elastic manager.watch, launch
            # controller.pod watcher)
            while True:
                alive = False
                for w in workers:
                    rc = w.poll()
                    if rc is None:
                        alive = True
                    elif rc != 0:
                        failed = (w.rank, rc)
                        break
                if failed or not alive:
                    break
                time.sleep(0.2)
        finally:
            signal.signal(signal.SIGINT, old_int)
            signal.signal(signal.SIGTERM, old_term)

        if failed is None:
            for w in workers:
                w.terminate()
            return 0

        rank, rc = failed
        print(f"[launch] worker rank {rank} exited with {rc}; "
              f"tearing down peers", file=sys.stderr)
        for w in workers:
            w.terminate()
        from ..fleet.elastic.manager import ELASTIC_EXIT_CODE

        if rc == ELASTIC_EXIT_CODE:
            # the worker ASKED to be relaunched (ResilientLoop preemption
            # commit, or the step watchdog detecting a hang) — honor it
            # without consuming the fault budget; its checkpoint
            # generations make the restart cheap (reference: elastic
            # manager treats ELASTIC_EXIT_CODE as RESTART, not ERROR)
            if relaunches >= args.max_relaunches:
                print(f"[launch] giving up after {relaunches} requested "
                      f"relaunches", file=sys.stderr)
                return rc
            relaunches += 1
            master = args.master or f"127.0.0.1:{_free_port()}"
            print(f"[launch] relaunch {relaunches}/{args.max_relaunches} "
                  f"requested by worker (ranks preserved)", file=sys.stderr)
            continue
        if restarts >= args.max_restarts:
            print(f"[launch] giving up after {restarts} restarts",
                  file=sys.stderr)
            return rc if rc else 1
        restarts += 1
        # a fresh coordinator port avoids colliding with a half-dead one
        master = args.master or f"127.0.0.1:{_free_port()}"
        print(f"[launch] restart {restarts}/{args.max_restarts} "
              f"(ranks preserved)", file=sys.stderr)


def main():
    sys.exit(launch())
