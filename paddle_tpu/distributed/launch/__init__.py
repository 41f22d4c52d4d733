"""Distributed launcher (``python -m paddle_tpu.distributed.launch``).

Reference parity: python/paddle/distributed/launch/ (Context arg/env
parsing, CollectiveController process watch, Pod/Container spawn, elastic
relaunch — verify).

TPU-native design: one worker process per HOST (a TPU host owns all its
local chips through one PJRT client, unlike the reference's
process-per-GPU), so ``--nproc_per_node`` defaults to 1; multi-host runs
rendezvous through the C++ TCPStore at ``--master`` and jax's
coordination service gets the same address. Failure handling is
relaunch-from-checkpoint: the watch loop restarts the whole local pod on
worker death (paddle's elastic manager semantics, SURVEY §5)."""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

from ..launch_utils import find_free_port
from ...utils.flags import env_int

__all__ = ["LaunchConfig", "launch_pod", "main"]


class LaunchConfig:
    def __init__(self, script: str, script_args=(), nnodes: int = 1,
                 node_rank: int = 0, nproc_per_node: int = 1,
                 master: Optional[str] = None, log_dir: str = "log",
                 max_restarts: int = 0, backend: Optional[str] = None,
                 envs: Optional[dict] = None):
        self.script = script
        self.script_args = list(script_args)
        self.nnodes = nnodes
        self.node_rank = node_rank
        self.nproc_per_node = nproc_per_node
        self.master = master or f"127.0.0.1:{find_free_port()}"
        self.log_dir = log_dir
        self.max_restarts = max_restarts
        self.backend = backend
        self.envs = envs or {}

    @property
    def world_size(self):
        return self.nnodes * self.nproc_per_node


def _worker_env(cfg: LaunchConfig, local_rank: int, restart: int) -> dict:
    rank = cfg.node_rank * cfg.nproc_per_node + local_rank
    env = dict(os.environ)
    env.update(cfg.envs)
    env.update({
        # the reference's env contract (SURVEY §2.4)
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(cfg.world_size),
        "PADDLE_MASTER": cfg.master,
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_RESTART_COUNT": str(restart),
        # jax distributed coordination mirrors it
        "JAX_COORDINATOR_ADDRESS": cfg.master,
        "JAX_PROCESS_ID": str(rank),
        "JAX_NUM_PROCESSES": str(cfg.world_size),
    })
    if cfg.backend:
        env["JAX_PLATFORMS"] = cfg.backend
    return env


def _spawn_pod(cfg: LaunchConfig, restart: int) -> List[subprocess.Popen]:
    os.makedirs(cfg.log_dir, exist_ok=True)
    procs = []
    for lr in range(cfg.nproc_per_node):
        rank = cfg.node_rank * cfg.nproc_per_node + lr
        log = open(os.path.join(cfg.log_dir,
                                f"workerlog.{rank}.r{restart}"), "w")
        cmd = [sys.executable, "-u", cfg.script] + cfg.script_args
        p = subprocess.Popen(cmd, env=_worker_env(cfg, lr, restart),
                             stdout=log, stderr=subprocess.STDOUT)
        p._pt_log = log  # keep handle for close
        procs.append(p)
    return procs


def _kill_pod(procs):
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    deadline = time.time() + 10
    for p in procs:
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
    for p in procs:
        p._pt_log.close()


def launch_pod(cfg: LaunchConfig) -> int:
    """Spawn the local pod and watch it. On a worker failure: if restarts
    remain, kill the pod and relaunch it (workers resume from their last
    checkpoint — the reference's elastic recovery model); else tear down
    and return the failing exit code."""
    restart = 0
    while True:
        procs = _spawn_pod(cfg, restart)
        failed_code = None
        while True:
            alive = 0
            for p in procs:
                code = p.poll()
                if code is None:
                    alive += 1
                elif code != 0 and failed_code is None:
                    failed_code = code
            if failed_code is not None or alive == 0:
                break
            time.sleep(0.2)
        if failed_code is None:
            for p in procs:
                p._pt_log.close()
            return 0
        _kill_pod(procs)
        if restart >= cfg.max_restarts:
            print(f"[launch] worker failed with exit code {failed_code}; "
                  f"no restarts left", file=sys.stderr)
            return failed_code
        restart += 1
        print(f"[launch] worker failed (exit {failed_code}); relaunching "
              f"pod (restart {restart}/{cfg.max_restarts})",
              file=sys.stderr)


def main(argv=None):
    import argparse
    parser = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="launch distributed training "
                    "(one worker process per TPU host)")
    parser.add_argument("--nnodes", type=int, default=1)
    parser.add_argument("--node_rank", type=int,
                        default=env_int("PADDLE_NODE_RANK", 0))
    parser.add_argument("--nproc_per_node", type=int, default=1)
    parser.add_argument("--master", type=str, default=None,
                        help="host:port of the rank-0 rendezvous store")
    parser.add_argument("--log_dir", type=str, default="log")
    parser.add_argument("--max_restarts", type=int, default=0,
                        help=">0 enables elastic relaunch-on-failure")
    parser.add_argument("--backend", type=str, default=None,
                        help="override JAX_PLATFORMS for workers")
    parser.add_argument("--devices", type=str, default=None,
                        help="accepted for reference-CLI compatibility; "
                        "TPU visibility is per-host, so this is ignored")
    parser.add_argument("script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    cfg = LaunchConfig(
        script=args.script, script_args=args.script_args,
        nnodes=args.nnodes, node_rank=args.node_rank,
        nproc_per_node=args.nproc_per_node, master=args.master,
        log_dir=args.log_dir, max_restarts=args.max_restarts,
        backend=args.backend)
    return launch_pod(cfg)
