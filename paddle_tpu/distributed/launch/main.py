"""Job launcher: ``python -m paddle_tpu.distributed.launch``.

(reference: python/paddle/distributed/launch/main.py:20 +
controllers/collective.py:37 CollectiveController.build_pod — spawns one
process per GPU with PADDLE_TRAINER_ENDPOINTS / PADDLE_MASTER / rank
envs; controllers/watcher.py liveness monitor.)

TPU-native process model: XLA is single-controller per HOST — one
process drives all local chips (the reference runs one per GPU). So:
- single host, no --nnodes: exec the script in-process (env setup only);
- --nnodes N: this process is one trainer of N; we export the PADDLE_*
  envs and (when available) point jax.distributed at the coordinator so
  multi-host meshes form over DCN;
- --nproc_per_node K (CPU simulation only): fork K local trainer
  processes with ranked envs, watch them, propagate the first failure
  (the watcher role). Every child would see every local chip, and a chip
  belongs to one process at a time, so on a host with TPUs this path
  refuses to start unless the children are pinned to the CPU
  (``JAX_PLATFORMS=cpu``).

The launcher itself never touches JAX: the parent only sets environment,
so the chips are left to the child.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List

__all__ = ["launch"]


def _parse(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch a (multi-host) training job")
    p.add_argument("--master", default=None,
                   help="coordinator host:port (rank-0 host)")
    p.add_argument("--nnodes", type=int, default=1,
                   help="number of hosts in the job")
    p.add_argument("--rank", type=int, default=None,
                   help="this host's rank (default: from env or 0)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="local trainer processes (CPU simulation; "
                        "refused on a host with TPUs)")
    p.add_argument("--devices", default=None,
                   help="visible device ids, comma separated")
    p.add_argument("--log_dir", default=None, help="per-rank log dir")
    p.add_argument("--elastic_level", type=int, default=0,
                   help="0: fail fast; 1: relaunch the pod on failure "
                        "(trainers must resume from their checkpoint, "
                        "see fleet.elastic.load_train_state)")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="relaunch budget under --elastic_level 1")
    p.add_argument("training_script", help="script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _base_env(args, rank: int, world: int) -> dict:
    env = dict(os.environ)
    env["PADDLE_TRAINER_ID"] = str(rank)
    env["PADDLE_TRAINERS_NUM"] = str(world)
    if args.master:
        env["PADDLE_MASTER"] = args.master
    if args.devices is not None:
        env["CUDA_VISIBLE_DEVICES"] = args.devices  # parity name
        env["TPU_VISIBLE_DEVICES"] = args.devices
    env["PADDLE_DISTRI_BACKEND"] = "xla"
    return env


def _local_tpu_chips() -> int:
    """TPU chips on this host's PCI bus, counted without creating a JAX
    backend (the parent must not take the chips)."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def _watch(procs: List[subprocess.Popen]) -> int:
    """Reference watcher.py: first non-zero exit kills the pod."""
    try:
        while True:
            alive = False
            for i, p in enumerate(procs):
                rc = p.poll()
                if rc is None:
                    alive = True
                elif rc != 0:
                    for q in procs:
                        if q.poll() is None:
                            q.send_signal(signal.SIGTERM)
                    return rc
            if not alive:
                return 0
            time.sleep(0.2)
    except KeyboardInterrupt:
        for q in procs:
            if q.poll() is None:
                q.send_signal(signal.SIGTERM)
        return 130


def launch(argv=None) -> int:
    args = _parse(argv)
    world_hosts = args.nnodes
    host_rank = args.rank if args.rank is not None else \
        int(os.environ.get("PADDLE_TRAINER_ID", "0"))

    if args.nproc_per_node <= 1:
        # TPU path: ONE process drives all local chips
        env = _base_env(args, host_rank, world_hosts)
        if world_hosts > 1 and args.master:
            # multi-host: jax.distributed coordinator over DCN
            env.setdefault("JAX_COORDINATOR_ADDRESS", args.master)
            env.setdefault("JAX_NUM_PROCESSES", str(world_hosts))
            env.setdefault("JAX_PROCESS_ID", str(host_rank))
        os.environ.update(env)
        cmd = [sys.executable, args.training_script,
               *args.training_script_args]
        return subprocess.call(cmd, env=env)

    # simulation path: K ranked local processes (reference build_pod)
    chips = _local_tpu_chips()
    if chips and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        print(f"launch: --nproc_per_node {args.nproc_per_node} refused: "
              f"this host has {chips} TPU chip(s), every child would "
              "open all of them, and a chip belongs to one process at a "
              "time. One process drives all local chips (drop "
              "--nproc_per_node), or set JAX_PLATFORMS=cpu for the CPU "
              "simulation.", file=sys.stderr)
        return 2

    def build_pod(attempt: int):
        procs = []
        world = args.nproc_per_node * world_hosts
        master = args.master or "127.0.0.1:35127"
        for local in range(args.nproc_per_node):
            rank = host_rank * args.nproc_per_node + local
            env = _base_env(args, rank, world)
            env["PADDLE_MASTER"] = master
            env["PADDLE_LOCAL_RANK"] = str(local)
            env["PADDLE_RESTART_COUNT"] = str(attempt)
            if attempt > 0:
                env["PADDLE_ELASTIC_RESTART"] = "1"
            stdout = None
            if args.log_dir:
                os.makedirs(args.log_dir, exist_ok=True)
                suffix = f".{attempt}" if attempt else ""
                stdout = open(os.path.join(
                    args.log_dir, f"workerlog.{rank}{suffix}"), "w")
            procs.append(subprocess.Popen(
                [sys.executable, args.training_script,
                 *args.training_script_args],
                env=env, stdout=stdout,
                stderr=subprocess.STDOUT if stdout else None))
        return procs

    # elastic relaunch loop (reference elastic/manager.py:237-264: the
    # launcher restarts the pod on world change; trainers resume from
    # their sharded checkpoint — fleet.elastic.load_train_state, tested
    # end-to-end in tests/test_elastic_resume.py)
    attempts = args.max_restarts if args.elastic_level >= 1 else 0
    attempt = 0
    while True:
        rc = _watch(build_pod(attempt))
        if rc == 0 or attempt >= attempts:
            break
        attempt += 1
        print(f"launch: pod failed (rc={rc}); elastic relaunch "
              f"{attempt}/{attempts}", file=sys.stderr)
    if rc != 0:
        print(f"launch: pod failed with exit code {rc}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(launch())
