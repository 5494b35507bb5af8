"""Distributed launcher (reference: python/paddle/distributed/launch/main.py:21
+ controllers/collective.py): starts one process per node/rank with the env
contract (PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM, PADDLE_TRAINER_ENDPOINTS),
captures per-rank logs, and watches for failures.

TPU-native: one SPMD process per HOST (chips are driven via the mesh, not via
per-chip processes). `python -m paddle_tpu.distributed.launch --nnodes N
train.py` execs the script once per host with rank env set; a watcher restarts
or tears down the group on child failure (the launch/controllers/watcher.py
analog). Multi-host rendezvous metadata comes from --master host:port or env.
With --nproc_per_node > 1 on a TPU host each child is given its own chip
(launch/chips.py); a split with no verified recipe is refused up front.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _parse_args(argv):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--nnodes", type=int, default=1, help="number of hosts")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per host (1 = SPMD over all local chips)")
    p.add_argument("--master", type=str, default=None, help="rendezvous host:port")
    p.add_argument("--rank", type=int, default=int(os.getenv("PADDLE_NODE_RANK", "0")))
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--job_id", type=str, default="default")
    p.add_argument("--rdzv_timeout", type=float, default=300.0,
                   help="seconds to wait for all nodes at the master")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _rendezvous(args):
    """Multi-node master rendezvous (reference launch/controllers/master.py):
    the node-0 LAUNCHER hosts the job's TCPStore for its whole lifetime
    (trainer rank 0 then degrades to a store client); every node registers
    its hostname and blocks until all --nnodes are present, and the shared
    store doubles as the cross-node abort channel for the watcher."""
    import socket

    from paddle_tpu.distributed.store import TCPStore

    host, port = args.master.rsplit(":", 1)
    store = TCPStore(host, int(port), is_master=(args.rank == 0),
                     world_size=args.nnodes, timeout=args.rdzv_timeout)
    pre = f"launch/{args.job_id}"
    store.set(f"{pre}/node/{args.rank}", socket.gethostname().encode())
    peers = []
    for r in range(args.nnodes):
        peers.append(store.wait(f"{pre}/node/{r}").decode())
    print(f"rendezvous complete: {args.nnodes} nodes {peers}", file=sys.stderr)
    return store, pre, peers


def launch(argv=None):
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    os.makedirs(args.log_dir, exist_ok=True)
    procs = []
    nproc = args.nproc_per_node
    world = args.nnodes * nproc
    base_rank = args.rank * nproc
    # single-node multi-process: auto-assign rendezvous ports (TCPStore on
    # PADDLE_MASTER; jax.distributed coordination service on PADDLE_COORDINATOR)
    coordinator = os.getenv("PADDLE_COORDINATOR", "")
    rdzv_store, rdzv_pre, peers = None, None, None
    if args.nnodes > 1:
        if not args.master:
            print("--master host:port is required when --nnodes > 1", file=sys.stderr)
            return 2
        rdzv_store, rdzv_pre, peers = _rendezvous(args)
    elif world > 1:
        # ports may only be auto-picked when a single launcher spawns every
        # rank; multi-node launchers must agree, so they derive the
        # coordinator deterministically from --master (port+1) in
        # init_parallel_env instead
        if not args.master:
            args.master = f"127.0.0.1:{_free_port()}"
        if not coordinator:
            coordinator = f"{args.master.rsplit(':', 1)[0]}:{_free_port()}"
    from paddle_tpu.distributed.launch.chips import child_chip_env

    # one process per chip: each child of a multi-process TPU host gets its
    # own chip in the environment (or the split is refused) — children that
    # inherit the parent's environment would all ask for every chip
    chip_ports = [_free_port() for _ in range(nproc)] if nproc > 1 else []
    for local in range(nproc):
        rank = base_rank + local
        env = dict(os.environ)
        try:
            env.update(child_chip_env(local, nproc, env, chip_ports))
        except RuntimeError as e:  # raised for local 0: nothing started yet
            print(f"--nproc_per_node {nproc}: {e}", file=sys.stderr)
            return 2
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_LOCAL_RANK": str(local),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_JOB_ID": args.job_id,
            # session id namespaces store keys; single-node launches get a
            # fresh one per launch (stale keys from a previous incarnation are
            # dead), multi-node launchers must agree so it derives from the
            # job identity (operators can override via env)
            "PADDLE_JOB_SESSION": os.getenv(
                "PADDLE_JOB_SESSION",
                f"{args.job_id}-{os.getpid()}-{int(time.time())}" if args.nnodes == 1
                else f"{args.job_id}-{args.master or 'nomaster'}"),
        })
        if args.master:
            env["PADDLE_MASTER"] = args.master
        if coordinator:
            env["PADDLE_COORDINATOR"] = coordinator
        if peers is not None:
            # one endpoint PER TRAINER (host from its node; deterministic
            # port labels derived from the master port — trainers don't run
            # listening services in the SPMD design, the identity matters)
            mport = int(args.master.rsplit(":", 1)[1])
            eps = [f"{peers[r // nproc]}:{mport + 10 + r}" for r in range(world)]
            env["PADDLE_TRAINER_ENDPOINTS"] = ",".join(eps)
            env["PADDLE_NODE_RANK"] = str(args.rank)
        log = open(os.path.join(args.log_dir, f"workerlog.{rank}"), "w")
        cmd = [sys.executable, args.training_script] + args.training_script_args
        procs.append((subprocess.Popen(cmd, env=env, stdout=log, stderr=subprocess.STDOUT), log, rank))

    # watcher loop (reference launch/controllers/watcher.py): any failure
    # kills the local group AND — multi-node — broadcasts the abort through
    # the rendezvous store so every node's launcher tears down too
    exit_code = 0

    def _abort_group(code):
        for q, _, _ in procs:
            if q.poll() is None:
                q.send_signal(signal.SIGTERM)
        if rdzv_store is not None:
            try:
                rdzv_store.set(f"{rdzv_pre}/abort", str(code).encode())
            except Exception:
                pass

    try:
        while procs:
            alive = []
            for p, log, rank in procs:
                ret = p.poll()
                if ret is None:
                    alive.append((p, log, rank))
                elif ret != 0:
                    print(f"rank {rank} failed with exit code {ret}; terminating group",
                          file=sys.stderr)
                    exit_code = ret
                    _abort_group(ret)
                    alive = []
                    break
            procs = alive
            if procs and rdzv_store is not None:
                try:
                    remote = rdzv_store.get(f"{rdzv_pre}/abort")
                except Exception:
                    # the node-0 store died: the job is over one way or the
                    # other — tear down rather than crash with a traceback
                    remote = b"1"
                if remote:
                    exit_code = int(remote.decode() or 1)
                    print(f"remote node aborted (exit {exit_code}); terminating",
                          file=sys.stderr)
                    _abort_group(exit_code)
                    procs = []
                    break
            if procs:
                time.sleep(1)
    finally:
        for p, log, _ in procs:
            if p.poll() is None:
                p.terminate()
            log.close()
    if rdzv_store is not None:
        try:
            if exit_code != 0:
                # node 0 hosts the store: give the other nodes a grace window
                # to observe the abort key before the server dies with us
                if args.rank == 0:
                    time.sleep(min(10.0, args.rdzv_timeout))
            else:
                # every node drains until all report done (rank 0 must also
                # keep the store it hosts alive for the stragglers); a
                # straggler failing after our clean finish means the JOB
                # failed — report it, don't mask it
                rdzv_store.add(f"{rdzv_pre}/done", 1)
                deadline = time.time() + args.rdzv_timeout
                while time.time() < deadline:
                    if rdzv_store.add(f"{rdzv_pre}/done", 0) >= args.nnodes:
                        break
                    remote = rdzv_store.get(f"{rdzv_pre}/abort")
                    if remote:
                        exit_code = int(remote.decode() or 1)
                        break
                    time.sleep(0.5)
        except Exception:
            pass
    return exit_code


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
