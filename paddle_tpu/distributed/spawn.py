"""paddle.distributed.spawn analog (reference: distributed/spawn.py).

TPU-native: a single SPMD process drives all local chips, so spawn() runs the
function once in-process for nprocs covering local devices; true multi-host
launches go through paddle_tpu.distributed.launch which sets the process env
(the reference env contract) before exec. With nprocs > 1 each child gets its
own chip on a TPU host (launch/chips.py) or the split is refused — children
that inherit the parent's environment would all ask for every chip.
"""
from __future__ import annotations

import multiprocessing
import os

__all__ = ["spawn"]


def _run_rank(func, args, env):
    # module-level: the spawn start method pickles the target by import path
    os.environ.update(env)
    func(*args)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    if nprocs in (-1, 0, 1):
        # SPMD: one driving process
        func(*args)
        return None
    from paddle_tpu.distributed.launch.chips import child_chip_env
    from paddle_tpu.distributed.launch.main import _free_port

    ctx = multiprocessing.get_context("spawn")
    ports = [_free_port() for _ in range(nprocs)]
    envs = [{"PADDLE_TRAINER_ID": str(rank),
             "PADDLE_TRAINERS_NUM": str(nprocs),
             **child_chip_env(rank, nprocs, dict(os.environ), ports)}
            for rank in range(nprocs)]
    procs = []
    for env in envs:
        p = ctx.Process(target=_run_rank, args=(func, args, env),
                        daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
            if p.exitcode:
                raise RuntimeError(f"spawned rank failed with exit code {p.exitcode}")
    return procs
