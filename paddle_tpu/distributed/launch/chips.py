"""One process per chip: the TPU environment of a launched child process.

A TPU chip belongs to one process at a time, and a process that starts JAX
with the parent's environment asks for EVERY chip of the host — so N children
started that way fail or hang on each other. The launcher and `spawn` call
`child_chip_env` for each child: one child keeps the whole host (the normal
SPMD case: one process drives all chips through the mesh), several children
get one chip each, and a split this module has no verified recipe for is
refused before anything starts.

Stays off JAX and libtpu: the parent must not touch the chips it hands out.
"""
from __future__ import annotations

import glob
import os

__all__ = ["local_tpu_chips", "child_chip_env"]

# chips on the host -> (TPU_PROCESS_BOUNDS, TPU_CHIPS_PER_PROCESS_BOUNDS) for
# one process per chip. Only the 2x2 host is here: it is the one this was run
# on (CHANGES.md, PR 21); add a shape when it has been seen to come up.
_ONE_CHIP_PER_PROCESS = {4: ("2,2,1", "1,1,1")}


def local_tpu_chips() -> int:
    """TPU chips this host can open, counted from their device nodes — what
    libtpu itself enumerates: /dev/accel<N>, or numbered VFIO groups on the
    parts (v5e and later) that are passed through that way. The PCI bus is
    no guide: a one-chip slice of a four-chip host still lists four."""
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    try:
        return sum(name.isdigit() for name in os.listdir("/dev/vfio"))
    except OSError:
        return 0


def child_chip_env(local_rank: int, nproc: int, env: dict,
                   ports: list[int]) -> dict:
    """Environment additions giving child `local_rank` of `nproc` its chip.

    `env` is the environment the child would otherwise get; `ports` are
    `nproc` free local ports shared by all children of the group (the TPU
    runtime's own rendezvous). Empty when there is nothing to assign: one
    child, children pinned off the TPU by JAX_PLATFORMS, or a host with no
    TPU. Raises RuntimeError for a split with no verified recipe."""
    if nproc == 1:
        return {}
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return {}
    chips = local_tpu_chips()
    if chips == 0:
        return {}
    if nproc != chips or chips not in _ONE_CHIP_PER_PROCESS:
        raise RuntimeError(
            f"{nproc} processes on a host with {chips} TPU chips is not a "
            f"supported split: start 1 process (it drives all {chips} chips "
            f"through the mesh), or one process per chip on a host of "
            f"{sorted(_ONE_CHIP_PER_PROCESS)} chips; to run the children on "
            f"the CPU set JAX_PLATFORMS=cpu")
    process_bounds, chip_bounds = _ONE_CHIP_PER_PROCESS[chips]
    rank = str(local_rank)
    hosts = ",".join(["localhost"] * nproc)
    # libtpu reads two generations of names (HOST/WORKER and PROCESS/TASK);
    # a TPU VM image presets the older ones for the whole host, so both are
    # set here and agree
    return {
        "TPU_VISIBLE_CHIPS": rank, "TPU_VISIBLE_DEVICES": rank,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": chip_bounds,
        "TPU_CHIPS_PER_HOST_BOUNDS": chip_bounds,
        "TPU_PROCESS_BOUNDS": process_bounds,
        "TPU_HOST_BOUNDS": process_bounds,
        "TPU_PROCESS_ADDRESSES": ",".join(f"localhost:{p}" for p in ports),
        "TPU_WORKER_HOSTNAMES": hosts,
        "TPU_PROCESS_PORT": str(ports[local_rank]),
        "CLOUD_TPU_TASK_ID": rank, "TPU_WORKER_ID": rank,
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
