"""Launcher worker: print the devices this process came up with, once.

    python -m paddle_tpu.distributed.launch --nproc_per_node 4 \
        --log_dir LOGS tests/workers/print_devices_worker.py [HOLD_SECONDS]

On a four-chip TPU host every workerlog must show exactly one local device
(one process per chip); under JAX_PLATFORMS=cpu it shows the CPU device.
HOLD_SECONDS keeps the process alive after printing: the TPU runtime builds
one slice out of the group, and a process that leaves while a slower peer is
still joining takes the peer down with it (a real trainer stays, and meets
its peers in jax.distributed anyway).
"""
import json
import os
import sys
import time

import jax

print("DEVICES " + json.dumps({
    "rank": int(os.environ.get("PADDLE_TRAINER_ID", "0")),
    "local": [str(d) for d in jax.local_devices()],
    "global_count": jax.device_count(),
    "platform": jax.devices()[0].platform,
}), flush=True)
time.sleep(float(sys.argv[1]) if len(sys.argv) > 1 else 0.0)
