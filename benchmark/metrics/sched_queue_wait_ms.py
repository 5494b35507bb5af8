"""95th percentile of a request's wait between the scheduler's `arrival_t`
and `admitted_t`. Layer: scheduler. Moves ttft_p95_ms."""
import numpy as np


def read(run):
    if not run["queue_waits"]:
        return None
    return 1e3 * float(np.percentile(run["queue_waits"], 95))
