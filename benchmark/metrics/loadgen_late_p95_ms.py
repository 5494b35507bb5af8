"""95th percentile of how late the benchmark's own generator submitted a
request after it was due: a starved generator must not read as a fast
server. Layer: benchmark load generator. Moves ttft_p95_ms."""
import numpy as np


def read(run):
    late = [r["submit_t"] - (run["t0"] + r["due_s"]) for r in run["records"] if r["submit_t"]]
    return 1e3 * float(np.percentile(late, 95)) if late else None
