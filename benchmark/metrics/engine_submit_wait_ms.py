"""95th percentile of the program's `engine.submit_wait` spans: what
`ServingEngine.submit` waits for the step lock before the scheduler has the
request. Layer: serving engine. Moves ttft_p95_ms."""
import numpy as np


def read(run):
    durs = [e["dur"] / 1e3 for e in run["spans"] if e["name"] == "engine.submit_wait"]
    return float(np.percentile(durs, 95)) if durs else None
