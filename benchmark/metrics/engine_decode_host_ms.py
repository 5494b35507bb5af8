"""Median over decode steps of the host's own work in one: the program's
`engine.decode.pack` + `engine.decode.dispatch` + `engine.decode.apply`
spans, that is `engine.decode_step` without `engine.decode.readback`, where
the host waits for the device. Layer: serving engine. Moves itl_p95_ms."""
from statistics import median

PARTS = ("engine.decode.pack", "engine.decode.dispatch", "engine.decode.apply")


def read(run):
    # one of each a step, in order, on the engine's thread
    by_part = [sorted((e["ts"], e["dur"]) for e in run["spans"] if e["name"] == p) for p in PARTS]
    steps = [sum(d for _, d in parts) / 1e3 for parts in zip(*by_part)]
    return median(steps) if steps else None
