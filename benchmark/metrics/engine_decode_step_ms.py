"""Median duration of the program's `engine.decode_step` spans (host clock
around one packed decode dispatch and its read-back). Layer: serving engine.
Moves itl_p95_ms."""
from statistics import median


def read(run):
    durs = [e["dur"] / 1e3 for e in run["spans"] if e["name"] == "engine.decode_step"]
    return median(durs) if durs else None
