"""Time of the program's `engine.prefill` and `engine.prefill_packed` spans
over the thousands of prompt tokens they carried. Layer: serving engine.
Moves ttft_p95_ms."""


def read(run):
    spans = [e for e in run["spans"] if e["name"] in ("engine.prefill", "engine.prefill_packed")]
    tokens = sum(e["args"].get("tokens", 0) for e in spans)
    if not tokens:
        return None
    return sum(e["dur"] for e in spans) / 1e3 / (tokens / 1e3)
