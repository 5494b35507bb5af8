"""Share of the traced window in which a collective ran on a device and no
compute ran beside it, averaged over the chips. Layer: mesh / GSPMD. Moves
train_tokens_per_s_per_chip."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["collective_s"]:
        return None
    return 100.0 * trace["exposed_collective_s"] / run["trace_window_s"]
