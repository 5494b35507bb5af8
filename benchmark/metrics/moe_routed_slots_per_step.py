"""Token-expert pairs routed to the held experts a step, all expert layers
together: the program's `moe.routed_slots` (host_counters) over the steps of
the window. Layer: experts. Moves train_tokens_per_s_per_chip."""


def read(run):
    moe = run.get("moe") or {}
    return moe["routed_slots"] / moe["steps"] if moe.get("steps") else None
