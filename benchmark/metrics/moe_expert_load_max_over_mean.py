"""Largest over mean load of a held expert, the window's mean of the
program's per-step `moe.max_expert_load` and `moe.mean_expert_load`: 1 is a
balanced router. Layer: experts. Moves train_tokens_per_s_per_chip."""


def read(run):
    moe = run.get("moe") or {}
    if not moe.get("steps") or not moe.get("mean_expert_load"):
        return None
    return moe["max_expert_load"] / moe["mean_expert_load"]
