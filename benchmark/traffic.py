"""The one traffic generator. A mix is a data file `traffic/<mix>.json`; this
module turns it and `--seed` into the inputs of a run. Nothing else seeds a
run.

Two families of mix, told apart by the file's `family`:

  requests      an open loop of generation requests. `rate_per_s` is fixed in
                the file. Every seed gets the SAME set of prompt lengths,
                output lengths and gaps between arrivals (the quantiles of
                the file's distributions at that rate and length of window)
                in another order, with other token ids: runs with different
                seeds then do the same work. `burst` requests arrive together.
                `shared_prefix_tokens` of every prompt are the same tokens.
  token_stream  training batches: `rows` x (`seq_len` + 1) token ids a step,
                every row different, a new batch every step.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n whole numbers: the (i + 0.5) / n quantiles of the distribution,
    clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        vals = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec.get("min", 1), spec.get("max", 1 << 30)).astype(int)


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> list[dict]:
    """[{due_s, prompt (int32 array), max_new_tokens}] sorted by due time,
    all due inside [0, seconds)."""
    rng = np.random.default_rng([int(seed), 1])
    burst = int(mix.get("burst", 1))
    n_arrivals = max(1, int(round(mix["rate_per_s"] * seconds / burst)))
    n = n_arrivals * burst
    if mix.get("arrivals", "poisson") == "poisson":
        u = (np.arange(n_arrivals) + 0.5) / n_arrivals
        gaps = -np.log1p(-u)
        gaps *= seconds / gaps.sum() * n_arrivals / (n_arrivals + 1)
    else:
        gaps = np.full(n_arrivals, seconds / (n_arrivals + 1))
    due = np.repeat(np.cumsum(rng.permutation(gaps)), burst)
    prompts = rng.permutation(_quantiles(mix["prompt_tokens"], n))
    outputs = rng.permutation(_quantiles(mix["output_tokens"], n))
    shared = int(mix.get("shared_prefix_tokens", 0))
    prefix = rng.integers(1, vocab, shared).astype(np.int32)
    out = []
    for t, p, o in zip(due, prompts, outputs):
        body = rng.integers(1, vocab, max(int(p) - shared, 1)).astype(np.int32)
        out.append({"due_s": float(t), "prompt": np.concatenate([prefix, body])[:max(int(p), 1)],
                    "max_new_tokens": int(o)})
    return out


def token_batches(mix: dict, seed: int, n_batches: int, vocab: int, rows: int | None = None):
    """int32 [n_batches, rows, seq_len + 1]: ids are [..., :-1], labels
    [..., 1:]. Every row of every batch differs."""
    rng = np.random.default_rng([int(seed), 2])
    rows = rows or mix["rows"]
    return rng.integers(0, vocab, (n_batches, rows, mix["seq_len"] + 1), dtype=np.int32)


def warmup_lengths(mix: dict) -> tuple[int, int]:
    """The shortest and longest prompt the mix can send: what set-up has to
    have warmed."""
    spec = mix["prompt_tokens"]
    if spec["dist"] == "fixed":
        return spec["value"], spec["value"]
    return spec["min"], spec["max"]
