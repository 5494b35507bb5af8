"""paddle_tpu.tuning — block-size autotuning.

* `blocks.resolve_blocks` — the ONE resolution helper every Pallas
  kernel's block shapes go through: explicit FLAGS override > tuning-cache
  hit > heuristic default, provenance recorded.
* `autotune` — searches the legal block lattice by timing real kernel
  invocations; winners persist in the JSON tuning cache
  (FLAGS_tuning_cache_dir, FLAGS_autotune=load|search).

The tuned block shapes are part of the lowered HLO, so JAX's persistent
compilation cache (`core/compile_cache.py`, the one compile cache) keys on
them by itself: re-tuning recompiles exactly the programs whose blocks
changed.

Observability: `autotune_trials_total`, `tuning_cache_rejects_total` and
`block_resolutions_total{provenance=}` are mirrored into the process
metrics registry by a scrape-time collector (registered lazily and
re-registered after a test-isolation `registry().reset()`); journal events
ride component "tuning" (`autotune`, `autotune_skip`, `cache_reject`).
"""
from __future__ import annotations

from paddle_tpu.tuning.blocks import (KERNELS, Resolution, TuningCache,
                                      TUNING_SCHEMA, cache_key,
                                      last_resolution, resolve_blocks,
                                      trial_blocks, tuning_counters)

__all__ = ["KERNELS", "Resolution", "TuningCache", "TUNING_SCHEMA",
           "cache_key", "last_resolution", "resolve_blocks", "trial_blocks",
           "tuning_counters", "ensure_metrics_collector"]


def _collect(reg):
    t = tuning_counters()
    reg.counter("autotune_trials_total",
                "block-lattice candidates timed by the autotuner"
                ).labels()._set_total(float(t["autotune_trials"]))
    reg.counter("tuning_cache_rejects_total",
                "tuning-cache files rejected (stale schema/corrupt JSON)"
                ).labels()._set_total(float(t["tuning_cache_rejects"]))
    res = reg.counter("block_resolutions_total",
                      "kernel block-shape resolutions by provenance "
                      "(flag > tuned > default; trial = autotuner timing)",
                      labels=("provenance",))
    for prov in ("flag", "tuned", "default", "trial"):
        res.labels(provenance=prov)._set_total(
            float(t.get(f"resolutions_{prov}", 0)))


def ensure_metrics_collector():
    """Idempotently (re-)register the tuning collector on the process
    registry. Called on every counter bump because `registry().reset()`
    (test isolation) drops collectors; the membership probe is O(#collectors)
    and counter bumps are never on a per-step hot path."""
    from paddle_tpu.observability import metrics as obs

    obs.registry().ensure_collector(_collect)
