"""Shared block-shape resolution for the Pallas kernels + JSON tuning cache.

Reference analog: the reference's KernelFactory keeps one dispatch table
mapping (op, shape, dtype, place) to a selected kernel configuration; this
module is that table for the Pallas block shapes, with an on-disk tuning
cache behind it.

Every `ops/pallas/*` kernel resolves its block/tile parameters through ONE
function, `resolve_blocks`, with the precedence the tentpole contract
fixes (docs/autotuning.md):

    explicit FLAGS override  >  tuning-cache hit  >  heuristic default

and the chosen provenance recorded per kernel (`last_resolution`), so a
test — or a human staring at a perf regression — can answer "which block
shape actually ran, and why" without re-deriving flag state.

The tuning cache is a single JSON file (`tuning_cache.json` under
FLAGS_tuning_cache_dir) with schema ``paddle_tpu-tune1``: entries keyed by
(kernel, geometry, dtype, platform, lowering-relevant flags). A file with
any other schema is REJECTED with a re-tune pointer — same convention as
the ``paddle_tpu-npz1`` artifact loader's legacy rejection — never
silently reinterpreted. FLAGS_autotune selects the mode: ``off`` (default;
heuristics/flags only — zero behavior change), ``load`` (consult the
cache, heuristic on miss), ``search`` (on miss, time the legal lattice
now via tuning.autotune, persist the winner, use it).
"""
from __future__ import annotations

import json
import os
import threading
import warnings
from dataclasses import dataclass, field, replace

__all__ = ["KERNELS", "Resolution", "resolve_blocks", "last_resolution",
           "note_derived", "trial_blocks", "cache_key", "TuningCache",
           "TUNING_SCHEMA", "tuning_counters", "bump_counter"]

TUNING_SCHEMA = "paddle_tpu-tune1"


@dataclass(frozen=True)
class KernelBlocks:
    """One kernel's tunable block parameters and the flags that override
    them. `auto` is each flag's means-unset sentinel (0 for the 0=auto
    knobs); None means the flag's default is a REAL value (e.g.
    serving_page_size=16) and an override is detected by explicit-set
    tracking (`flags.flag_explicit`) instead."""

    params: tuple
    flags: tuple
    auto: tuple
    lowering_flags: tuple = ()   # extra flags folded into the cache key
    # fused_ce's historical contract: ONE chunk flag set is a valid
    # override, the other fills from the tier below. Flash keeps the
    # strict both-or-neither contract (partial overrides warn + ignore).
    partial_ok: bool = False


# The five Pallas kernel families (six entries: flash fwd/bwd tile
# independently). tests/test_tuning.py grep-guards that each kernel file
# resolves through here — a sixth copy of pick logic fails tier-1.
KERNELS: dict[str, KernelBlocks] = {
    "flash_fwd": KernelBlocks(
        ("block_q", "block_k"), ("flash_block_q", "flash_block_k"), (0, 0),
        ("flash_segment_block_skip",)),
    "flash_bwd": KernelBlocks(
        ("block_q", "block_k"),
        ("flash_bwd_block_q", "flash_bwd_block_k"), (0, 0),
        ("flash_segment_block_skip",)),
    "grouped_matmul": KernelBlocks(
        ("block_rows",), ("moe_block_rows",), (0,)),
    "fused_ce": KernelBlocks(
        ("chunk_tokens", "chunk_vocab"),
        ("fused_ce_chunk_tokens", "fused_ce_chunk_vocab"), (0, 0),
        ("fused_ce_variant",), partial_ok=True),
    "rmsnorm": KernelBlocks(
        ("block_rows",), ("rmsnorm_block_rows",), (0,)),
    "paged_attention": KernelBlocks(
        ("page_size",), ("serving_page_size",), (None,)),
}


@dataclass(frozen=True)
class Resolution:
    """What ran and why: `values` maps the kernel's param names to the
    chosen ints; `provenance` is one of flag|tuned|default|trial|caller;
    `source` is the human detail ('FLAGS_flash_block_q/k', the cache key,
    'heuristic', ...); `derived` holds what the kernel worked out FROM the
    resolution and its shapes (fused_ce's backward depth): shown here, but
    no `params` entry, so no flag, no tuning-cache field, no search axis."""

    kernel: str
    values: dict
    provenance: str
    source: str
    derived: dict = field(default_factory=dict)

    def as_tuple(self) -> tuple:
        return tuple(self.values[p] for p in KERNELS[self.kernel].params)


_STATE = threading.local()
_last: dict[str, Resolution] = {}
_counters_lock = threading.Lock()
_counters = {
    "resolutions_flag": 0, "resolutions_tuned": 0,
    "resolutions_default": 0, "resolutions_trial": 0,
    "autotune_trials": 0, "tuning_cache_rejects": 0,
}
_warned_once: set = set()


def bump_counter(name: str, n: int = 1):
    with _counters_lock:
        _counters[name] = _counters.get(name, 0) + n
    from paddle_tpu.tuning import ensure_metrics_collector

    ensure_metrics_collector()


def tuning_counters() -> dict:
    with _counters_lock:
        return dict(_counters)


def _warn_once(key: str, msg: str):
    if key in _warned_once:
        return
    _warned_once.add(key)
    warnings.warn(msg)


def last_resolution(kernel: str) -> Resolution | None:
    """The most recent Resolution recorded for `kernel` in this process —
    the provenance assertion surface of the acceptance criteria."""
    return _last.get(kernel)


def note_derived(res: Resolution, **derived) -> Resolution:
    """Record `res` with `derived` attached as its kernel's last resolution
    (a caller-supplied block never went through `resolve_blocks`, so this
    is also where provenance 'caller' gets recorded)."""
    res = replace(res, derived=derived)
    _last[res.kernel] = res
    return res


def trial_blocks(kernel: str, values: dict):
    """Context manager forcing `kernel` to resolve to `values` with
    provenance 'trial' on this thread — how the autotuner times a
    candidate through the kernel's real public entry point."""
    from contextlib import contextmanager

    @contextmanager
    def ctx():
        trials = getattr(_STATE, "trial", None)
        if trials is None:
            trials = _STATE.trial = {}
        prev = trials.get(kernel)
        trials[kernel] = dict(values)
        try:
            yield
        finally:
            if prev is None:
                trials.pop(kernel, None)
            else:
                trials[kernel] = prev

    return ctx()


def _platform() -> str:
    import jax

    try:
        return jax.default_backend()
    except Exception:  # pragma: no cover - backend init failure
        return "unknown"


def cache_key(kernel: str, geometry: dict, dtype: str = "",
              platform: str | None = None) -> str:
    """Tuning-cache key: kernel | canonical geometry | dtype | platform |
    lowering-relevant flag values (docs/autotuning.md#cache-key-anatomy)."""
    from paddle_tpu.core.flags import flag

    spec = KERNELS[kernel]
    geom = ",".join(f"{k}={geometry[k]}" for k in sorted(geometry))
    lf = ",".join(f"{f}={flag(f)}" for f in spec.lowering_flags)
    return "|".join([kernel, geom, str(dtype or ""),
                     platform or _platform(), lf])


# ---------------------------------------------------------------------------
# tuning cache (JSON, schema paddle_tpu-tune1)
# ---------------------------------------------------------------------------


class TuningCache:
    """The JSON block-shape cache. One file per directory
    (`tuning_cache.json`); entries are {cache_key: {"values": {...},
    "ms": best_trial_ms, "trials": n, "jax": version}}. Loading a file
    with an unknown schema raises with a re-tune pointer (the
    paddle_tpu-npz1 legacy-rejection convention) — dispatch-time callers
    catch that, warn once, and fall through to the heuristic default."""

    FILENAME = "tuning_cache.json"

    def __init__(self, cache_dir: str):
        self.dir = str(cache_dir)
        self.path = os.path.join(self.dir, self.FILENAME)
        self.entries: dict[str, dict] = {}

    @classmethod
    def load(cls, cache_dir: str) -> "TuningCache":
        self = cls(cache_dir)
        if not os.path.exists(self.path):
            return self
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                blob = json.load(f)
        except (json.JSONDecodeError, UnicodeDecodeError, OSError) as e:
            raise ValueError(
                f"{self.path!r}: unreadable tuning cache ({e}); delete the "
                f"file and re-run the autotuner (FLAGS_autotune=search) to "
                f"regenerate it") from e
        fmt = blob.get("format") if isinstance(blob, dict) else None
        if fmt != TUNING_SCHEMA:
            raise ValueError(
                f"{self.path!r}: unsupported tuning-cache format {fmt!r}; "
                f"expected {TUNING_SCHEMA!r} — stale schema entries are "
                f"never reinterpreted (block meanings may have changed); "
                f"delete the file and re-run the autotuner "
                f"(FLAGS_autotune=search) to re-tune")
        self.entries = dict(blob.get("entries", {}))
        return self

    def lookup(self, key: str) -> dict | None:
        e = self.entries.get(key)
        if not isinstance(e, dict) or "values" not in e:
            return None
        return {k: int(v) for k, v in e["values"].items()}

    def store(self, key: str, values: dict, ms: float | None = None,
              trials: int = 0):
        import jax

        self.entries[key] = {
            "values": {k: int(v) for k, v in values.items()},
            "ms": None if ms is None else round(float(ms), 4),
            "trials": int(trials),
            "jax": jax.__version__,
        }
        self.save()

    def save(self):
        os.makedirs(self.dir, exist_ok=True)
        import jax

        blob = {"format": TUNING_SCHEMA, "jax": jax.__version__,
                "entries": self.entries}
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(blob, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)


_cache_memo: dict[str, tuple[float, TuningCache]] = {}
_cache_lock = threading.Lock()


def _cache_for(cache_dir: str) -> TuningCache | None:
    """mtime-checked per-directory cache instance; schema rejection
    degrades to 'no cache' with a one-time warning (dispatch must never
    crash on a bad cache file)."""
    try:
        mtime = os.stat(os.path.join(cache_dir,
                                     TuningCache.FILENAME)).st_mtime
    except OSError:
        mtime = -1.0
    with _cache_lock:
        hit = _cache_memo.get(cache_dir)
        if hit is not None and hit[0] == mtime:
            return hit[1]
    try:
        cache = TuningCache.load(cache_dir)
    except ValueError as e:
        bump_counter("tuning_cache_rejects")
        _warn_once(f"tune-reject:{cache_dir}", str(e))
        from paddle_tpu.observability import events as _events

        _events.emit("tuning", "cache_reject", severity="warn",
                     dir=cache_dir, error=str(e)[:200])
        cache = None
    with _cache_lock:
        _cache_memo[cache_dir] = (mtime, cache)
    return cache


# ---------------------------------------------------------------------------
# the resolver
# ---------------------------------------------------------------------------


def _flag_overrides(spec: KernelBlocks):
    """([(param, value)], n_set) — which override flags the user set."""
    from paddle_tpu.core.flags import flag, flag_explicit

    out, n_set = [], 0
    for p, f, auto in zip(spec.params, spec.flags, spec.auto):
        v = flag(f)
        is_set = (flag_explicit(f) if auto is None else v != auto)
        out.append((p, int(v) if is_set else None))
        n_set += bool(is_set)
    return out, n_set


def _record(res: Resolution) -> Resolution:
    _last[res.kernel] = res
    bump_counter(f"resolutions_{res.provenance}")
    return res


def resolve_blocks(kernel: str, geometry: dict, *, dtype: str = "",
                   default=None, validate=None) -> Resolution:
    """Resolve `kernel`'s block parameters for `geometry`.

    `default` maps geometry -> dict (or tuple in param order) and supplies
    the heuristic tier; `validate(values, geometry)` may raise ValueError
    — a flag override that fails validation propagates (the caller's
    existing error contract), a tuned entry that fails it degrades to the
    default with a one-time warning."""
    spec = KERNELS[kernel]

    trials = getattr(_STATE, "trial", None)
    if trials and kernel in trials:
        return _record(Resolution(kernel, dict(trials[kernel]), "trial",
                                  "autotune trial override"))

    overrides, n_set = _flag_overrides(spec)
    flag_names = " and ".join(f"FLAGS_{f}" for f in spec.flags)
    if n_set == len(spec.params):
        values = {p: v for p, v in overrides}
        if validate is not None:
            validate(values, geometry)
        return _record(Resolution(kernel, values, "flag", flag_names))

    res = _resolve_below_flags(kernel, spec, geometry, dtype, default,
                               validate)
    if 0 < n_set < len(spec.params):
        if spec.partial_ok:
            values = {p: (v if v is not None else res.values[p])
                      for p, v in overrides}
            if validate is not None:
                validate(values, geometry)
            set_names = ", ".join(
                f"FLAGS_{f}" for (p, v), f in zip(overrides, spec.flags)
                if v is not None)
            return _record(Resolution(
                kernel, values, "flag",
                f"{set_names} (unset params from {res.provenance})"))
        # the deduplicated partial-override branch (previously copied in
        # flash fwd AND bwd): name the flag pair AND what actually ran
        warnings.warn(
            f"{kernel}: set BOTH {flag_names} for an explicit block "
            f"override; partial override ignored — using {res.provenance} "
            f"blocks {res.values} ({res.source})")
    return res


def _resolve_below_flags(kernel, spec, geometry, dtype, default, validate):
    from paddle_tpu.core.flags import flag

    mode = str(flag("autotune"))
    if mode not in ("off", "load", "search"):
        _warn_once(f"autotune-mode:{mode}",
                   f"FLAGS_autotune={mode!r} is not one of off|load|search; "
                   f"treating as 'off'")
        mode = "off"
    cache_dir = str(flag("tuning_cache_dir"))
    if mode != "off" and cache_dir:
        key = cache_key(kernel, geometry, dtype)
        cache = _cache_for(cache_dir)
        tuned = cache.lookup(key) if cache is not None else None
        if tuned is not None and set(tuned) == set(spec.params):
            try:
                if validate is not None:
                    validate(tuned, geometry)
            except ValueError as e:
                _warn_once(f"tuned-invalid:{key}",
                           f"{kernel}: tuned blocks {tuned} from {key!r} "
                           f"fail validation ({e}); falling back to the "
                           f"heuristic default — re-tune with "
                           f"FLAGS_autotune=search")
            else:
                return _record(Resolution(kernel, tuned, "tuned", key))
        if mode == "search" and cache is not None:
            searching = getattr(_STATE, "searching", None)
            if searching is None:
                searching = _STATE.searching = set()
            if kernel not in searching:
                searching.add(kernel)
                try:
                    from paddle_tpu.tuning.autotune import autotune_kernel

                    won = autotune_kernel(kernel, geometry, dtype=dtype,
                                          cache=cache)
                    if won is not None:
                        return _record(Resolution(kernel, won["values"],
                                                  "tuned", key))
                except Exception as e:  # search must never break dispatch
                    _warn_once(f"search-fail:{key}",
                               f"{kernel}: autotune search failed ({e}); "
                               f"falling back to the heuristic default")
                finally:
                    searching.discard(kernel)

    d = default(geometry) if callable(default) else default
    if d is None:
        raise ValueError(f"{kernel}: no default block heuristic supplied "
                         f"and no flag/tuned value available")
    if not isinstance(d, dict):
        d = dict(zip(spec.params, d))
    return _record(Resolution(kernel, {p: int(v) for p, v in d.items()},
                              "default", "heuristic"))
