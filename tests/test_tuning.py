"""Tier-1 tuning plane: the shared block-size resolver (precedence +
provenance), the JSON tuning cache (round-trip, stale-schema rejection
mirroring the paddle_tpu-npz1 convention), the CPU-interpret autotuner
end-to-end (search -> persist -> load -> dispatch), and the grep guards
that keep all five Pallas kernels resolving through ONE helper and the
package on ONE compile cache (JAX's persistent one)."""
import json
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.flags import _REGISTRY, flag, set_flags
from paddle_tpu.tuning import (KERNELS, TuningCache, cache_key,
                               last_resolution, resolve_blocks, trial_blocks,
                               tuning_counters)
from paddle_tpu.tuning.blocks import _last

TUNE_FLAGS = ("autotune", "tuning_cache_dir", "flash_block_q",
              "flash_block_k", "flash_bwd_block_q", "flash_bwd_block_k",
              "moe_block_rows", "rmsnorm_block_rows",
              "fused_ce_chunk_tokens", "fused_ce_chunk_vocab",
              "serving_page_size")


@pytest.fixture(autouse=True)
def _flags_hygiene():
    """set_flags marks a flag explicitly-set forever (that IS the override
    signal for real-default flags like serving_page_size), so tests must
    restore the explicit bit along with the value."""
    saved = {n: (_REGISTRY[n].value, _REGISTRY[n].explicit)
             for n in TUNE_FLAGS}
    yield
    for n, (v, ex) in saved.items():
        _REGISTRY[n].value = v
        _REGISTRY[n].explicit = ex
    _last.clear()


def _resolve_rmsnorm(**geom):
    g = {"rows": 512, "d": 128}
    g.update(geom)
    return resolve_blocks("rmsnorm", g, default=lambda _: (256,))


class TestResolvePrecedence:
    def test_default_tier(self):
        res = _resolve_rmsnorm()
        assert res.provenance == "default"
        assert res.values == {"block_rows": 256}
        assert last_resolution("rmsnorm") is res

    def test_flag_override_wins(self, tmp_path):
        cache = TuningCache(str(tmp_path))
        cache.store(cache_key("rmsnorm", {"rows": 512, "d": 128}),
                    {"block_rows": 64})
        set_flags({"rmsnorm_block_rows": 32, "autotune": "load",
                   "tuning_cache_dir": str(tmp_path)})
        res = _resolve_rmsnorm()
        assert res.provenance == "flag"
        assert res.values == {"block_rows": 32}
        assert "FLAGS_rmsnorm_block_rows" in res.source

    def test_tuned_tier_between_flag_and_default(self, tmp_path):
        key = cache_key("rmsnorm", {"rows": 512, "d": 128})
        TuningCache(str(tmp_path)).store(key, {"block_rows": 64})
        set_flags({"autotune": "load", "tuning_cache_dir": str(tmp_path)})
        res = _resolve_rmsnorm()
        assert res.provenance == "tuned"
        assert res.values == {"block_rows": 64}
        assert res.source == key  # provenance names the cache entry

    def test_autotune_off_ignores_cache(self, tmp_path):
        TuningCache(str(tmp_path)).store(
            cache_key("rmsnorm", {"rows": 512, "d": 128}),
            {"block_rows": 64})
        set_flags({"autotune": "off", "tuning_cache_dir": str(tmp_path)})
        assert _resolve_rmsnorm().provenance == "default"

    def test_trial_tier_beats_flags(self):
        set_flags({"rmsnorm_block_rows": 32})
        with trial_blocks("rmsnorm", {"block_rows": 8}):
            res = _resolve_rmsnorm()
            assert res.provenance == "trial"
            assert res.values == {"block_rows": 8}
        assert _resolve_rmsnorm().provenance == "flag"

    def test_partial_override_warns_with_pair_and_provenance(self):
        """The deduplicated flash branch: ONE of the pair set must warn
        naming BOTH flags AND what actually ran, then be ignored."""
        set_flags({"flash_block_q": 256})  # flash_block_k left auto
        with pytest.warns(UserWarning,
                          match="FLAGS_flash_block_q and FLAGS_flash_block_k"
                          ) as rec:
            res = resolve_blocks("flash_fwd", {"seq_len": 1024},
                                 default=lambda g: (512, 1024))
        assert res.provenance == "default"
        assert res.values == {"block_q": 512, "block_k": 1024}
        assert "partial override ignored" in str(rec[0].message)
        assert "default" in str(rec[0].message)  # the fallback provenance

    def test_fused_ce_partial_fills_from_lower_tier(self):
        """fused_ce's historical contract: one chunk flag alone IS a valid
        override; the other parameter fills from the tier below."""
        set_flags({"fused_ce_chunk_tokens": 128})
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # no partial-override warning
            res = resolve_blocks("fused_ce",
                                 {"n_tokens": 4096, "vocab": 32000},
                                 default=lambda g: (1024, 32000))
        assert res.provenance == "flag"
        assert res.values == {"chunk_tokens": 128, "chunk_vocab": 32000}
        assert "FLAGS_fused_ce_chunk_tokens" in res.source

    def test_flag_failing_validation_raises(self):
        def validate(values, geometry):
            if geometry["seq_len"] % values["block_q"]:
                raise ValueError("non-divisor")

        set_flags({"flash_block_q": 384, "flash_block_k": 384})
        with pytest.raises(ValueError, match="non-divisor"):
            resolve_blocks("flash_fwd", {"seq_len": 1024},
                           default=lambda g: (512, 1024), validate=validate)

    def test_tuned_failing_validation_degrades(self, tmp_path):
        key = cache_key("rmsnorm", {"rows": 512, "d": 128})
        TuningCache(str(tmp_path)).store(key, {"block_rows": 7})
        set_flags({"autotune": "load", "tuning_cache_dir": str(tmp_path)})

        def validate(values, geometry):
            if values["block_rows"] == 7:
                raise ValueError("bad tuned value")

        with pytest.warns(UserWarning, match="re-tune"):
            res = resolve_blocks("rmsnorm", {"rows": 512, "d": 128},
                                 default=lambda _: (256,),
                                 validate=validate)
        assert res.provenance == "default"

    def test_page_size_explicit_set_detection(self):
        """serving_page_size has a REAL default (16), no 0-sentinel: only
        an explicit set_flags/env set counts as a flag override."""
        res = resolve_blocks("paged_attention",
                             {"num_kv_heads": 4, "head_dim": 64,
                              "max_seq_len": 256},
                             default=lambda g: (16,))
        assert res.provenance == "default"
        set_flags({"serving_page_size": 8})
        res = resolve_blocks("paged_attention",
                             {"num_kv_heads": 4, "head_dim": 64,
                              "max_seq_len": 256},
                             default=lambda g: (16,))
        assert res.provenance == "flag"
        assert res.values == {"page_size": 8}

    def test_resolution_counters_by_provenance(self):
        before = tuning_counters()
        _resolve_rmsnorm()
        set_flags({"rmsnorm_block_rows": 32})
        _resolve_rmsnorm()
        after = tuning_counters()
        assert after["resolutions_default"] == before["resolutions_default"] + 1
        assert after["resolutions_flag"] == before["resolutions_flag"] + 1


class TestTuningCache:
    def test_round_trip(self, tmp_path):
        key = cache_key("rmsnorm", {"rows": 512, "d": 128},
                        platform="cpu")
        cache = TuningCache(str(tmp_path))
        cache.store(key, {"block_rows": 64}, ms=1.25, trials=4)
        re = TuningCache.load(str(tmp_path))
        assert re.lookup(key) == {"block_rows": 64}
        entry = re.entries[key]
        assert entry["ms"] == 1.25 and entry["trials"] == 4
        assert entry["jax"] == jax.__version__

    def test_key_anatomy(self):
        """kernel | sorted geometry | dtype | platform | lowering flags —
        every axis must move the key."""
        base = cache_key("flash_fwd", {"seq_len": 1024}, "bf16", "tpu")
        assert base == ("flash_fwd|seq_len=1024|bf16|tpu|"
                        "flash_segment_block_skip=True")
        assert cache_key("flash_fwd", {"seq_len": 2048}, "bf16", "tpu") != base
        assert cache_key("flash_fwd", {"seq_len": 1024}, "f32", "tpu") != base
        assert cache_key("flash_fwd", {"seq_len": 1024}, "bf16", "cpu") != base
        assert cache_key("flash_bwd", {"seq_len": 1024}, "bf16", "tpu") != base
        set_flags({"flash_segment_block_skip": False})
        try:
            assert cache_key("flash_fwd", {"seq_len": 1024}, "bf16",
                             "tpu") != base
        finally:
            set_flags({"flash_segment_block_skip": True})

    def test_stale_schema_rejected_with_retune_pointer(self, tmp_path):
        """paddle_tpu-npz1 convention: an unknown schema is REJECTED with
        a pointer at the fix, never silently reinterpreted."""
        path = tmp_path / TuningCache.FILENAME
        path.write_text(json.dumps({"format": "paddle_tpu-tune0",
                                    "entries": {"k": {"values": {"b": 1}}}}))
        with pytest.raises(ValueError) as ei:
            TuningCache.load(str(tmp_path))
        msg = str(ei.value)
        assert "paddle_tpu-tune0" in msg and "paddle_tpu-tune1" in msg
        assert "FLAGS_autotune=search" in msg  # the re-tune pointer

    def test_corrupt_json_rejected(self, tmp_path):
        (tmp_path / TuningCache.FILENAME).write_text("{not json")
        with pytest.raises(ValueError, match="re-run the autotuner"):
            TuningCache.load(str(tmp_path))

    def test_resolver_degrades_on_stale_cache(self, tmp_path):
        """Dispatch never crashes on a bad cache file: one warning, one
        reject counter, heuristic blocks."""
        (tmp_path / TuningCache.FILENAME).write_text(
            json.dumps({"format": "paddle_tpu-tune0", "entries": {}}))
        set_flags({"autotune": "load", "tuning_cache_dir": str(tmp_path)})
        before = tuning_counters()["tuning_cache_rejects"]
        with pytest.warns(UserWarning, match="FLAGS_autotune=search"):
            res = _resolve_rmsnorm()
        assert res.provenance == "default"
        assert tuning_counters()["tuning_cache_rejects"] == before + 1
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            assert _resolve_rmsnorm().provenance == "default"  # warned ONCE


class TestAutotuneEndToEnd:
    def test_search_persist_load_dispatch(self, tmp_path):
        """The acceptance loop on CPU interpret: FLAGS_autotune=search
        times the rmsnorm row-block lattice through the kernel's real
        entry point, persists the winner, and a load-mode resolve consumes
        it with provenance 'tuned'."""
        from paddle_tpu.ops.pallas.rmsnorm_kernel import rmsnorm

        set_flags({"autotune": "search", "tuning_cache_dir": str(tmp_path)})
        trials_before = tuning_counters()["autotune_trials"]
        x = jnp.arange(64 * 128, dtype=jnp.float32).reshape(64, 128) / 999.0
        w = jnp.ones((128,), jnp.float32)
        y = rmsnorm(x, w)
        res = last_resolution("rmsnorm")
        assert res is not None and res.provenance == "tuned"
        assert tuning_counters()["autotune_trials"] > trials_before
        # the winner persisted with the current schema
        blob = json.loads((tmp_path / TuningCache.FILENAME).read_text())
        assert blob["format"] == "paddle_tpu-tune1"
        key = cache_key("rmsnorm", {"rows": 64, "d": 128})
        assert blob["entries"][key]["values"] == dict(res.values)
        # numerics match the composite reference
        ref = (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)
        # a fresh load-mode process-alike: resolve-only, zero new trials
        _last.clear()
        set_flags({"autotune": "load"})
        trials_before = tuning_counters()["autotune_trials"]
        rmsnorm(x, w)
        res2 = last_resolution("rmsnorm")
        assert res2.provenance == "tuned"
        assert res2.values == res.values
        assert tuning_counters()["autotune_trials"] == trials_before
        # journal carries the search record
        from paddle_tpu.observability import events
        recs = events.journal().recent(component="tuning", n=50)
        assert any(r["event"] == "autotune" for r in recs)

    def test_candidate_lattices_are_legal(self):
        from paddle_tpu.tuning.autotune import (VMEM_BUDGET_BYTES,
                                                candidate_blocks)

        for c in candidate_blocks("flash_fwd", {"seq_len": 2048}):
            assert 2048 % c["block_q"] == 0 and 2048 % c["block_k"] == 0
        for c in candidate_blocks("grouped_matmul",
                                  {"n_rows": 512, "num_groups": 4}):
            assert 512 % c["block_rows"] == 0
        for c in candidate_blocks("fused_ce",
                                  {"n_tokens": 4096, "vocab": 32000}):
            assert c["chunk_tokens"] <= 4096 and c["chunk_vocab"] <= 32000
            assert c["chunk_tokens"] * c["chunk_vocab"] * 4 \
                <= VMEM_BUDGET_BYTES

    def test_metrics_collector_exposes_tuning_counters(self):
        from paddle_tpu.observability import metrics as obs_metrics
        from paddle_tpu.tuning import ensure_metrics_collector

        _resolve_rmsnorm()
        ensure_metrics_collector()
        snap = obs_metrics.registry().snapshot()
        for name in ("autotune_trials_total", "tuning_cache_rejects_total",
                     "block_resolutions_total"):
            assert name in snap, name
        provs = {s["labels"].get("provenance")
                 for s in snap["block_resolutions_total"]["samples"]}
        assert {"flag", "tuned", "default", "trial"} <= provs


KERNEL_FILES = {
    "flash_attention.py": ("flash_fwd", "flash_bwd"),
    "grouped_matmul.py": ("grouped_matmul",),
    "fused_ce.py": ("fused_ce",),
    "rmsnorm_kernel.py": ("rmsnorm",),
    "paged_attention.py": ("paged_attention",),
}


class TestSharedResolverGuard:
    """Tier-1 grep guard (ISSUE 20 satellite): every Pallas kernel's block
    pick goes through tuning.blocks.resolve_blocks — a sixth copy of the
    flag/warn pick logic fails here."""

    def _pallas_dir(self):
        import paddle_tpu.ops.pallas as p

        return os.path.dirname(os.path.abspath(p.__file__))

    def test_all_kernels_resolve_through_the_shared_helper(self):
        d = self._pallas_dir()
        for fname, kernels in KERNEL_FILES.items():
            if fname == "paged_attention.py":
                # the page size is resolved ONCE at engine construction
                # (serving/engine.py), not per kernel call
                import paddle_tpu.serving.engine as eng

                src = open(eng.__file__.replace(".pyc", ".py")).read()
            else:
                src = open(os.path.join(d, fname)).read()
            assert "resolve_blocks" in src, (
                f"{fname}: block pick no longer routed through "
                f"tuning.blocks.resolve_blocks")
            for k in kernels:
                assert k in KERNELS

    def test_partial_override_branch_lives_only_in_blocks(self):
        """The deduplicated warn branch must not grow copies again."""
        import paddle_tpu

        root = os.path.dirname(os.path.abspath(paddle_tpu.__file__))
        offenders = []
        for dirpath, _, files in os.walk(root):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                if "partial override ignored" in open(path).read():
                    offenders.append(os.path.relpath(path, root))
        assert offenders == [os.path.join("tuning", "blocks.py")], offenders

    def test_the_package_names_no_second_compile_cache(self):
        """PR 30 deleted the serialized-executable cache beside JAX's
        persistent one (`core/compile_cache.py`): no file of the package
        may name it again without a cell that shows it to win."""
        import paddle_tpu

        root = os.path.dirname(os.path.abspath(paddle_tpu.__file__))
        offenders = []
        for dirpath, _, files in os.walk(root):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                src = open(path).read()
                if "program_cache" in src or "AotProgram" in src:
                    offenders.append(os.path.relpath(path, root))
        assert offenders == [], offenders

    def test_kernel_registry_covers_the_contract(self):
        assert set(KERNELS) == {"flash_fwd", "flash_bwd", "grouped_matmul",
                                "fused_ce", "rmsnorm", "paged_attention"}
        for name, spec in KERNELS.items():
            assert len(spec.params) == len(spec.flags) == len(spec.auto)
            for f in spec.flags + spec.lowering_flags:
                flag(f)  # registered
