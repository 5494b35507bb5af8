"""ZeRO-3 sharded weights + gather-ahead scan loop (ISSUE 6).

Covers: loss parity of the sharded-weights scan (gather-ahead AND
gather-at-start) against the replicated path, exact parameter-memory
sharding, the HLO CI guard (per-iteration all-gathers in the compiled scan
body, NO up-front full-stack gather), sharded<->replicated state-dict
round-trips with optimizer state and bit-parity resume, per-stage sharding
composition with the pipelined runtimes, the safe npz+JSON deployment
container, and the per-(reason, shape) fallback-warning dedup."""
import re
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import build_mesh, set_mesh
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.parallel import CompiledTrainStep

ZD = 8  # the virtual device count conftest pins


@pytest.fixture(autouse=True)
def _mesh_teardown():
    yield
    set_mesh(None)


def _model(n_layers=4, **over):
    paddle.seed(0)
    cfg = llama_tiny_config(num_hidden_layers=n_layers, **over)
    return cfg, LlamaForCausalLM(cfg)


def _data(cfg, batch=8, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    labels = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
    return ids, labels


def _step(model, optimizer=None, **kw):
    opt = optimizer or paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters())
    return CompiledTrainStep(model, lambda out, lab: out, optimizer=opt,
                             **kw)


def _run(step, ids, labels, n):
    return [float(step(ids, labels, labels)) for _ in range(n)]


def _per_device_param_bytes(step):
    return sum(v.addressable_shards[0].data.nbytes
               for v in step._param_vals)


def _total_param_bytes(step):
    return sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in step._param_vals)


@pytest.fixture(scope="module")
def ref_losses():
    """4 replicated-scan reference losses on the sharding mesh (the zero3
    arms must match these to <=1e-5 rel; in practice bit-identically)."""
    set_mesh(None)
    build_mesh({"sharding": ZD})
    cfg, m = _model(4)
    ids, labels = _data(cfg)
    step = _step(m, scan_layers=True)
    losses = _run(step, ids, labels, 4)
    set_mesh(None)
    return cfg, losses


class TestZero3Parity:
    @pytest.mark.parametrize("mode", ["ahead", "start"])
    def test_losses_match_replicated(self, ref_losses, mode):
        cfg, ref = ref_losses
        build_mesh({"sharding": ZD})
        _, m = _model(4)
        step = _step(m, scan_layers=True, zero_axis="sharding",
                     zero_stage=3, zero3_gather=mode)
        assert step._zero3_scan_info is not None
        assert step._zero3_scan_info.mode == mode
        ids, labels = _data(cfg)
        losses = _run(step, ids, labels, 4)
        np.testing.assert_allclose(losses, ref, rtol=1e-5)
        # params persist reduce-scattered: per-device bytes = total/shard
        assert (_per_device_param_bytes(step)
                <= _total_param_bytes(step) // ZD + 4096)

    def test_dp_sharding_mixed_mesh(self, ref_losses):
        """zero3 over 'sharding' composes with a dp axis (batch sharded over
        both, weights over 'sharding' only)."""
        cfg, _ = ref_losses
        build_mesh({"dp": 2, "sharding": 4})
        _, m_ref = _model(4)
        ids, labels = _data(cfg)
        ref = _run(_step(m_ref, scan_layers=True), ids, labels, 3)
        set_mesh(None)
        build_mesh({"dp": 2, "sharding": 4})
        _, m = _model(4)
        step = _step(m, scan_layers=True, zero_axis="sharding", zero_stage=3)
        losses = _run(step, ids, labels, 3)
        np.testing.assert_allclose(losses, ref, rtol=1e-5)

    def test_mp_sharding_mixed_mesh(self, ref_losses):
        """zero3 composes with tensor parallelism: mp columns keep their mp
        dims (per-column gathers), the rest shard over 'sharding' — and the
        stacked LAYER dim is never chosen for state sharding (it would make
        every scan iteration's state slice cross-device)."""
        cfg, _ = ref_losses
        build_mesh({"sharding": 4, "mp": 2})
        _, m_ref = _model(4)
        ids, labels = _data(cfg)
        ref = _run(_step(m_ref, scan_layers=True), ids, labels, 3)
        set_mesh(None)
        build_mesh({"sharding": 4, "mp": 2})
        _, m = _model(4)
        step = _step(m, scan_layers=True, zero_axis="sharding", zero_stage=3)
        losses = _run(step, ids, labels, 3)
        np.testing.assert_allclose(losses, ref, rtol=1e-5)
        n_outer = len(step._outer_params)
        for st in step._opt_states[n_outer:]:
            for v in st.values():
                spec = getattr(v.sharding, "spec", None)
                if spec and len(spec) > 0:
                    assert spec[0] != "sharding", \
                        "optimizer state sharded on the stacked layer dim"

    def test_interior_remat_policies_rejected(self):
        build_mesh({"sharding": ZD})
        _, m = _model(4)
        with pytest.raises(ValueError, match="sharded stack"):
            _step(m, scan_layers=True, zero_axis="sharding", zero_stage=3,
                  remat="save_dots")

    def test_unknown_gather_mode_rejected(self):
        build_mesh({"sharding": ZD})
        _, m = _model(4)
        with pytest.raises(ValueError, match="zero3 gather mode"):
            _step(m, scan_layers=True, zero_axis="sharding", zero_stage=3,
                  zero3_gather="sometimes")

    def test_typo_axis_warns_instead_of_silent_replicated(self):
        """A zero_axis that names NO mesh axis must not silently train
        replicated at Z x the provisioned parameter memory."""
        build_mesh({"sharding": ZD})
        _, m = _model(4)
        with pytest.warns(UserWarning, match="not a mesh axis"):
            step = _step(m, scan_layers=True, zero_axis="shard",
                         zero_stage=3)
        assert step._zero3_scan_info is None


def _compiled(step, ids):
    step._build()
    placed, _ = step._spec_cache.place([ids._value] * 3)
    lowered = step._jitted.lower(
        step._param_vals, step._opt_states, tuple(placed),
        jax.random.key(0), jnp.asarray(1e-3, jnp.float32),
        jnp.asarray(1, jnp.int32))
    return lowered.compile()


def _compiled_text(step, ids):
    return _compiled(step, ids).as_text()


def _all_gather_result_shapes(txt):
    """Leading-dims lists of every all-gather RESULT in optimized HLO."""
    return [
        [int(d) for d in m.group(1).split(",")]
        for m in re.finditer(r"= \w+\[([0-9,]+)\][^=]* all-gather\(", txt)]


class TestHLOGuard:
    """CI guard (tier-1, CPU): the compiled zero3 scan body must gather
    per iteration and must NOT gather the whole parameter stack up front —
    the same inspection style as the PR-2 depth-independence guard."""

    L = 4

    def _text(self, mode):
        build_mesh({"sharding": ZD})
        cfg, m = _model(self.L)
        step = _step(m, scan_layers=True, zero_axis="sharding",
                     zero_stage=3, zero3_gather=mode)
        ids, _ = _data(cfg)
        txt = _compiled_text(step, ids)
        set_mesh(None)
        return txt, step

    def test_gather_ahead_structure(self):
        txt, step = self._text("ahead")
        shapes = _all_gather_result_shapes(txt)
        assert shapes, "no all-gathers in the compiled zero3 step"
        # the stacked decoder columns are never gathered whole: no all-gather
        # result carries the leading layer dim
        n_outer = len(step._outer_params)
        stack_elems = {int(np.prod(v.shape))
                       for v in step._param_vals[n_outer:]}
        for dims in shapes:
            assert dims[0] != self.L or int(np.prod(dims)) not in stack_elems, \
                f"up-front full-stack all-gather found: {dims}"
        # the loop stays a loop (depth-independent program), with the
        # gathers inside it
        assert "while" in txt

    def test_gather_at_start_detected(self):
        """Detector sanity: the overlap-free baseline DOES gather whole
        stacked columns, and the guard's inspection sees it."""
        txt, step = self._text("start")
        shapes = _all_gather_result_shapes(txt)
        n_outer = len(step._outer_params)
        stack_elems = {int(np.prod(v.shape))
                       for v in step._param_vals[n_outer:]}
        assert any(dims[0] == self.L and int(np.prod(dims)) in stack_elems
                   for dims in shapes), \
            "gather-at-start baseline shows no full-stack all-gather"


    def test_gather_ahead_keeps_two_layers_live_not_the_stack(self):
        """XLA's memory analysis of the two compiled steps (a static count,
        no clock): gather-at-start holds the whole gathered stack,
        gather-ahead two layers of it, so the peak gap accounts for the
        (L - 2) layers gather-ahead never materialises (at least half of
        their bytes: the compiler may overlap other temporaries)."""
        L = 8
        peak, layer_bytes = {}, None
        for mode in ("start", "ahead"):
            build_mesh({"sharding": ZD})
            # weights outweigh the activations of an 8 x 16 batch
            cfg, m = _model(L, hidden_size=256, intermediate_size=512)
            step = _step(m, scan_layers=True, zero_axis="sharding",
                         zero_stage=3, zero3_gather=mode)
            ids, _ = _data(cfg)
            ma = _compiled(step, ids).memory_analysis()
            peak[mode] = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                          + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
            n_outer = len(step._outer_params)
            layer_bytes = sum(int(np.prod(v.shape[1:])) * v.dtype.itemsize
                              for v in step._param_vals[n_outer:])
            set_mesh(None)
        assert peak["start"] - peak["ahead"] >= 0.5 * (L - 2) * layer_bytes, (
            peak, layer_bytes)


class TestStateDictRoundTrip:
    """Satellite: save under zero_axis sharding, restore replicated (and
    vice versa), optimizer state included, bit-parity losses after resume."""

    def _checkpoint(self, step, model, optimizer):
        step.sync_params_to_model()
        step.sync_states_to_optimizer()
        sd = {k: np.asarray(v._value) for k, v in model.state_dict().items()}
        return sd, optimizer.state_dict()

    def _restore(self, cfg, sd, opt_sd):
        _, m = _model(4)
        missing, unexpected = m.set_state_dict(sd)
        assert not missing and not unexpected
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        opt.set_state_dict(opt_sd)
        return m, opt

    def test_sharded_to_replicated(self, ref_losses):
        cfg, ref = ref_losses
        build_mesh({"sharding": ZD})
        _, m = _model(4)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        step = _step(m, optimizer=opt, scan_layers=True,
                     zero_axis="sharding", zero_stage=3)
        ids, labels = _data(cfg)
        first = _run(step, ids, labels, 2)
        sd, opt_sd = self._checkpoint(step, m, opt)
        m2, opt2 = self._restore(cfg, sd, opt_sd)
        step2 = _step(m2, optimizer=opt2, scan_layers=True)  # replicated
        rest = _run(step2, ids, labels, 2)
        np.testing.assert_allclose(first + rest, ref, rtol=1e-5)

    def test_replicated_to_sharded(self, ref_losses):
        cfg, ref = ref_losses
        build_mesh({"sharding": ZD})
        _, m = _model(4)
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        step = _step(m, optimizer=opt, scan_layers=True)  # replicated
        ids, labels = _data(cfg)
        first = _run(step, ids, labels, 2)
        sd, opt_sd = self._checkpoint(step, m, opt)
        m2, opt2 = self._restore(cfg, sd, opt_sd)
        step2 = _step(m2, optimizer=opt2, scan_layers=True,
                      zero_axis="sharding", zero_stage=3)
        rest = _run(step2, ids, labels, 2)
        np.testing.assert_allclose(first + rest, ref, rtol=1e-5)

    def test_sharded_resume_bit_parity(self, ref_losses):
        """2 steps sharded -> checkpoint round-trip -> resume sharded must
        continue the uninterrupted 4-step trajectory BIT-exactly."""
        cfg, _ = ref_losses
        build_mesh({"sharding": ZD})
        _, m_a = _model(4)
        opt_a = paddle.optimizer.AdamW(learning_rate=1e-3,
                                       parameters=m_a.parameters())
        step_a = _step(m_a, optimizer=opt_a, scan_layers=True,
                       zero_axis="sharding", zero_stage=3)
        ids, labels = _data(cfg)
        straight = _run(step_a, ids, labels, 4)

        set_mesh(None)
        build_mesh({"sharding": ZD})
        _, m_b = _model(4)
        opt_b = paddle.optimizer.AdamW(learning_rate=1e-3,
                                       parameters=m_b.parameters())
        step_b = _step(m_b, optimizer=opt_b, scan_layers=True,
                       zero_axis="sharding", zero_stage=3)
        first = _run(step_b, ids, labels, 2)
        sd, opt_sd = self._checkpoint(step_b, m_b, opt_b)
        m_c, opt_c = self._restore(cfg, sd, opt_sd)
        step_c = _step(m_c, optimizer=opt_c, scan_layers=True,
                       zero_axis="sharding", zero_stage=3)
        rest = _run(step_c, ids, labels, 2)
        assert first == straight[:2]
        assert rest == straight[2:], (rest, straight[2:])


class TestPipelineZeroAxisGuard:
    def test_zero_axis_must_be_a_data_axis(self):
        """The psum_scatter grad reduction (the all_gather transpose) is
        only correct when the batch is sharded over zero_axis; a non-data
        axis (batch replicated over it) would silently scale dW by the
        shard count — must raise at construction, before any compile."""
        from paddle_tpu.models.llama import (LlamaDecoderLayer,
                                             LlamaPretrainingCriterion,
                                             _EmbeddingStage, _HeadStage)
        from paddle_tpu.parallel.pipeline import PipelinedTrainStep

        cfg = llama_tiny_config(vocab_size=64, hidden_size=32,
                                intermediate_size=64, num_hidden_layers=2,
                                num_attention_heads=2, num_key_value_heads=2,
                                max_position_embeddings=16)
        mesh = build_mesh({"pp": 2, "mp": 2})
        paddle.seed(0)
        embed = _EmbeddingStage(cfg)
        blocks = [LlamaDecoderLayer(cfg) for _ in range(2)]
        head = _HeadStage(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        with pytest.raises(ValueError, match="data axis"):
            PipelinedTrainStep(embed, blocks, head,
                               lambda lg, lb: crit(lg, lb), mesh=mesh,
                               num_micro=2, zero_axis="mp")


@pytest.mark.slow
class TestPipelineComposition:
    """Per-stage sharding composes with pp in both pipelined runtimes."""

    def _modules(self, cfg, n_blocks):
        from paddle_tpu.models.llama import (LlamaDecoderLayer,
                                             LlamaPretrainingCriterion,
                                             _EmbeddingStage, _HeadStage)

        paddle.seed(0)
        embed = _EmbeddingStage(cfg)
        blocks = [LlamaDecoderLayer(cfg) for _ in range(n_blocks)]
        head = _HeadStage(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        params = (embed.parameters()
                  + [p for b in blocks for p in b.parameters()]
                  + head.parameters())
        return embed, blocks, head, crit, params

    def test_1f1b_zero_axis_matches_baseline(self):
        from paddle_tpu.parallel.pipeline import PipelinedTrainStep

        cfg = llama_tiny_config(vocab_size=128, hidden_size=64,
                                intermediate_size=128, num_hidden_layers=4,
                                max_position_embeddings=32)
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, 128, (8, 16)).astype(np.int64))
        labels = paddle.to_tensor(
            rng.randint(0, 128, (8, 16)).astype(np.int64))
        losses, per_dev = {}, {}
        for zaxis in (None, "sharding"):
            set_mesh(None)
            mesh = build_mesh({"pp": 2, "dp": 2, "sharding": 2})
            embed, blocks, head, crit, params = self._modules(cfg, 4)
            opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=params)
            step = PipelinedTrainStep(
                embed, blocks, head, lambda lg, lb: crit(lg, lb),
                optimizer=opt, mesh=mesh, num_micro=2, zero_axis=zaxis)
            losses[zaxis] = [float(step(ids, labels)) for _ in range(2)]
            per_dev[zaxis] = sum(v.addressable_shards[0].data.nbytes
                                 for v in step._stacked_blocks)
        np.testing.assert_allclose(losses["sharding"], losses[None],
                                   rtol=1e-5)
        assert per_dev["sharding"] == per_dev[None] // 2

    def test_zbh1_zero_axis_matches_baseline(self):
        from paddle_tpu.distributed.fleet.meta_parallel import PipelineLayer
        from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel \
            import _decompose_run
        from paddle_tpu.models.llama import LlamaPretrainingCriterion
        from paddle_tpu.parallel.zero_bubble import ZBH1PipelinedStep

        rng = np.random.RandomState(0)
        ids = rng.randint(0, 256, (4, 16)).astype(np.int64)
        losses, per_dev = {}, {}
        for zaxis, axes in ((None, {"pp": 2}),
                            ("sharding", {"pp": 2, "sharding": 4})):
            set_mesh(None)
            mesh = build_mesh(axes)
            paddle.seed(0)
            cfg = llama_tiny_config(num_hidden_layers=2,
                                    use_parallel_cross_entropy=False)
            crit = LlamaPretrainingCriterion(cfg)
            pipe = PipelineLayer(
                layers=LlamaForCausalLM.pipeline_layers(cfg), num_stages=2,
                loss_fn=lambda out, lab: crit(out, lab))
            ze, zb, zh = _decompose_run(pipe.run_function, 2)
            opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                         parameters=pipe.parameters())
            step = ZBH1PipelinedStep(ze, zb, zh, lambda o, l: crit(o, l),
                                     mesh=mesh, num_micro=2, optimizer=opt,
                                     zero_axis=zaxis)
            losses[zaxis] = [float(step(ids, ids)) for _ in range(2)]
            per_dev[zaxis] = sum(v.addressable_shards[0].data.nbytes
                                 for v in step._stacked_blocks)
        np.testing.assert_allclose(losses["sharding"], losses[None],
                                   rtol=1e-5)
        assert per_dev["sharding"] == per_dev[None] // 4


class TestArtifactContainer:
    """Satellite: the .pdmodel container is data-only members + JSON
    metadata; legacy pickle artifacts are rejected with a re-export
    pointer."""

    def test_round_trip_with_bf16(self, tmp_path):
        import ml_dtypes

        from paddle_tpu.inference.artifact import (read_artifact,
                                                   write_artifact)

        path = str(tmp_path / "m.pdmodel")
        params = [np.arange(12, dtype=np.float32).reshape(3, 4),
                  np.ones((2, 2), dtype=ml_dtypes.bfloat16)]
        blob = {"stablehlo": b"\x00mlir-bytes", "params": params,
                "class": "X", "in_shapes": [((1, "b"), "int32")],
                "feed_names": ["x0"], "fetch_count": 2}
        write_artifact(path, blob)
        out = read_artifact(path)
        assert bytes(out["stablehlo"]) == blob["stablehlo"]
        assert out["class"] == "X" and out["fetch_count"] == 2
        for a, b in zip(out["params"], params):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_legacy_pickle_rejected_everywhere(self, tmp_path):
        import pickle

        from paddle_tpu.inference.artifact import read_artifact
        from paddle_tpu.inference.serve import Artifact

        path = str(tmp_path / "legacy.pdmodel")
        with open(path, "wb") as f:
            pickle.dump({"stablehlo": b"", "params": []}, f)
        with pytest.raises(ValueError, match="pickle"):
            read_artifact(path)
        with pytest.raises(ValueError, match="jit.save"):
            Artifact(path)

    def test_jit_save_serves_through_container(self, tmp_path):
        import paddle_tpu.nn as nn
        from paddle_tpu import jit
        from paddle_tpu.inference.serve import Artifact
        from paddle_tpu.jit import InputSpec

        paddle.seed(0)
        layer = nn.Linear(4, 3)
        prefix = str(tmp_path / "lin")
        jit.save(layer, prefix,
                 input_spec=[InputSpec([None, 4], "float32")])
        art = Artifact(prefix, warmup=0)
        x = np.ones((2, 4), np.float32)
        got = art.run([x])[0]
        ref = np.asarray(layer(paddle.to_tensor(x))._value)
        np.testing.assert_allclose(got, ref, rtol=1e-6)


class TestFallbackWarningKey:
    """Satellite: the one-time XLA-fallback warning dedups per
    (reason, shape-signature), so a second distinct cause still warns."""

    def test_same_reason_new_shape_warns_again(self):
        import paddle_tpu.nn.functional as Fmod

        Fmod._warned_pallas_blocks.clear()
        try:
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                Fmod._warn_pallas_blocks_once("r1", shape_sig=(1, 48, 2, 8))
                Fmod._warn_pallas_blocks_once("r1", shape_sig=(1, 48, 2, 8))
                Fmod._warn_pallas_blocks_once("r1", shape_sig=(1, 80, 2, 8))
                Fmod._warn_pallas_blocks_once("r2", shape_sig=(1, 48, 2, 8))
            assert len(w) == 3
        finally:
            Fmod._warned_pallas_blocks.clear()
