"""Test harness: hardware-free multi-device testing.

Replicates the reference's fake-backend pattern (SURVEY §4.4: custom_cpu
plugin + PADDLE_DISTRI_CUSTOM_DEVICE_TYPE) the TPU-native way — a virtual
8-device CPU platform via XLA_FLAGS, so every sharding/collective test runs
the real mesh code paths without TPUs.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


# compile-heavy / multi-process modules: the FULL tier (CI gate). The quick
# tier (-m "not slow") keeps a <3-min per-commit signal (reference
# testslist.csv run_type tiers, test/collective/README.md)
SLOW_TEST_MODULES = {
    "test_parallel", "test_zero_bubble", "test_multiprocess",
    "test_multinode_launch", "test_io_workers", "test_op_numeric",
    "test_vision_models", "test_vision_models2", "test_examples",
    "test_dist_model", "test_strategy_passes", "test_torch_parity",
    "test_group_sharded", "test_ring_attention", "test_flash_attention",
    "test_functional_tail", "test_fused_layers", "test_engine_logging",
    "test_loss_parity", "test_models_configs", "test_moe", "test_moe_gates",
    "test_vision_ops", "test_nn_layers", "test_optimizer",
    "test_aux_subsystems", "test_fft_signal_distribution",
    "test_advice_fixes_r4", "test_static_graph", "test_jit_save_load",
    "test_parallel_parity", "test_serving_system",
}


# Single heavy tests inside otherwise-quick modules: the interpret-mode kernel
# parity runs that came back to life in PR 21 (they died at import before and
# cost nothing). Tier-1 runs against a hard time limit, and each of these has
# a lighter sibling of the same kernel left in the quick tier.
SLOW_TESTS = {
    "test_serving.py::TestDecodeParity::test_bf16_gqa_parity_1e3",
    "test_serving.py::TestDecodeParity::test_fp32_parity",
    "test_serving.py::TestSpeculativeInterpretKernel::test_fp32",
    "test_disagg.py::TestPackedPrefillParity::"
    "test_fp32_parity_through_interpret_kernels",
    "test_fused_cross_entropy.py::TestFusedLinearCE::"
    "test_loss_and_grad_parity[bfloat16-pallas]",
    "test_paged_attention.py::TestKernelParity::test_bf16_parity_gqa",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        file_id = item.nodeid.rsplit("/", 1)[-1]
        mod = file_id.split("::")[0].removesuffix(".py")
        if mod in SLOW_TEST_MODULES or file_id in SLOW_TESTS:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu

    paddle_tpu.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture(autouse=True)
def _faults_hygiene():
    """A test that arms a fault point (or leaves FLAGS_fault_injection set)
    must not chaos-inject into the rest of the suite."""
    yield
    from paddle_tpu.core.flags import set_flags
    from paddle_tpu.distributed.resilience import faults

    faults.reset()
    set_flags({"fault_injection": "", "ckpt_fault_injection": ""})


@pytest.fixture(autouse=True)
def _observability_hygiene():
    """A test that starts a tracing window or fills the event journal must
    not leak spans/events into the rest of the suite (the metrics registry
    is additive-only and stays — module-scoped engines keep their
    scrape-time collectors alive across tests)."""
    yield
    from paddle_tpu.observability import events, tracing

    tracing.reset()
    events.journal().clear()


@pytest.fixture(autouse=True)
def _thread_hygiene():
    """Tier-1 guard: DataLoader/DeviceFeeder prefetch threads, the
    elastic-checkpoint writer, store heartbeats, AND the serving fleet's
    threads (engine drivers, replica drivers, the router health monitor)
    must not leak across tests. Every such background thread carries its
    subsystem name prefix ("paddle_tpu.io", "paddle_tpu.ckpt",
    "paddle_tpu.serving", "paddle_tpu.store") and is joined on
    close/exhaustion — a test that strands one fails here instead of
    poisoning the rest of the suite."""
    import threading
    import time

    # compare Thread OBJECTS, not idents: CPython recycles idents, so a
    # leaked thread could inherit a baseline thread's ident and hide
    before = set(threading.enumerate())

    def leaked():
        return [t for t in threading.enumerate()
                if t.name.startswith(("paddle_tpu.io", "paddle_tpu.ckpt",
                                      "paddle_tpu.serving",
                                      "paddle_tpu.store"))
                and t not in before and t.is_alive()]

    yield
    deadline = time.time() + 3.0
    while leaked() and time.time() < deadline:
        time.sleep(0.02)  # grace: exhausted workers exit right after _End
    assert not leaked(), (
        f"leaked prefetch threads: {[t.name for t in leaked()]}")


@pytest.fixture
def flash_interpret():
    """Run the Pallas flash-attention kernels — including the segment-aware
    forward/dq/dkv variants and the F.scaled_dot_product_attention fast
    path — under interpret=True on CPU, so the tier-1 suite exercises the
    SAME kernel code paths (online softmax, causal+segment masking, block
    skipping) the TPU runs through Mosaic."""
    from paddle_tpu.ops.pallas.flash_attention import force_interpret

    with force_interpret():
        yield


@pytest.fixture
def paged_interpret():
    """Run the Pallas paged decode-attention kernel under interpret=True on
    CPU — the serving analog of `flash_interpret`: the dispatcher
    (paged_attention) then routes into the SAME kernel code path (scalar-
    prefetch page gather, online softmax over pages, the shared
    block-skip predicate) the TPU runs through Mosaic, instead of the XLA
    reference fallback."""
    from paddle_tpu.ops.pallas.paged_attention import force_interpret

    with force_interpret():
        yield


@pytest.fixture
def fp8_smoke():
    """Tier-1-safe fp8 smoke path: flip the `fp8_policy` flag to 'matmuls'
    so flag-driven step construction builds the float8 dot_general path —
    XLA CPU executes f8E4M3FN/f8E5M2 dots via emulation, so the tier-1
    suite exercises the SAME lowered program structure the TPU runs
    (the fp8 analog of `flash_interpret`)."""
    from paddle_tpu.core.flags import get_flags, set_flags

    prev = get_flags("fp8_policy")["fp8_policy"]
    set_flags({"fp8_policy": "matmuls"})
    yield
    set_flags({"fp8_policy": prev})


@pytest.fixture
def mesh8():
    """A pp2 x dp2 x mp2 mesh over the 8 virtual devices."""
    from paddle_tpu.distributed.mesh import build_mesh, set_mesh

    m = build_mesh({"pp": 2, "dp": 2, "mp": 2})
    yield m
    set_mesh(None)
