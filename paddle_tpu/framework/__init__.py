"""Framework glue (reference: python/paddle/framework + python/paddle/base/framework.py)."""
from paddle_tpu.framework.io_ import load, save  # noqa: F401
from paddle_tpu.core.flags import get_flags, set_flags  # noqa: F401
from paddle_tpu.ops.random_state import seed, default_generator  # noqa: F401


def get_default_dtype():
    from paddle_tpu.core.dtype import get_default_dtype as g

    return g()


def set_default_dtype(d):
    from paddle_tpu.core.dtype import set_default_dtype as s

    return s(d)


def in_dynamic_mode():
    return True


# ---------------------------------------------------------------------------
# build/introspection tail (reference: paddle.is_compiled_with_*, iinfo/finfo,
# rng-state surface, set_printoptions, LazyGuard)

def is_compiled_with_cuda():
    return False  # TPU-native build


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_cinn():
    return False  # XLA is the compiler


def is_compiled_with_custom_device(device_type: str):
    """The TPU is the custom device of this build (the reference's
    CustomDevice seam is PJRT here)."""
    return device_type == "tpu"


class iinfo:
    def __init__(self, dtype):
        import numpy as _np

        from paddle_tpu.core.dtype import to_jax_dtype

        info = _np.iinfo(_np.dtype(str(to_jax_dtype(dtype))))
        self.min = int(info.min)
        self.max = int(info.max)
        self.bits = info.bits
        self.dtype = str(info.dtype)


class finfo:
    def __init__(self, dtype):
        import jax.numpy as _jnp
        import numpy as _np

        from paddle_tpu.core.dtype import to_jax_dtype

        jdt = to_jax_dtype(dtype)
        info = _jnp.finfo(jdt)
        self.min = float(info.min)
        self.max = float(info.max)
        self.eps = float(info.eps)
        self.tiny = float(info.tiny)
        self.smallest_normal = float(info.tiny)
        self.resolution = float(getattr(info, "resolution", info.eps))
        self.bits = info.bits
        self.dtype = str(_np.dtype(jdt)) if jdt != _jnp.bfloat16 else "bfloat16"


def get_rng_state(device=None):
    """Opaque RNG state list (reference returns per-device GeneratorState)."""
    from paddle_tpu.ops.random_state import default_generator

    return [default_generator.get_state()]


def set_rng_state(state_list, device=None):
    from paddle_tpu.ops.random_state import default_generator

    default_generator.set_state(state_list[0])


get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor repr printing options (reference base/framework
    set_printoptions); maps onto numpy printoptions, which Tensor.__repr__
    uses."""
    import numpy as _np

    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


class LazyGuard:
    """reference framework LazyGuard: defer parameter initialization. Eager
    init is cheap on host here, so the guard only marks the scope (kept for
    source parity)."""

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False
