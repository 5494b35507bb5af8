"""Device management: Place objects + set_device/get_device.

Reference parity: `paddle.device.set_device` / `CUDAPlace`/`CPUPlace`/`CustomPlace`
(reference: python/paddle/device/__init__.py, phi DeviceContext at
paddle/phi/core/device_context.h:36). On TPU the device zoo collapses to
{tpu, cpu}: a Place maps to a concrete `jax.Device`, and "streams" map to XLA's
async dispatch (every jax op is issued asynchronously; `synchronize` blocks).
"""
from __future__ import annotations

import threading

import jax

__all__ = [
    "Place",
    "TPUPlace",
    "CPUPlace",
    "set_device",
    "get_device",
    "get_all_devices",
    "device_count",
    "synchronize",
    "is_compiled_with_tpu",
    "current_jax_device",
]


class Place:
    """A device place: device type + ordinal, resolving to a jax.Device."""

    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self) -> jax.Device:
        # eager tensors live on PROCESS-LOCAL devices: in a multi-process job
        # (jax.distributed) a device_put to a non-addressable global device
        # would produce an array this process cannot read. A platform this
        # host does not have is an error — 'tpu' never means "whatever is
        # there", so set_device('tpu') on a CPU-only host raises instead of
        # quietly computing on the CPU.
        try:
            devs = jax.local_devices(backend=self.device_type)
        except RuntimeError as e:
            raise RuntimeError(
                f"no jax devices of type '{self.device_type}': {e}") from e
        if self.device_id >= len(devs):
            raise RuntimeError(
                f"device ordinal {self.device_id} out of range for "
                f"'{self.device_type}' ({len(devs)} present)"
            )
        return devs[self.device_id]

    def is_tpu_place(self):
        return self.device_type not in ("cpu",)

    def is_cpu_place(self):
        return self.device_type == "cpu"


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def CPUPlace() -> Place:
    return Place("cpu", 0)


class _DeviceState(threading.local):
    def __init__(self):
        self.place = None


_state = _DeviceState()


def _default_place() -> Place:
    plat = jax.devices()[0].platform
    return Place("cpu" if plat == "cpu" else "tpu", 0)


def set_device(device) -> Place:
    """Set the global default place, e.g. ``set_device('tpu')`` / ``'tpu:0'`` / ``'cpu'``."""
    if isinstance(device, Place):
        _state.place = device
        return device
    if not isinstance(device, str):
        raise TypeError(f"device must be str or Place, got {type(device)}")
    if ":" in device:
        dtype_, _, ordinal = device.partition(":")
        place = Place(dtype_, int(ordinal))
    else:
        place = Place(device, 0)
    place.jax_device()  # validate eagerly
    _state.place = place
    return place


def get_device() -> str:
    place = _state.place or _default_place()
    return f"{place.device_type}:{place.device_id}"


def current_place() -> Place:
    if _state.place is None:
        _state.place = _default_place()
    return _state.place


def current_jax_device() -> jax.Device:
    return current_place().jax_device()


def get_all_devices():
    return [f"{'cpu' if d.platform == 'cpu' else 'tpu'}:{d.id}" for d in jax.devices()]


def device_count(device_type: str = "tpu") -> int:
    try:
        return len(jax.local_devices(backend=device_type))
    except RuntimeError:  # this host has no such platform
        return 0


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def synchronize(device=None):
    """Block until all issued work on the device is complete.

    XLA dispatch is async (the analog of the reference's CUDA streams,
    paddle/phi/core/device_context.h); this is the barrier.
    """
    for d in jax.devices():
        try:
            d.synchronize_all_activity()  # pjrt api, may not exist on all backends
        except AttributeError:
            pass
    # Portable fallback: a tiny blocking transfer.
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()
