"""Device placement.

Analog of the reference's Place hierarchy (`paddle/common/place.h` — CPUPlace /
GPUPlace / XPUPlace / CustomPlace) re-targeted at TPU: the framework's places
are ``tpu`` (a PJRT TPU device) and ``cpu`` (XLA-CPU), with ``tpu``
transparently falling back to XLA-CPU when no TPU is attached (the fake-device
testing strategy the reference implements with `custom_cpu` plugins — see
SURVEY.md §4 "Fake-backend strategy").
"""
from __future__ import annotations

import threading



class Place:
    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        if ":" in device_type:
            device_type, _, idx = device_type.partition(":")
            device_id = int(idx)
        device_type = device_type.lower()
        if device_type == "gpu":  # compat: treat gpu requests as the accelerator
            device_type = "tpu"
        if device_type not in ("cpu", "tpu"):
            raise ValueError(f"Unsupported device type: {device_type!r} (use 'cpu' or 'tpu')")
        self.device_type = device_type
        self.device_id = device_id

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    # reference-API compat: code written for GPU targets the accelerator
    # here (Place("gpu", i) normalizes to tpu), so a "gpu place" question
    # means "is this the accelerator" — must answer True or ported code
    # silently takes its CPU fallback branch.
    def is_gpu_place(self):
        return self.device_type == "tpu"

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        if isinstance(other, str):
            try:
                other = Place(other)
            except ValueError:
                return False
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(device_id: int = 0):
    return Place("tpu", device_id)


def CUDAPlace(device_id: int = 0):
    """Reference-compat: code written for GPU runs on the accelerator
    (Place("gpu", i) already normalizes to the tpu device)."""
    return Place("gpu", device_id)


def CUDAPinnedPlace():
    """Reference-compat: pinned host staging memory maps to plain host
    memory (PJRT handles the staging buffers)."""
    return Place("cpu", 0)


_state = threading.local()


def _default_place() -> Place:
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        return Place("cpu", 0)
    return Place("tpu", 0)


def set_device(device) -> Place:
    """paddle.set_device parity (reference: python/paddle/device/__init__.py)."""
    p = device if isinstance(device, Place) else Place(device)
    _state.place = p
    return p


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}"


def current_place() -> Place:
    p = getattr(_state, "place", None)
    if p is None:
        p = _default_place()
        _state.place = p
    return p


def jax_device(place: Place | None = None):
    """Resolve a Place to a concrete jax.Device. A 'tpu' place with no TPU
    attached raises: it never resolves to the CPU."""
    import jax

    place = place or current_place()
    if place.device_type == "cpu":
        return jax.local_devices(backend="cpu")[0]
    devs = [d for d in jax.devices() if d.platform != "cpu"]
    if not devs:
        raise RuntimeError(
            f"{place!r} needs a TPU and JAX reports none "
            f"({jax.devices()[0].platform}); name a cpu place to run there")
    return devs[min(place.device_id, len(devs) - 1)]


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True
