"""Place/device model.

Reference: ``phi::Place`` (paddle/phi/common/place.h:58) names a device slot;
DeviceContext/streams are per-place. Under PJRT there is no user-managed stream or
allocator — a Place is just a ``jax.Device`` — so this module is a thin naming layer:
``TPUPlace(i)``/``CPUPlace()`` map to jax devices, and ``set_device`` picks the default
placement for newly created tensors.
"""
from __future__ import annotations

import jax


class Place:
    """Named device slot (kind + index)."""

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind
        self.index = index

    def is_tpu_place(self):
        return self.kind == "tpu"

    def is_cpu_place(self):
        return self.kind == "cpu"

    def is_gpu_place(self):  # parity shim: CUDAPlace maps onto the accelerator
        return self.kind in ("gpu", "tpu")

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.index) == (other.kind, other.index)

    def __hash__(self):
        return hash((self.kind, self.index))

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"


def TPUPlace(index: int = 0) -> Place:
    return Place("tpu", index)


def CPUPlace() -> Place:
    return Place("cpu", 0)


def CUDAPlace(index: int = 0) -> Place:  # parity alias: the accelerator place
    return Place("tpu", index)


XPUPlace = TPUPlace
CustomPlace = TPUPlace


_current_place: Place | None = None


def set_device(device: str | Place) -> Place:
    """paddle.device.set_device — "tpu", "tpu:1", "cpu"."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    kind, _, idx = device.partition(":")
    kind = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}.get(kind, kind)
    _current_place = Place(kind, int(idx) if idx else 0)
    return _current_place


def get_device() -> str:
    p = get_place()
    return f"{p.kind}:{p.index}"


def get_place() -> Place:
    global _current_place
    if _current_place is None:
        accel = [d for d in jax.devices() if d.platform != "cpu"]
        _current_place = Place(accel[0].platform, 0) if accel else Place("cpu", 0)
    return _current_place


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return len(jax.devices())
