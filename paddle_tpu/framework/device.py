"""Device / Place abstraction.

Plays the role of Paddle's ``Place`` hierarchy (``paddle/phi/common/place.h``,
UNVERIFIED — reference mount empty at survey time). On TPU the device runtime
(streams, contexts, allocators) is owned by PJRT/XLA, so this layer is a thin,
honest façade: Places name PJRT devices; there are no user-managed streams.
"""

from __future__ import annotations

import jax

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "XPUPlace", "CustomPlace",
    "set_device", "get_device", "device_count", "is_compiled_with_cuda",
    "is_compiled_with_xpu", "is_compiled_with_tpu", "place_of", "get_all_devices",
]


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        """The PJRT device this place names. ``jax.devices()`` lists the
        default backend, which is the TPU whenever one is attached (jax
        fails at start-up there if it cannot take it), so on a chip host
        a ``tpu:N`` place always resolves to a TPU device.

        Source compatibility on a CPU-ONLY host: an accelerator place
        (``TPUPlace``/``CUDAPlace``/``XPUPlace`` in code ported from
        Paddle, and in the CPU tests) resolves to a CPU device instead
        of failing. This is the only case in which the returned device's
        platform differs from the place's; ``chip_smoke.py`` checks that
        it does not happen with a chip present."""
        devs = [d for d in jax.devices() if _kind(d) == self.device_type]
        if not devs:
            devs = jax.devices("cpu")
        return devs[min(self.device_id, len(devs) - 1)]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(Place):
    """Accepted for source compatibility; resolves to the accelerator
    (TPU if present, else CPU)."""
    device_type = "tpu"


class XPUPlace(Place):
    device_type = "tpu"


class CustomPlace(Place):
    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


def _kind(dev) -> str:
    p = dev.platform.lower()
    if p == "tpu":
        return "tpu"
    if p in ("gpu", "cuda", "rocm"):
        return "gpu"
    return "cpu"


_current_device: str | None = None


def get_all_devices():
    return jax.devices()


def device_count(device_type: str | None = None) -> int:
    if device_type is None:
        return len(jax.devices())
    return len([d for d in jax.devices() if _kind(d) == device_type])


def set_device(device: str) -> Place:
    """``paddle.set_device('tpu:0' | 'cpu' | 'gpu:0')``."""
    global _current_device
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu"}.get(name, name)
    _current_device = f"{name}:{idx}"
    if name == "cpu":
        return CPUPlace(idx)
    return TPUPlace(idx)


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    default = jax.devices()[0]
    return f"{_kind(default)}:{default.id}"


def default_place() -> Place:
    name, _, idx = get_device().partition(":")
    return CPUPlace(int(idx or 0)) if name == "cpu" else TPUPlace(int(idx or 0))


def place_of(data) -> Place:
    try:
        devs = list(data.devices())
        dev = devs[0]
        kind = _kind(dev)
        return CPUPlace(dev.id) if kind == "cpu" else TPUPlace(dev.id)
    except Exception:
        return default_place()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return device_count("tpu") > 0
