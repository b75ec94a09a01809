"""paddle.device parity (reference: python/paddle/device/__init__.py —
set_device:281, streams/events, paddle.device.cuda memory API).

TPU-native: XLA owns per-device scheduling, so Stream/Event are ordering
no-ops that preserve the API (work under one JAX device is already ordered;
``synchronize`` blocks on outstanding async dispatch).  Memory stats come
from PJRT ``device.memory_stats()``.
"""
from __future__ import annotations

from typing import Optional

import jax

from ..framework.device import (  # noqa: F401
    Place, CPUPlace, TPUPlace, CUDAPlace, set_device, get_device,
    device_count, is_compiled_with_cuda, is_compiled_with_xpu,
)

__all__ = [
    "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "set_device", "get_device",
    "device_count", "synchronize", "Stream", "Event", "current_stream",
    "set_stream", "stream_guard", "get_all_device_type",
    "get_available_device", "get_all_custom_device_type",
    "get_available_custom_device", "is_compiled_with_cuda",
    "is_compiled_with_xpu", "cuda",
]


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_all_custom_device_type():
    return [p for p in get_all_device_type() if p not in ("cpu", "gpu", "tpu")]


def get_available_custom_device():
    return [d for d in get_available_device()
            if d.split(":")[0] not in ("cpu", "gpu", "tpu")]


def synchronize(device=None) -> None:
    """Block until all queued device work completes (reference:
    paddle.device.synchronize).  JAX dispatch is async; this drains it."""
    try:
        jax.effects_barrier()
    except Exception:
        (jax.device_put(0.0) + 0).block_until_ready()


class Stream:
    """Ordering handle (reference: paddle.device.Stream).  Under XLA one
    device has one well-ordered execution; record/wait are no-ops kept so
    multi-stream CUDA code ports cleanly."""

    def __init__(self, device=None, priority: int = 2):
        self.device = device
        self.priority = priority

    def wait_event(self, event: "Event") -> None: ...
    def wait_stream(self, stream: "Stream") -> None: ...
    def record_event(self, event: Optional["Event"] = None) -> "Event":
        return event or Event()
    def query(self) -> bool:
        return True
    def synchronize(self) -> None:
        synchronize(self.device)


class Event:
    """reference: paddle.device.Event."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self.device = device

    def record(self, stream: Optional[Stream] = None) -> None: ...
    def query(self) -> bool:
        return True
    def synchronize(self) -> None:
        synchronize(self.device)


_current_stream = Stream()


def current_stream(device=None) -> Stream:
    return _current_stream


def set_stream(stream: Stream) -> Stream:
    global _current_stream
    prev, _current_stream = _current_stream, stream
    return prev


class stream_guard:
    """Context manager (reference: paddle.device.stream_guard)."""

    def __init__(self, stream: Stream):
        self._stream = stream
        self._prev = None

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False

from . import cuda  # noqa: E402,F401  (imported last: cuda.py re-uses Stream/Event)


# ------------------------------------------------ compile-config predicates
def XPUPlace(device_id: int = 0):
    """compat shim (reference XPUPlace): maps to the accelerator place."""
    from ..framework.device import CUDAPlace
    return CUDAPlace(device_id)


def IPUPlace():
    """compat shim (reference IPUPlace): IPU is not a PJRT target here."""
    from ..framework.device import CPUPlace
    return CPUPlace()


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    """XLA is the compiler backend (the role CINN plays in the reference)
    — but CINN itself is not linked."""
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_custom_device(device_type: str = "") -> bool:
    """A TPU reaches jax as a PJRT plug-in device: the reference's
    custom-device notion."""
    import jax as _jax
    return any(d.platform not in ("cpu", "gpu", "cuda")
               for d in _jax.devices())


def is_compiled_with_distribute() -> bool:
    return True


def get_cudnn_version():
    """reference: device.get_cudnn_version — None when not a CUDA build."""
    return None


class _PlatformNamespace:
    """device.gpu / device.xpu / device.npu namespaces (reference exposes
    per-vendor helper modules; each maps onto the single PJRT device
    surface here)."""

    def __init__(self, name):
        self._name = name

    def device_count(self):
        import jax as _jax
        try:
            return len([d for d in _jax.devices()
                        if d.platform != "cpu"])
        except Exception:
            return 0

    def synchronize(self, device=None):
        return synchronize(device)


gpu = _PlatformNamespace("gpu")
xpu = _PlatformNamespace("xpu")
npu = _PlatformNamespace("npu")
