"""Device management.

Counterpart of the reference's device runtime (``paddle/phi/backends/``,
``python/paddle/device/``).  On the TPU stack, PJRT *is* the device layer: JAX
owns device discovery, memory, and streams.  This module provides the
Paddle-shaped API surface (``set_device``/``get_device``/``synchronize``,
``Stream``/``Event`` shims) over it.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax


_CURRENT_DEVICE: Optional[jax.Device] = None


def _platform_of(spec: str) -> str:
    # accepts "tpu", "cpu", "gpu", "tpu:0"
    return spec.split(":")[0].lower()


def set_device(device: str):
    """Select the device eager tensors are placed on. E.g. ``set_device('tpu')``."""
    global _CURRENT_DEVICE
    plat = _platform_of(device)
    idx = int(device.split(":")[1]) if ":" in device else 0
    devs = [d for d in jax.devices() if d.platform.lower() == plat]
    if not devs:
        raise RuntimeError(
            f"set_device({device!r}): no {plat} device; jax.devices() holds "
            f"{sorted({d.platform for d in jax.devices()})}")
    if idx >= len(devs):
        raise RuntimeError(
            f"set_device({device!r}): {plat} has {len(devs)} device(s)")
    _CURRENT_DEVICE = devs[idx]
    return _CURRENT_DEVICE


def get_device() -> str:
    d = current_device()
    return f"{d.platform}:{d.id}"


def current_device() -> jax.Device:
    global _CURRENT_DEVICE
    if _CURRENT_DEVICE is None:
        _CURRENT_DEVICE = jax.devices()[0]
    return _CURRENT_DEVICE


def device_count(platform: Optional[str] = None) -> int:
    try:
        return len(jax.devices(platform)) if platform else len(jax.devices())
    except RuntimeError:
        return 0


def is_compiled_with_tpu() -> bool:
    return any(d.platform.lower() != "cpu" for d in jax.devices())


def synchronize(device=None) -> None:
    """Block until all queued work on the device is complete.

    JAX dispatch is async; a cheap barrier is to block on a trivial computation.
    """
    (jax.device_put(0, current_device()) + 0).block_until_ready()


class Event:
    """Paddle-shaped event shim (``python/paddle/device/__init__.py`` Event).

    XLA's execution model has no user-visible streams; record/synchronize map to
    host-side timestamps around async dispatch barriers.
    """

    def __init__(self, enable_timing: bool = True):
        self._t: Optional[float] = None
        self.enable_timing = enable_timing

    def record(self, stream=None) -> None:
        synchronize()
        self._t = time.perf_counter()

    def synchronize(self) -> None:
        synchronize()

    def query(self) -> bool:
        return True

    def elapsed_time(self, end: "Event") -> float:
        if self._t is None or end._t is None:
            raise RuntimeError("events must be recorded before elapsed_time")
        return (end._t - self._t) * 1000.0


class Stream:
    """Stream shim: XLA enqueues on a single per-device compute stream."""

    def __init__(self, device=None, priority: int = 2):
        self.device = device or current_device()

    def synchronize(self) -> None:
        synchronize()

    def query(self) -> bool:
        return True

    def wait_event(self, event: Event) -> None:
        event.synchronize()

    def wait_stream(self, stream: "Stream") -> None:
        stream.synchronize()


_DEFAULT_STREAM = None


def current_stream(device=None) -> Stream:
    global _DEFAULT_STREAM
    if _DEFAULT_STREAM is None:
        _DEFAULT_STREAM = Stream(device)
    return _DEFAULT_STREAM


@contextlib.contextmanager
def stream_guard(stream: Stream):
    yield stream
