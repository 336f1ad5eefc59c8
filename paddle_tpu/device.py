"""paddle.device (reference: python/paddle/device/) — device queries, memory
stats (HBM via PJRT memory_stats instead of the reference's CUDA allocator
counters), stream compat shims (XLA owns scheduling)."""

from __future__ import annotations

import jax

from .framework import core as _core
from .framework.core import (  # noqa: F401
    CPUPlace,
    Place,
    TPUPlace,
    get_device,
    set_device,
)


def set_memory_fraction(fraction, device=None):
    """Cap the HBM fraction the process preallocates (reference:
    FLAGS_fraction_of_gpu_memory_to_use over the BFC allocator).

    TPU-native: the allocator is PJRT's; the knob is
    XLA_PYTHON_CLIENT_MEM_FRACTION and it only takes effect BEFORE the
    first jax backend initialization — call this first thing, or set the
    env var in the launcher.  Raises if the backend is already live with a
    different setting rather than silently doing nothing."""
    import os

    import jax

    want = float(fraction)
    live = getattr(getattr(jax._src, "xla_bridge", None), "_backends", None)
    cur = os.environ.get("XLA_PYTHON_CLIENT_MEM_FRACTION")
    already = cur is not None and float(cur) == want
    if live and not already:
        raise RuntimeError(
            "set_memory_fraction must run before the first jax computation "
            "(the PJRT allocator is configured at backend init); set "
            f"XLA_PYTHON_CLIENT_MEM_FRACTION={want} in the environment or "
            "call earlier"
        )
    os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] = str(want)


def get_all_device_type():
    kinds = {"cpu"}
    try:
        if jax.devices()[0].platform != "cpu":
            kinds.add("tpu")
    except RuntimeError:
        pass
    return sorted(kinds)


def get_available_device():
    return [f"tpu:{i}" for i in range(_core.device_count("tpu"))] or ["cpu"]


def get_available_custom_device():
    return []


def is_compiled_with_cinn():
    return False


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def device_count():
    return max(_core.device_count("tpu"), 1)


class Stream:
    """Compat shim: XLA's runtime owns stream scheduling on TPU."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


def stream_guard(stream):
    import contextlib

    @contextlib.contextmanager
    def _guard():
        yield

    return _guard()


def synchronize(device=None):
    """Block until all queued work completes (XLA: drain async dispatch).
    A device that fails here raises: the caller asked for a barrier."""
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()


class cuda:
    """Namespace mirror of paddle.device.cuda, mapped to the TPU backend."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return _core.device_count("tpu")

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def current_stream(device=None):
        return Stream(device)

    @staticmethod
    def stream_guard(stream):
        return stream_guard(stream)

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def _mem_stats(device=None):
        devs = jax.devices()
        d = devs[device if isinstance(device, int) else 0]
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:
            pass
        return stats

    @staticmethod
    def memory_allocated(device=None):
        return int(cuda._mem_stats(device).get("bytes_in_use", 0))

    @staticmethod
    def max_memory_allocated(device=None):
        return int(cuda._mem_stats(device).get("peak_bytes_in_use", 0))

    @staticmethod
    def memory_reserved(device=None):
        return int(cuda._mem_stats(device).get("bytes_reserved", cuda.memory_allocated(device)))

    @staticmethod
    def max_memory_reserved(device=None):
        return int(cuda._mem_stats(device).get("peak_bytes_in_use", 0))

    @staticmethod
    def get_device_properties(device=None):
        devs = jax.devices()
        d = devs[device if isinstance(device, int) else 0]

        class _Props:
            name = str(d.device_kind)
            major = 0
            minor = 0
            total_memory = int(cuda._mem_stats(device).get("bytes_limit", 0))
            multi_processor_count = 1

        return _Props()


class tpu(cuda):
    """First-class TPU namespace: paddle_tpu.device.tpu.*"""

    @staticmethod
    def memory_stats(device=None):
        stats = dict(cuda._mem_stats(device))
        from . import native as _native

        stats.update(_native.host_memory_stats())
        return stats
