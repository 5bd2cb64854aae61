"""Device management (ref: python/paddle/device/__init__.py —
set_device:141/get_device:201, is_compiled_with_cuda, synchronize;
device/cuda/ memory_allocated, Stream/Event).

TPU-native mapping: PJRT owns devices; "set_device" selects the default
jax device for subsequent placements, "synchronize" drains dispatched
work (``block_until_ready`` on a fresh computation), and the cuda.* memory accessors forward
to the PJRT allocator stats (profiler/memory.py). Streams/events dissolve
— XLA's async dispatch IS the stream; Event becomes a completion fence."""

import jax

__all__ = ["set_device", "get_device", "get_all_device_type",
           "get_all_custom_device_type", "is_compiled_with_cuda",
           "is_compiled_with_rocm", "is_compiled_with_npu",
           "is_compiled_with_xpu", "is_compiled_with_tpu",
           "device_count", "synchronize", "cuda", "Stream", "Event"]

_current = None


def set_device(device: str):
    """'tpu', 'tpu:0', 'cpu' — pins the default placement device
    (≙ set_device:141). Returns the jax device."""
    global _current
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = {"gpu": "tpu"}.get(name, name)  # reference scripts say gpu
    devs = [d for d in jax.devices() if d.platform == name]
    if not devs and name != jax.default_backend():
        try:
            devs = jax.devices(name)
        except RuntimeError:
            devs = []
    if not devs:
        raise ValueError(
            f"no {name!r} devices; available: "
            f"{sorted({d.platform for d in jax.devices()})}")
    _current = devs[idx]
    jax.config.update("jax_default_device", _current)
    return _current


def get_device() -> str:
    """(≙ get_device:201) e.g. 'tpu:0'."""
    d = _current if _current is not None else jax.devices()[0]
    return f"{d.platform}:{d.id}"


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [p for p in get_all_device_type() if p not in ("cpu", "tpu")]


def is_compiled_with_cuda() -> bool:
    return False  # TPU build


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def device_count() -> int:
    return len(jax.devices())


def synchronize(device=None):
    """Block until dispatched work completes (≙ cuda.synchronize): a
    fresh computation queues behind everything already dispatched, and
    ``block_until_ready`` waits for it."""
    import jax.numpy as jnp
    (jnp.zeros(()) + 0.0).block_until_ready()


class Event:
    """Completion fence (≙ device.cuda.Event). record() captures the
    async-dispatch frontier; synchronize()/query() resolve it."""

    def __init__(self, enable_timing=False):
        self.enable_timing = enable_timing
        self._time = None

    def record(self, stream=None):
        import time
        synchronize()
        self._time = time.perf_counter()

    def query(self) -> bool:
        return True

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end: "Event") -> float:
        if self._time is None or end._time is None:
            raise RuntimeError("record() both events first")
        return (end._time - self._time) * 1e3


class Stream:
    """API-parity stream (≙ device.cuda.Stream). XLA's async dispatch is
    the one stream; this object scopes nothing but keeps ported code
    running."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        event.synchronize()

    def wait_stream(self, stream):
        stream.synchronize()

    def record_event(self, event=None):
        ev = event or Event()
        ev.record()
        return ev

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _CudaNamespace:
    """paddle.device.cuda parity surface, forwarding to PJRT stats."""

    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def memory_allocated(device=None):
        from paddle_tpu.profiler.memory import memory_allocated
        return memory_allocated(device)

    @staticmethod
    def max_memory_allocated(device=None):
        from paddle_tpu.profiler.memory import max_memory_allocated
        return max_memory_allocated(device)

    @staticmethod
    def memory_reserved(device=None):
        from paddle_tpu.profiler.memory import device_memory_stats
        return device_memory_stats(device).get("bytes_reserved", 0)

    @staticmethod
    def max_memory_reserved(device=None):
        from paddle_tpu.profiler.memory import device_memory_stats
        return device_memory_stats(device).get("peak_bytes_reserved", 0)

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def empty_cache():
        pass  # PJRT owns the allocator; no cache to flush

    @staticmethod
    def current_stream(device=None):
        return Stream(device)

    @staticmethod
    def stream_guard(stream):
        return stream


cuda = _CudaNamespace()
