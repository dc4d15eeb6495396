"""Device API (reference: python/paddle/device/__init__.py).

TPU is the first-class accelerator. CUDA entry points exist for API
parity and report unavailability — zero CUDA in this framework.
"""
from __future__ import annotations

import contextlib

import jax

from .._core.tensor import Place

_current_device = None


class CPUPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class TPUPlace(Place):
    def __init__(self, idx=0):
        super().__init__("tpu", idx)


class CUDAPlace(Place):  # parity shim
    def __init__(self, idx=0):
        super().__init__("gpu", idx)


class CUDAPinnedPlace(Place):
    def __init__(self):
        super().__init__("cpu", 0)


class XPUPlace(Place):
    def __init__(self, idx=0):
        super().__init__("tpu", idx)


def _platform():
    # a backend that fails to initialise raises: answering "cpu" would
    # hide a broken accelerator behind a CPU run
    return jax.default_backend()


def set_device(device):
    """Select "cpu" or the accelerator jax runs on ("tpu:0"; "gpu" and
    "xpu" are parity spellings of the same). Asking for an accelerator
    when jax runs on the CPU raises — it never quietly becomes "cpu"."""
    global _current_device
    if not str(device).startswith("cpu") and _platform() == "cpu":
        raise ValueError(
            f"set_device({device!r}): jax runs on the CPU here, there is "
            "no accelerator to select (JAX_PLATFORMS picks the platform)")
    _current_device = str(device)
    return get_device()


def get_device():
    if _current_device and _current_device.startswith("cpu"):
        return "cpu"
    plat = _platform()
    return f"{plat}:0" if plat != "cpu" else "cpu"


def get_all_device_type():
    return list({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return ["tpu"] if _platform() == "tpu" else []


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [f"tpu:{d.id}" for d in jax.devices()] if _platform() == "tpu" else []


def device_count():
    return jax.device_count()


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    return False  # XLA is the compiler; CINN does not exist here


def is_compiled_with_distribute():
    return True


def is_compiled_with_custom_device(device_type="tpu"):
    return device_type in ("tpu", "npu") and _platform() == "tpu"


def synchronize(device=None):
    """Block until all queued device work completes (TPU: drain async dispatch)."""
    try:
        (jax.device_put(0) + 0).block_until_ready()
    except Exception:
        pass


class Stream:
    """Parity shim: XLA:TPU executes a single ordered stream per core."""

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

    def query(self):
        return True


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


@contextlib.contextmanager
def stream_guard(stream):
    yield


from . import cuda  # noqa: E402


def get_cudnn_version():
    """reference: device.get_cudnn_version — None when no cuDNN (always,
    on a TPU build)."""
    return None


class IPUPlace:
    """Another vendor's accelerator: importable for API parity, unusable
    by design (see static.ipu_shard_guard)."""

    def __init__(self, *a):
        pass

    def __repr__(self):
        return "IPUPlace() [unsupported on the TPU build]"


def set_stream(stream=None):
    """reference: device.set_stream — XLA owns stream scheduling on TPU;
    accepted and ignored (returns the previous 'stream', i.e. None)."""
    return None
