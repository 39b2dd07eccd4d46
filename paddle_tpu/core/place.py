"""Place / device addressing.

Analog of phi::Place (paddle/phi/common/place.h) and paddle.device: places name
jax devices. On TPU there is no per-op device dispatch — placement is realized
through jax default_device / shardings — so Place is a thin addressing record
kept for API parity plus a handle to the backing jax device.
"""

from __future__ import annotations

import functools

import jax


class Place:
    """Base device address: a backend kind plus a device index."""

    kind = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"

    def __eq__(self, other):
        return isinstance(other, Place) and self.kind == other.kind and self.device_id == other.device_id

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def jax_device(self):
        """Resolve to a jax.Device, falling back to the default backend."""
        devs = _devices_for_kind(self.kind)
        if not devs:
            devs = jax.devices()
        return devs[self.device_id % len(devs)]

    def is_cpu_place(self):
        return self.kind == "cpu"

    def is_tpu_place(self):
        return self.kind == "tpu"

    def is_gpu_place(self):  # API parity; never true on this stack
        return self.kind == "gpu"


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    kind = "tpu"


class CUDAPlace(Place):
    """Accepted for API compatibility; resolves to the default accelerator."""

    kind = "gpu"


class XPUPlace(Place):
    kind = "xpu"


class IPUPlace(Place):
    kind = "ipu"


class NPUPlace(Place):
    kind = "npu"


class MLUPlace(Place):
    kind = "mlu"


class CUDAPinnedPlace(Place):
    """Pinned host memory place; host arrays are always transfer-ready here."""

    kind = "cuda_pinned"

    def __init__(self):
        super().__init__(0)


class CustomPlace(Place):
    def __init__(self, dev_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.kind = dev_type


def platform() -> str:
    """Platform of the device programs are being built for — the ONE device
    predicate every kernel gate reads. Inside ``jax.set_mesh`` it is the
    mesh's device (so AOT-compiling for a TPU topology from a CPU host picks
    the TPU kernels); otherwise the default backend. A backend that fails to
    initialize raises here: it must never select a reference path."""
    dev = jax.sharding.get_abstract_mesh().abstract_device
    if dev is None:
        return jax.default_backend()
    kind = dev.device_kind
    return "tpu" if kind.startswith("TPU") else kind.lower()


def on_tpu() -> bool:
    return platform() == "tpu"


def pallas_interpret() -> bool:
    """Pallas interpret mode is for CPU tests only: compiled Mosaic on TPU,
    the interpreter on cpu, and an error anywhere else (no kernel may run
    silently interpreted on a device nobody asked for)."""
    plat = platform()
    if plat == "tpu":
        return False
    if plat == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on tpu and interpreted on cpu; "
        f"platform {plat!r} is unsupported")


def is_compile_only(device) -> bool:
    """True for a device of ``jax.experimental.topologies``: programs can be
    compiled for it (AOT, from a host without the chip), arrays cannot be
    put on it."""
    return device.client.runtime_type == "compile_only_runtime"


def _devices_for_kind(kind: str):
    """Devices of one platform; [] when that platform has no backend here
    (jax raises RuntimeError for an unknown/absent backend name)."""
    try:
        return jax.devices(kind)
    except RuntimeError:
        return []


@functools.lru_cache(maxsize=None)
def _default_place() -> Place:
    plat = jax.default_backend()
    if plat == "tpu":
        return TPUPlace(0)
    if plat == "gpu":
        return CUDAPlace(0)
    return CPUPlace(0)


_current_place = None


def set_device(device) -> Place:
    """paddle.set_device analog: 'tpu', 'tpu:0', 'cpu', Place instance."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return device
    name = str(device)
    idx = 0
    if ":" in name:
        name, idx_s = name.split(":", 1)
        idx = int(idx_s)
    kind_map = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": CUDAPlace, "cuda": CUDAPlace, "xpu": XPUPlace}
    cls = kind_map.get(name)
    if cls is None:
        _current_place = CustomPlace(name, idx)
    else:
        _current_place = cls(idx)
    return _current_place


def get_device() -> str:
    place = _current_place or _default_place()
    return f"{place.kind}:{place.device_id}"


def current_place() -> Place:
    return _current_place or _default_place()


def device_count(kind: str = None) -> int:
    if kind is None:
        kind = (_current_place or _default_place()).kind
    return len(_devices_for_kind(kind)) or 1


def is_compiled_with_tpu() -> bool:
    return len(_devices_for_kind("tpu")) > 0


def is_compiled_with_cuda() -> bool:
    return False
