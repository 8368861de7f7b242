"""Device identity ("Place") abstraction.

Mirrors the reference's ``Place`` variants (paddle/fluid/platform/place.h) but a
Place here is a facade over a ``jax.Device``. TPU is the first-class device; CPU
is the host fallback (and what tests run on with a virtual multi-device mesh).
"""
from __future__ import annotations

import jax


class Place:
    """Base device identity: a (device_type, device_id) pair bound to a jax.Device."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    @property
    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if _kind_matches(d, self.device_type)]
        if not devs:
            # Fall back to the default backend's devices (e.g. asking for TPU on a
            # CPU-only test host): behave like the reference's CPU-fallback kernel pick.
            devs = jax.devices()
        return devs[self.device_id % len(devs)]

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(Place):  # API-compat alias; maps to the accelerator backend if present.
    device_type = "gpu"


def _kind_matches(dev: jax.Device, device_type: str) -> bool:
    return dev.platform.lower() == device_type


def _default_place() -> Place:
    dev = jax.devices()[0]
    plat = dev.platform.lower()
    if plat == "cpu":
        return CPUPlace(0)
    if plat in ("gpu", "cuda", "rocm"):
        return CUDAPlace(0)
    if plat == "tpu":
        return TPUPlace(0)
    raise RuntimeError(
        f"unknown JAX platform {dev.platform!r}: a TPU is platform 'tpu' "
        f"and nothing else is assumed to be one")


_EXPECTED_PLACE = None


def get_device() -> str:
    p = _get_expected_place()
    return f"{p.device_type}:{p.device_id}"


def set_device(device: str) -> Place:
    global _EXPECTED_PLACE
    if ":" in device:
        kind, idx = device.split(":")
        idx = int(idx)
    else:
        kind, idx = device, 0
    kind = kind.lower()
    cls = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": CUDAPlace, "cuda": CUDAPlace}.get(kind)
    if cls is None:
        raise ValueError(f"Unknown device {device!r}")
    _EXPECTED_PLACE = cls(idx)
    return _EXPECTED_PLACE


def _get_expected_place() -> Place:
    global _EXPECTED_PLACE
    if _EXPECTED_PLACE is None:
        _EXPECTED_PLACE = _default_place()
    return _EXPECTED_PLACE


def is_compiled_with_tpu() -> bool:
    return any(_kind_matches(d, "tpu") for d in jax.devices())


class CUDAPinnedPlace(Place):  # API-compat: pinned host memory has no TPU role
    def __init__(self):
        super().__init__("cpu", 0)


class NPUPlace(Place):  # API-compat alias for custom-device builds
    def __init__(self, idx=0):
        super().__init__("npu", idx)
