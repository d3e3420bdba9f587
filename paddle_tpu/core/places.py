"""Places and device discovery.

Capability equivalent of the reference's Place variant + DeviceContextPool
(reference: paddle/fluid/platform/place.h:25-78, device_context.h:131-173).
On TPU the "device context" is owned by the XLA runtime (PJRT); the framework's
job is discovery, selection, and mesh construction — not stream management.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import jax

from .enforce import InvalidArgumentError, OutOfRangeError


@dataclass(frozen=True)
class Place:
    """A logical device slot: backend kind + index (≙ platform::Place)."""
    kind: str  # "cpu" | "tpu" | "gpu"
    device_id: int = 0

    def __repr__(self):
        return f"{self.kind.upper()}Place({self.device_id})"


def CPUPlace(device_id: int = 0) -> Place:  # noqa: N802  (API parity with reference)
    return Place("cpu", device_id)


def TPUPlace(device_id: int = 0) -> Place:  # noqa: N802
    return Place("tpu", device_id)


_KIND_ALIASES = {
    "tpu": ("tpu",),
    "cpu": ("cpu",),
    "gpu": ("gpu", "cuda", "rocm"),
}


def devices(kind: Optional[str] = None) -> List[jax.Device]:
    """All visible jax devices, optionally filtered by kind (≙ InitDevices,
    reference platform/init.cc:76)."""
    devs = jax.devices()
    if kind is None:
        return devs
    aliases = _KIND_ALIASES.get(kind, (kind,))
    out = [d for d in devs if d.platform in aliases]
    return out


def device_count(kind: Optional[str] = None) -> int:
    return len(devices(kind))


def kind_of(platform: str) -> str:
    """Resolve a jax platform name to its place kind (cuda -> gpu etc.)."""
    for kind, aliases in _KIND_ALIASES.items():
        if platform in aliases:
            return kind
    return platform


def default_place() -> Place:
    """Best available backend: TPU > GPU > CPU."""
    return Place(kind_of(jax.devices()[0].platform), 0)


def place_to_device(place: Place) -> jax.Device:
    devs = devices(place.kind)
    if not devs:
        raise InvalidArgumentError(f"no devices of kind {place.kind!r} visible")
    if place.device_id >= len(devs):
        raise OutOfRangeError(
            f"device_id {place.device_id} out of range for {len(devs)} "
            f"{place.kind} devices")
    return devs[place.device_id]


def is_compiled_with_tpu() -> bool:
    return device_count("tpu") > 0
