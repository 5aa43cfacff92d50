"""In-process jax backend bring-up: report the device, or raise.

A run is one process on one machine that either holds the accelerator or
does not. There is nothing to outlast and nobody to hand the chip to: a
probe child would be a second process asking for a chip the parent may
already hold. So bring-up is ``jax.devices()`` in this process, and a
platform that is not the one the caller needs is a typed error — never a
switch to another platform. An entry point that cannot do without the chip
passes ``require="tpu"``; code that is correct on any
platform (the trainer supervisor, the CPU test suite) passes nothing and
gets the device stamp for its records.
"""

from __future__ import annotations

from dataclasses import asdict, dataclass
from typing import Dict, Optional


class BackendUnavailableError(RuntimeError):
    """jax has no backend, or not the platform the caller requires."""


@dataclass(frozen=True)
class BackendInfo:
    """The device as jax reports it (``jax.devices()[0]``)."""

    platform: str
    device_kind: str
    n_devices: int

    def as_dict(self) -> Dict:
        return asdict(self)


def bring_up(require: Optional[str] = None) -> BackendInfo:
    """Initialize the jax backend in this process and describe it.

    ``require`` names the platform the caller cannot do without
    ("tpu"); anything else found raises :class:`BackendUnavailableError`
    naming both. A backend that fails to initialize at all raises the
    same type with jax's reason attached.
    """
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BackendUnavailableError(
            f"jax could not initialize a backend: {e}"
        ) from e
    info = BackendInfo(
        platform=devices[0].platform,
        device_kind=devices[0].device_kind,
        n_devices=len(devices),
    )
    if require is not None and info.platform != require:
        raise BackendUnavailableError(
            f"this run needs a {require} device and jax found "
            f"{info.n_devices} x {info.platform} ({info.device_kind}); "
            "there is no CPU fallback — run it where the chip is"
        )
    return info
