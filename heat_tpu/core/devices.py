"""Device abstraction over JAX platforms.

TPU-native re-design of the reference device layer (reference:
heat/core/devices.py:17-167, `Device`, `cpu`, `gpu`, `get_device`,
`sanitize_device`, `use_device`). The reference binds each MPI rank to one
torch device (GPU picked round-robin by rank, devices.py:100). Here a
``Device`` names a JAX *platform* whose device set backs the arrays; the
actual placement of shards onto the platform's chips is owned by the
:class:`~heat_tpu.core.communication.Communication` mesh, not by the device —
on TPU the "one rank = one chip" pairing of the reference is replaced by
"one mesh = all chips".
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Union

import jax

__all__ = [
    "Device", "cpu", "get_device", "sanitize_device", "use_device",
    "ChipPeaks", "chip_peaks",
]


class ChipPeaks(NamedTuple):
    """Published per-chip peaks: the denominators of every MFU and
    roofline share this repo reports."""

    bf16_flops: float       # FLOP/s
    int8_ops: float         # OP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


# THE peak table, keyed by ``jax.Device.device_kind``. One row per chip a
# builder can reach. Source: Google Cloud documentation, "TPU v5e" (197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip).
_CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(197e12, 393e12, 819e9, 16e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; an unknown kind is an error, never a
    default — a utilization against a guessed peak is not a measurement."""
    try:
        return _CHIP_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(_CHIP_PEAKS)}); add a sourced row to "
            "heat_tpu/core/devices.py before reporting utilization on it"
        ) from None


class Device:
    """A logical compute platform backing DNDarray storage.

    Parameters
    ----------
    device_type : str
        Platform name understood by ``jax.devices()`` — ``"cpu"``, ``"tpu"``,
        ``"gpu"``. A platform that is not attached raises at first use:
        ``"tpu"`` never stands for another platform.
    device_id : int, optional
        Index of a specific device of that platform; ``None`` means the whole
        platform (all chips — the normal, mesh-backed mode).
    """

    def __init__(self, device_type: str, device_id: Optional[int] = None):
        self.__device_type = device_type
        self.__device_id = device_id

    @property
    def device_type(self) -> str:
        return self.__device_type

    @property
    def device_id(self) -> Optional[int]:
        return self.__device_id

    def jax_devices(self) -> List["jax.Device"]:
        """All JAX devices belonging to this platform (one-element list if a
        specific ``device_id`` was requested); ``RuntimeError`` (from JAX,
        naming the platform) when it is not attached."""
        devs = jax.devices(self.__device_type)
        if self.__device_id is not None:
            return [devs[self.__device_id]]
        return devs

    @property
    def jax_device(self) -> "jax.Device":
        """The first (or the requested) JAX device of this platform."""
        return self.jax_devices()[0]

    def __eq__(self, other) -> bool:
        if isinstance(other, Device):
            return (
                self.device_type == other.device_type and self.device_id == other.device_id
            )
        return NotImplemented

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self) -> str:
        return f"device({self.__str__()!r})"

    def __str__(self) -> str:
        if self.__device_id is None:
            return self.__device_type
        return f"{self.__device_type}:{self.__device_id}"


# platform singletons ---------------------------------------------------------

cpu = Device("cpu")
"""The CPU platform (always available)."""

# The default device is JAX's default backend (the TPU where one is attached
# and JAX_PLATFORMS allows it); resolved lazily so that test harnesses can
# force ``jax_platforms=cpu`` before first array use.
__default_device: Optional[Device] = None


def get_device() -> Device:
    """The currently globally-set default device (reference devices.py:125)."""
    global __default_device
    if __default_device is None:
        __default_device = Device(jax.default_backend())
    return __default_device


def sanitize_device(device: Optional[Union[str, Device]]) -> Device:
    """Map a device specifier (None/str/Device) onto a Device object
    (reference devices.py:128-154)."""
    if device is None:
        return get_device()
    if isinstance(device, Device):
        return device
    if isinstance(device, str):
        spec = device.strip().lower()
        if ":" in spec:
            dtype, _, did = spec.partition(":")
            dev = Device(dtype, int(did))
        else:
            dev = Device(spec)
        # validate platform exists now rather than at first use
        dev.jax_devices()
        return dev
    raise ValueError(f"Unknown device, must be str or Device, got {device!r}")


def use_device(device: Optional[Union[str, Device]] = None) -> None:
    """Set the globally-used default device (reference devices.py:157)."""
    global __default_device
    __default_device = sanitize_device(device)
