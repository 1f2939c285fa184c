"""Published peaks of each accelerator the benchmark accepts, keyed by the
``device_kind`` JAX reports. A device missing here is refused, never
given a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class DeviceRefused(RuntimeError):
    """The run found no accelerator it can measure."""


def check_devices(devices, chips: int) -> dict:
    """The peaks of ``devices[0]``; raises :class:`DeviceRefused` unless
    there are at least ``chips`` TPUs of a kind listed in :data:`PEAKS`."""
    if not devices:
        raise DeviceRefused("JAX found no device")
    d = devices[0]
    if d.platform != "tpu":
        raise DeviceRefused(f"JAX's first device is {d.platform!r} "
                            f"({d.device_kind}); the benchmark runs on a TPU")
    if d.device_kind not in PEAKS:
        raise DeviceRefused(f"no published peaks for {d.device_kind!r}")
    if len(devices) < chips:
        raise DeviceRefused(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return PEAKS[d.device_kind]
