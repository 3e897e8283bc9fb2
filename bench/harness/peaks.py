"""Published peaks of each accelerator the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never a
default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bf16 matmul peak of one chip
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; raises ``UnknownDevice``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
