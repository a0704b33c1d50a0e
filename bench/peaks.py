"""Peak rates of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  Source: Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "https://cloud.google.com/tpu/docs/v5e",
    },
}


def peaks_of(device_kind: str) -> dict:
    """The peak table of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
