"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.Device.device_kind``.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture table): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2
at 819 GB/s, 1,600 Gbit/s inter-chip interconnect per chip.  JAX
reports a v5e chip's kind as "TPU v5 lite".

A kind that is not in the table is an error: a share of a peak that
silently fell back to another chip's numbers would be wrong.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises KeyError for a kind that is not
    in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to benchmarks/chip/peaks.py with its source") from None
