"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default: a roofline share against a guessed peak means
nothing.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s per chip.
The bf16 peak is the one that bounds these fp32 programs: at JAX's
default matmul precision the chip rounds fp32 matmul inputs to bf16
and runs them on the MXU at the bf16 rate.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2**30,
                    "source": "Google Cloud TPU v5e documentation"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
