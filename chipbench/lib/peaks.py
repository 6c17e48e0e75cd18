"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.  The
operators under test compute in fp32, which the MXU runs as several bf16
passes, so the bf16 peak is the upper bound a roofline share is read
against.  A device kind missing from this table is an error, never a
default.
"""

SOURCE = "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM"

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"add it to chipbench/lib/peaks.py with its source"
                       ) from None
