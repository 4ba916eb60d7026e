"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.
The layout kernels run in float32 on the vector units, which these
figures do not bound; a roofline share needs a peak measured in the
same run (PERF.md, Open questions).
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}") from None
