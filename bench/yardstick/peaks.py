"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
16 GB of HBM2 at 819 GB/s per chip, 197 TFLOP/s bf16, 393 TOP/s int8.
The f32 stencils here run on the vector units, whose f32 rate is not
published, so only the HBM bandwidth is used: a share of it is a memory
roofline share.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e system architecture",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
