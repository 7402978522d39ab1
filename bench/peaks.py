"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.  A device not in the table is an
error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture): per
    # chip 197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM at
    # 819 GB/s.  No float32 peak is published, so the compute bound of
    # float32 work is taken at the bfloat16 peak, which it cannot exceed.
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "flops_dtype": "bfloat16",
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
