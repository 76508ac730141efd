"""Published per-chip peak rates, keyed by ``jax.Device.device_kind``.

Source for "TPU v5 lite" (the kind JAX reports for a TPU v5e): Google Cloud
documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM
at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (four links, 50 GB/s
each). A device kind without a row has no peaks to divide by: looking it
up raises instead of borrowing another chip's numbers.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,        # FLOP/s per chip
        "hbm_bw": 819e9,             # bytes/s per chip
        "hbm_bytes": 16e9,           # bytes per chip
        "ici_bw": 50e9,              # bytes/s per link
    },
}


def device_peaks(kind: str) -> dict:
    """The peak-rate row for ``device_kind`` ``kind``."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peak rates for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
