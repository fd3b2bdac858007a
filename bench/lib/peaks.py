"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from the program's ``launch/roofline.py::PEAKS`` so that no later
change to the program can move the yardstick. Source: Google Cloud
documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM
at 819 GB/s, 1,600 Gbit/s of ICI per chip. A kind that is not in the
table is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def chip_peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
