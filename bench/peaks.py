"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``src/repro/launch/roofline.py`` (``PEAKS``) so that the
yardstick stays with the benchmark. Source: Google Cloud documentation,
"TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
No float64 peak is published: the v5e emulates f64, so no replay
metric is a share of a peak.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The row of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
