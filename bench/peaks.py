"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from ``benchmarks/roofline.py:PEAKS`` so that the yardstick lives with
the benchmark.  A device kind that is not in the table is an error, never a
default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, \"TPU v5e\"",
    },
}


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises on an unknown kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[device_kind]
