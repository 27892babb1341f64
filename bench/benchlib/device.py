"""The chip a run is on, its published peaks, and its memory peak."""

from __future__ import annotations

import sys

# Published peaks per chip, keyed by jax's ``device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bf16
        "bytes_per_s": 819e9,        # HBM
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def require_chips(chips: int) -> dict:
    """The first ``chips`` JAX devices must be TPUs; exit non-zero with no
    result otherwise. Returns the device record of the result line."""
    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found platform {platform!r}")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips; JAX found "
                 f"{len(devs)}")
    peaks(kind)
    return {"platform": platform, "kind": kind, "count": chips}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        sys.exit(f"bench: no published peaks for device kind {kind!r}; "
                 "add them to benchlib/device.py PEAKS with their source")
    return PEAKS[kind]


def memory_peak_bytes(chips: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
