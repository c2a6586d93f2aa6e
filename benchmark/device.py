"""Which device ran: the report every result line carries, and the
refusal to run where JAX shows no TPU (copied from chip_smoke.py)."""

from __future__ import annotations

import os
import sys
from typing import Any, Dict, List

NO_TPU = 4


def require_tpu(chips: int) -> List[Any]:
    """The devices, or exit non-zero within seconds: never a fallback."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:
        sys.stderr.write(f"benchmark: JAX found no accelerator: {err}\n")
        sys.exit(NO_TPU)
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < chips:
        sys.stderr.write(
            f"benchmark: needs {chips} TPU chip(s); JAX shows {len(devices)} device(s) "
            f"of platform {platform!r} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
            "The benchmark measures only on the chip.\n"
        )
        sys.exit(NO_TPU)
    return devices


def memory_peak_bytes(devices: List[Any]) -> int:
    """Peak bytes taken on the fullest device, from ``memory_stats()``.

    The TPU runtime keeps two accounts: ``bytes_in_use`` for arrays and
    ``bytes_reserved`` for the scratch space of the programs it runs (an
    executable's temporaries never show in ``peak_bytes_in_use``). Both
    are memory no one else can have, so the peak is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def report(devices: List[Any], peak_bytes: int) -> Dict[str, Any]:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak_bytes),
    }


def memory_lines(devices: List[Any]) -> List[Dict[str, int]]:
    """What each device's allocator reports, for an earlier output line."""
    keys = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved", "peak_bytes_reserved", "bytes_limit")
    return [{k: int((d.memory_stats() or {}).get(k, 0)) for k in keys} for d in devices]
