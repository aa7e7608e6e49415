"""The chip check and the device facts a result line carries."""
from __future__ import annotations

import sys


def require_chips(n: int):
    """The first ``n`` TPU devices; exits 2, printing no result, when JAX
    finds no TPU or fewer than ``n`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devs[0].platform!r} "
              f"({devs[0].device_kind})", file=sys.stderr)
        sys.exit(2)
    if len(devs) < n:
        print(f"bench: the cell needs {n} chips, JAX found {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    return devs[:n]


def peak_bytes(devs) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devs`` (0 where the
    backend reports none)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def describe(devs, memory_peak: int) -> dict:
    import jax
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
