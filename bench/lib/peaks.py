"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect over 4 links (50 GB/s each).  The engine computes float32 at
``HIGHEST`` (about six bf16 MXU passes), so every share of these peaks is
taken against the bf16 figure on purpose.  A kind missing from the table
is an error, never a default.
"""
from __future__ import annotations

import collections

Peaks = collections.namedtuple("Peaks", ["flops", "hbm_bw", "link_bw"])

PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak figures for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
