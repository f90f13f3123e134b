"""The table of peaks and the roofline bound.

Peaks are read from ``peaks.json`` by the ``device_kind`` JAX reports. A
device that is not in the table is an error, not a default.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def device_peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time_s(cost: dict, device_kind: str) -> dict:
    """The least time the chip could take for ``cost`` = {bytes, flops}: the
    larger of bytes over peak bandwidth and operations over the peak bf16
    rate, and which of the two bounds it."""
    peaks = device_peaks(device_kind)
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "bandwidth" if by_bytes >= by_flops else "compute",
            "by_bytes_s": by_bytes, "by_flops_s": by_flops}
