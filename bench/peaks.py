"""The chip's peaks, keyed by ``device_kind`` (``bench/peaks.json``)."""
from __future__ import annotations

import json
from pathlib import Path

_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip; an unknown kind raises."""
    table = json.loads(_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, pk: dict):
    """The least time the work allows, and which bound sets it."""
    t_c, t_m = flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
