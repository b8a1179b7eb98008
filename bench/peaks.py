"""The chip's published peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(kind: str, path: str = PATH) -> dict:
    """Peaks of ``kind``; a kind that is not in the table is an error."""
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple:
    """(percent of the roofline, the bound that limits it): the least time
    the chip could take, the larger of flops over peak FLOP/s and bytes
    over peak bytes/s, over the time taken."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
