"""The chip's peaks, the roofline, and the bytes every kind's extraction
requires. A kind's own operations and bytes are counted in its module
(``bench/models/<kind>.py``). The counts follow the algorithm, not an
implementation: aggregation counts the sampled block's nonzeros, never the
tiles or dense blocks a kernel happens to touch.
"""
from __future__ import annotations

import json
import os
from typing import Dict

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")
F32 = 4
NONZERO = 8          # a sparse entry: a 4-byte value and a 4-byte column


def peaks(device_kind: str, path: str = PEAKS) -> Dict[str, float]:
    """``{"flops_per_s", "hbm_bytes_per_s"}`` of ``device_kind``; a kind
    that is not in the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}


def extract_bytes(planes: int, row_entries: float, nnz: float) -> float:
    """Bytes the extraction needs per step: each plane reads the CSR
    entries of the sampled rows and writes the sampled block's
    nonzeros."""
    return planes * (row_entries + nnz) * NONZERO


def least_seconds(flops: float, nbytes: float,
                  peak: Dict[str, float]) -> float:
    """The roofline: the larger of compute time and memory time at the
    peaks."""
    return max(flops / peak["flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
