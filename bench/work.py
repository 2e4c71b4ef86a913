"""Operations and bytes a training step requires, counted from its sizes,
and the chip's peaks. The counts follow the algorithm, not an
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


def model_flops(batch: int, d_in: int, d_hidden: int, num_layers: int,
                num_classes: int, nnz: float) -> float:
    """Forward plus backward operations of one step. A matmul against a
    weight needs the weight's gradient and, unless its input is data, the
    input's; aggregation by the (data) adjacency needs only the input's.
    The input projection's input is the features, so it has no input
    gradient."""
    gemm = 2.0 * batch * d_hidden * d_hidden
    agg = 2.0 * nnz * d_hidden
    w_in = 2.0 * batch * d_in * d_hidden
    w_out = 2.0 * batch * d_hidden * num_classes
    forward = w_in + num_layers * (agg + gemm) + w_out
    backward = w_in + num_layers * (agg + 2 * gemm) + 2 * w_out
    return forward + backward


def extract_bytes(planes: int, row_entries: float, nnz: float) -> float:
    """Bytes the extraction needs per step: each plane reads the CSR
    entries of the sampled rows and writes the sampled block's
    nonzeros."""
    return planes * (row_entries + nnz) * NONZERO


def spmm_work(batch: int, d_hidden: int, num_layers: int,
              nnz: float) -> Dict[str, float]:
    """Operations and bytes of the aggregations of one step, forward and
    the input-gradient transpose: each reads its dense operand and the
    nonzeros and writes its result."""
    passes = 2 * num_layers
    return {"flops": passes * 2.0 * nnz * d_hidden,
            "bytes": passes * (2.0 * batch * d_hidden * F32
                               + nnz * NONZERO)}


def least_seconds(flops: float, nbytes: float,
                  peak: Dict[str, float]) -> float:
    """The roofline: the larger of compute time and memory time at the
    peaks."""
    return max(flops / peak["flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
