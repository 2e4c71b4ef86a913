"""Least time for the aggregations' required operations and bytes (the
kind's ``spmm_flops`` and ``spmm_bytes``, ``bench/models/gcn.py``
``spmm_work``), whichever bound is larger, over the device time under
``spmm``."""

SCOPES = ("spmm",)


def compute(ctx):
    s = ctx["trace"]["scope_s"].get("spmm")
    if not s or ctx["steps"] <= 0:
        return None
    w, peak = ctx["work"], ctx["peak"]
    least = max(w["spmm_flops"] / peak["flops_per_s"],
                w["spmm_bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least * ctx["steps"] / s
