"""Least time for the extraction's required bytes (``work.extract_bytes``)
at the chip's HBM peak, over the device time under ``extract``."""

SCOPES = ("extract",)


def compute(ctx):
    s = ctx["trace"]["scope_s"].get("extract")
    if not s or ctx["steps"] <= 0:
        return None
    least = ctx["work"]["extract_bytes"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * least * ctx["steps"] / s
