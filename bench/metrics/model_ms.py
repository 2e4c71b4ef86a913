"""Device time per step of the model's layer scopes (``spmm``, ``gemm``,
``tail``, ``reshard``, ``rotate``), forward and transposed."""

SCOPES = ("spmm", "gemm", "tail", "reshard", "rotate")


def compute(ctx):
    scope = ctx["trace"]["scope_s"]
    s = sum(scope.get(k, 0.0) for k in SCOPES)
    if s <= 0 or ctx["steps"] <= 0:
        return None
    return 1e3 * s / ctx["steps"]
