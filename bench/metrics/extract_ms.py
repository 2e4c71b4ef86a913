"""Device time per step of the operations under the ``extract`` scope."""

SCOPES = ("extract",)


def compute(ctx):
    s = ctx["trace"]["scope_s"].get("extract")
    if not s or ctx["steps"] <= 0:
        return None
    return 1e3 * s / ctx["steps"]
