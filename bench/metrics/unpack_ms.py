"""Device time per step of the operations under the ``unpack`` scope:
stripping the leading mesh axes that ``shard_map`` leaves on the CSR
shards and the batch (``GraphShards.squeeze_blocks``,
``Minibatch.strip_leading`` in ``core/minibatch.py``)."""

SCOPES = ("unpack",)


def compute(ctx):
    s = ctx["trace"]["scope_s"].get("unpack")
    if not s or ctx["steps"] <= 0:
        return None
    return 1e3 * s / ctx["steps"]
