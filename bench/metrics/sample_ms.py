"""Device time per step of the operations under the ``sample`` scope:
drawing the step's vertex sample (``MinibatchBuilder.sample_ids`` in
``core/minibatch.py`` and the draws of ``core/sampling.py``: key,
permutation of every vertex id, sorted sample)."""

SCOPES = ("sample",)


def compute(ctx):
    s = ctx["trace"]["scope_s"].get("sample")
    if not s or ctx["steps"] <= 0:
        return None
    return 1e3 * s / ctx["steps"]
