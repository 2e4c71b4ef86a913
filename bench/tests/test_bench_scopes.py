"""The program's scopes (``repro.obs.tracer.PHASES``) against the trace
reader's (``bench.trace.SCOPES``): the compiled chunk of a small cell puts
sampling's sorts under ``sample``, the CSR strip under ``unpack`` and the
optimizer, loss, projection and head under scopes of their own, and no
scope nests in another, so a reader that knows every scope gives the six
it knows now the same time as before."""
import os
import re

import pytest

from benchkit import ROOT, tiny_cell

FIXTURE = os.path.join(ROOT, "bench", "tests", "fixtures",
                       "products_trace.json.gz")
_TOKEN = re.compile(r"[A-Za-z_][A-Za-z0-9_.\-]*")
_DEF = re.compile(r"^\s*(?:ROOT )?%(\S+) = ")


def _phases():
    from repro.obs.tracer import PHASES
    return PHASES


@pytest.fixture(scope="module", params=[False, True],
                ids=["in-step", "prefetch"])
def chunk(request):
    """``(scope paths, names of sort instructions)`` of the compiled chunk
    of products' configuration at the CPU size, sampling in the step or
    prefetched."""
    import jax

    from bench import harness, trace
    cell = tiny_cell()
    cell.traffic["prefetch"] = request.param
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness.TrainCell, "warm_up", lambda self: None)
        tc = harness.TrainCell(cell, jax.devices("cpu"))
        tc.setup()
    hlo = tc.hlo_text(tc.fresh(tc.init(jax.random.PRNGKey(0)), 0))
    sorts = {m.group(1) for line in hlo.splitlines()
             for m in [_DEF.match(line)] if m and " sort(" in line}
    assert sorts
    return trace.scope_paths(hlo), sorts


def _scope(path, scopes):
    from bench import trace
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace, "SCOPES", tuple(scopes))
        return trace.scope_of(path)


def test_every_sort_outside_extract_is_sampling(chunk):
    paths, sorts = chunk
    scopes = [_scope(paths[s], _phases()) for s in sorts]
    assert set(scopes) <= {"sample", "extract"}, scopes
    assert "sample" in scopes


def test_the_csr_strip_is_unpack(chunk):
    paths, _ = chunk
    squeezes = [p for p in paths.values() if p.endswith("/squeeze")]
    unpack = [p for p in squeezes if _scope(p, _phases()) == "unpack"]
    assert unpack
    # no strip is left outside the program's scopes
    assert all(_scope(p, _phases()) for p in squeezes), squeezes


@pytest.mark.parametrize("scope", ["optim", "loss", "proj", "head"])
def test_each_new_scope_holds_ops(chunk, scope):
    paths, _ = chunk
    assert any(_scope(p, _phases()) == scope for p in paths.values())


def test_no_scope_nests_in_another_and_the_old_six_keep_their_ops(chunk):
    from bench import trace
    paths, _ = chunk
    assert set(trace.SCOPES) <= set(_phases())
    for name, path in paths.items():
        assert len(set(_TOKEN.findall(path)) & set(_phases())) <= 1, path
        old = trace.scope_of(path)
        assert old is None or _scope(path, _phases()) == old, (name, path)


def test_recorded_trace_reads_the_same_with_every_scope_known():
    """The chip's fixture (recorded on a program without the new scopes)
    holds no new scope's name, so a reader that knows every scope gives
    the six scopes and the busy time exactly what it gives now."""
    from bench import trace
    ev = trace.load_plain(FIXTURE)
    new = set(_phases()) - set(trace.SCOPES)
    for dev in ev["devices"]:
        for _, _, _, path in dev["ops"]:
            assert not set(_TOKEN.findall(path)) & new, path
    window = trace.window_of(ev["spans"], "bench.window")
    before = trace.reduce(ev, window)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace, "SCOPES", _phases())
        after = trace.reduce(ev, window)
    assert after["scope_s"] == before["scope_s"]
    assert after["busy_s"] == before["busy_s"]
