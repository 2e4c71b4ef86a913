"""The trace reduction, on hand-made intervals and on a trace recorded on
the chip (``fixtures/products_trace.json.gz``: two ``Trainer.run`` calls of
``products-b1024``, reduced to plain events by ``trace.load``)."""
import os

import pytest

from benchkit import ROOT

FIXTURE = os.path.join(ROOT, "bench", "tests", "fixtures",
                       "products_trace.json.gz")
# scopes of the hand-made paths below
HAND = ("extract", "spmm", "gemm", "tail")


def _cell_scopes():
    """The scopes products-b1024's per-layer metrics read."""
    from bench import manifest
    return manifest.load_cell("products-b1024", ROOT).scopes


def test_union_merges_overlaps_and_touching_intervals():
    from bench import trace
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == [
        (0, 4), (5, 9)]


def test_exclusive_time_leaves_an_enclosing_op_its_own_time():
    from bench import trace
    # a loop op (0-10) holding two ops, one of them holding another
    own = trace.exclusive([(0, 10), (1, 4), (2, 3), (5, 9)])
    assert own == [3, 2, 1, 4]
    assert trace.exclusive([(0, 2), (3, 5)]) == [2, 2]


def test_scope_of_takes_the_innermost_scope_forward_and_transposed():
    from bench import trace
    assert trace.scope_of("jit(chunk)/while/body/extract/pallas_call",
                          HAND) == "extract"
    assert trace.scope_of(
        "jit(chunk)/while/body/transpose(jvp(shard_map))/spmm/dot",
        HAND) == "spmm"
    assert trace.scope_of("jit(chunk)/tail/gemm/dot_general", HAND) == "gemm"
    assert trace.scope_of("jit(chunk)/while/body/sort", HAND) is None
    # a scope no metric reads is not attributed
    assert trace.scope_of("jit(chunk)/tail/gemm/dot_general",
                          ("extract", "tail")) == "tail"


def test_reduce_on_hand_made_events():
    from bench import trace
    ev = {"devices": [{"name": "/device:TPU:0", "ops": [
        ["a", 100.0, 50.0, "x/extract/k"],
        ["b", 160.0, 20.0, "x/transpose(jvp(y))/spmm/dot"],
        ["c", 300.0, 100.0, "x/sort"]]}],
        "spans": [["bench.window", 0.0, 500.0], ["bench.run", 90.0, 400.0],
                  ["bench.dispatch", 90.0, 20.0]]}
    red = trace.reduce(ev, (0.0, 500.0), HAND)
    assert red["busy_s"] == pytest.approx(170e-9)
    assert red["window_s"] == pytest.approx(500e-9)
    assert red["scope_s"] == pytest.approx({"extract": 50e-9,
                                            "spmm": 20e-9})
    assert [n for n, _ in red["device_ops"]] == ["c [-] sort",
                                                 "a [extract] k",
                                                 "b [spmm] dot"]
    # 0-100 before the first call (the window's loop), 150-160, 180-300
    # and 400-500 inside Trainer.run but outside any dispatch
    assert [(n, round(s * 1e9)) for n, s in red["idle_gaps"]] == [
        ("run", 120), ("window", 100), ("run", 100), ("run", 10)]
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(330e-9)


@pytest.fixture(scope="module")
def recorded():
    from bench import trace
    if not os.path.exists(FIXTURE):
        pytest.fail(f"missing fixture {FIXTURE}")
    ev = trace.load_plain(FIXTURE)
    return ev, trace.window_of(ev["spans"], "bench.window")


def test_recorded_busy_time_is_the_union_of_device_ops(recorded):
    from bench import trace
    ev, (t0, t1) = recorded
    red = trace.reduce(ev, (t0, t1), _cell_scopes())
    ops = sorted((max(s, t0), min(s + d, t1))
                 for _, s, d, _ in ev["devices"][0]["ops"]
                 if s < t1 and s + d > t0)
    covered, end = 0.0, t0
    for s, e in ops:                      # a sweep, independent of union()
        if e > end:
            covered += e - max(s, end)
            end = e
    assert red["busy_s"] == pytest.approx(covered * 1e-9, rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = 100 * (1 - red["busy_s"] / red["window_s"])
    assert 0 <= idle < 100


def test_recorded_scopes_bucket_forward_and_transposed_ops(recorded):
    from bench import trace
    ev, window = recorded
    scopes = _cell_scopes()
    red = trace.reduce(ev, window, scopes)
    for scope in ("extract", "spmm", "gemm", "tail"):
        assert red["scope_s"].get(scope, 0) > 0, scope
    transposed = [p for _, _, _, p in ev["devices"][0]["ops"]
                  if "transpose" in p and trace.scope_of(p, scopes) == "spmm"]
    assert transposed
    assert sum(red["scope_s"].values()) <= red["busy_s"] * (1 + 1e-9)


def test_recorded_top_ops_and_tagged_gaps(recorded):
    from bench import trace
    ev, window = recorded
    red = trace.reduce(ev, window, _cell_scopes(), top=10)
    secs = [s for _, s in red["device_ops"]]
    assert 0 < len(secs) <= 10 and secs == sorted(secs, reverse=True)
    assert sum(secs) <= red["busy_s"] * (1 + 1e-9)
    gaps = red["idle_gaps"]
    assert gaps and len(gaps) <= 10
    assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                          reverse=True)
    assert {n for n, _ in gaps} <= {"dispatch", "run", "window", "none"}
    every = trace.reduce(ev, window, _cell_scopes(),
                         top=10 ** 9)["idle_gaps"]
    assert sum(s for _, s in every) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


def test_scope_paths_read_op_name_metadata_by_instruction():
    from bench import trace
    hlo = "\n".join([
        '  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(chunk)/while/body/extract/gather" '
        'stack_frame_id=3}',
        '  ROOT %sort.2 = s32[4]{0} sort(%x), dimensions={0}, '
        'metadata={op_name="jit(chunk)/while/body/sort"}',
        '  %copy.1 = f32[8]{0} copy(%y)'])
    assert trace.scope_paths(hlo) == {
        "fusion.7": "jit(chunk)/while/body/extract/gather",
        "sort.2": "jit(chunk)/while/body/sort"}
    assert trace.op_name("%fusion.7 = f32[8]{0:T(128)} fusion(f32[8] "
                         "%p), kind=kLoop") == "fusion.7"
