"""Tests for the telemetry subsystem (``repro.obs``) — ISSUE-6.

* ``Tracer``: span nesting paths, the disabled-mode no-op contract (ONE
  shared null span, near-zero overhead), thread safety under concurrent
  recording, leaf-phase totals.
* ``LatencyHistogram``: bucket-resolved quantiles for a known sequence and
  the EXACT-merge property (merged == single histogram over the
  concatenated observations, bucket for bucket and quantile for quantile).
* ``comm_report``: tier-1 regression pins for the (1,1,1)x1 plan — the
  sampling program compiles with ZERO collectives, and the loss program's
  per-layer collective set is exactly the derived counts (XLA keeps the
  trivial single-participant collectives at mesh size 1, which is what
  makes them countable here).
* ``BenchWriter``/``compare_entries``: the BENCH_<name>.json round-trip
  and the regression/improvement thresholding.
* ``benchmarks.common.time_fn``: the (median, p10, p90) Timing contract
  and the csv -> JSON-writer single-path wiring.
"""
import json
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fourd, gcn_model as M, pipeline as PL
from repro.graphs import build_partitioned_graph, make_synthetic_dataset
from repro.obs import (CommReport, LatencyHistogram, Tracer, comm_report,
                       parse_hlo, shape_bytes)
from repro.obs.bench import BenchWriter, compare_entries, load_bench
from repro.obs.tracer import NULL_SPAN

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from benchmarks import common as bench_common  # noqa: E402


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_tracer_nesting_records_joined_paths():
    t = Tracer()
    with t.span("chunk"):
        with t.span("eval"):
            pass
        with t.span("eval"):
            pass
    with t.span("eval"):
        pass
    s = t.summary()
    assert s["chunk"]["count"] == 1
    assert s["chunk/eval"]["count"] == 2
    assert s["eval"]["count"] == 1
    # leaf totals fold both paths of "eval" together
    assert t.total("eval") == pytest.approx(
        s["chunk/eval"]["total_s"] + s["eval"]["total_s"])
    assert set(t.totals()) == {"chunk", "eval"}


def test_tracer_disabled_is_the_shared_null_span():
    t = Tracer(enabled=False)
    # ONE shared object: no allocation, no clock read, nothing recorded
    assert t.span("x") is NULL_SPAN
    assert t.span("y") is NULL_SPAN
    with t.span("x"):
        pass
    t.record("x", 1.0)
    assert t.summary() == {} and t.totals() == {}


def test_tracer_disabled_overhead_near_zero():
    on, off = Tracer(enabled=True), Tracer(enabled=False)
    N = 20000

    def loop(tr):
        t0 = time.perf_counter()
        for _ in range(N):
            with tr.span("p"):
                pass
        return time.perf_counter() - t0

    loop(off), loop(on)                     # warm both paths
    t_off, t_on = loop(off), loop(on)
    # the disabled path must be much cheaper than live spans; generous
    # bound so CI noise can't flake it
    assert t_off < t_on
    assert t_off / N < 2e-6, f"{t_off / N * 1e9:.0f} ns per disabled span"


def test_tracer_thread_safety():
    t = Tracer()
    errs = []

    def worker(name):
        try:
            for _ in range(500):
                with t.span(name):
                    with t.span("inner"):
                        pass
        except Exception as exc:            # pragma: no cover
            errs.append(exc)

    threads = [threading.Thread(target=worker, args=(f"w{i}",))
               for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs
    s = t.summary()
    for i in range(4):
        # stacks are thread-local: every thread nests under its OWN name
        assert s[f"w{i}"]["count"] == 500
        assert s[f"w{i}/inner"]["count"] == 500
    assert t.total("inner") > 0.0


def test_tracer_record_external_duration():
    t = Tracer()
    t.record("ckpt_io", 0.25)
    t.record("ckpt_io", 0.75)
    s = t.summary()["ckpt_io"]
    assert s["count"] == 2 and s["total_s"] == pytest.approx(1.0)
    assert s["max_ms"] == pytest.approx(750.0)


# ---------------------------------------------------------------------------
# LatencyHistogram
# ---------------------------------------------------------------------------

def test_histogram_quantiles_known_sequence():
    h = LatencyHistogram()
    lat = [0.001, 0.002, 0.003, 0.004, 0.100]       # seconds
    for x in lat:
        h.observe(x)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["mean_ms"] == pytest.approx(22.0)
    assert snap["max_ms"] == pytest.approx(100.0)
    # bucket resolution is 2**(1/4) ~ 19%: quantiles land in the right
    # bucket's upper edge, never below the true value, never 19% above
    assert 0.003 <= h.quantile(0.5) <= 0.003 * 2 ** 0.25
    assert h.quantile(0.99) == pytest.approx(0.100)  # clamped to exact max


def test_histogram_merge_is_exact():
    rng = np.random.default_rng(0)
    a_lat = rng.exponential(0.005, size=300)
    b_lat = rng.exponential(0.050, size=170)
    a, b, whole = (LatencyHistogram(), LatencyHistogram(),
                   LatencyHistogram())
    for x in a_lat:
        a.observe(float(x))
        whole.observe(float(x))
    for x in b_lat:
        b.observe(float(x))
        whole.observe(float(x))
    m = a.merge(b)
    # EXACT: bucket counts add, so the merged histogram is indistinguishable
    # from one built over the concatenated sequence — including p99
    assert m.counts == whole.counts
    assert m.count == whole.count == 470
    assert m.sum == pytest.approx(whole.sum)
    assert m.min == whole.min and m.max == whole.max
    for q in (0.5, 0.9, 0.95, 0.99):
        assert m.quantile(q) == whole.quantile(q)
    # approx only because sum accumulates in a different order
    assert m.snapshot() == pytest.approx(whole.snapshot())


def test_histogram_empty_snapshot():
    snap = LatencyHistogram().snapshot()
    assert snap == {"count": 0, "mean_ms": 0.0, "p50_ms": 0.0,
                    "p95_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}


# ---------------------------------------------------------------------------
# HLO comm accounting
# ---------------------------------------------------------------------------

def test_shape_bytes():
    assert shape_bytes("f32[64,32]") == 64 * 32 * 4
    assert shape_bytes("bf16[128]") == 128 * 2
    assert shape_bytes("(f32[8,8], s32[8])") == 8 * 8 * 4 + 8 * 4
    assert shape_bytes("pred[]") == 1


def test_parse_hlo_counts_async_pairs_once():
    txt = """
  %ag-start = (f32[32,8], f32[64,8]) all-gather-start(f32[32,8] %p), dims={0}
  %ag-done = f32[64,8] all-gather-done((f32[32,8], f32[64,8]) %ag-start)
  %ar = f32[16,16] all-reduce(f32[16,16] %q), to_apply=%sum
  ROOT %cp = f32[4,4] collective-permute(f32[4,4] %r), pairs={{0,1}}
"""
    r = parse_hlo(txt)
    assert r.counts["all-gather"] == 1          # -start/-done pair = ONE op
    assert r.counts["all-reduce"] == 1
    assert r.counts["collective-permute"] == 1
    assert r.bytes["all-reduce"] == 16 * 16 * 4
    assert r.bytes["collective-permute"] == 4 * 4 * 4
    assert r.total_count == 3
    assert r.kinds() == ("all-reduce", "all-gather", "collective-permute")


def test_comm_report_str_and_assert():
    empty = CommReport(counts={}, bytes={})
    assert "no collectives" in str(empty)
    assert empty.assert_no_collectives() is empty
    busy = CommReport(counts={"all-reduce": 2}, bytes={"all-reduce": 64})
    with pytest.raises(AssertionError, match="NOT communication-free"):
        busy.assert_no_collectives("sampling")


@pytest.fixture(scope="module")
def tiny_plan():
    ds = make_synthetic_dataset(n=256, num_classes=4, d_in=16,
                                avg_degree=8, seed=0)
    pg = build_partitioned_graph(ds, g=1)
    cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                      dropout=0.0)
    mesh = fourd.make_mesh_4d(1, 1)
    plan = fourd.build_plan(pg, cfg, mesh, batch=64)
    graph = plan.shard_graph(pg)
    params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
    return cfg, plan, graph, params


def test_sampling_compiles_with_zero_collectives_1x1x1(tiny_plan):
    """Tier-1 pin of the paper's central invariant at the (1,1,1)x1 plan:
    even the trivial mesh lowers the sampling program with NO collective
    ops of any kind."""
    _, plan, graph, _ = tiny_plan
    sample_fn, _ = PL.make_pipeline_fns(plan)
    r = comm_report(sample_fn, graph, jnp.asarray(0), jnp.asarray(0))
    r.assert_no_collectives("sampling")
    assert r.total_bytes == 0


def test_loss_collective_set_pinned_1x1x1(tiny_plan):
    """The expected per-layer collective set of the (1,1,1)x1 loss program.

    XLA retains the single-participant collectives at mesh size 1, so the
    fwd+bwd communication structure is countable. The lowered program is
    exactly linear in L (measured across num_layers in {2, 3, 4}): 8
    all-reduces per layer (the PMM psums of forward SpMM/GEMM, their
    backward transposes, and the rmsnorm reductions) plus 12 fixed
    (input/output projections, loss/count reductions, DP gradient psum);
    the gather reshard of the residual contributes 2 all-gathers per layer
    (row + col axis) whose gradient transposes are the 2 reduce-scatters
    per layer. Nothing else.

    The compiled program holds 2L + 3 fewer all-reduce ops, 6L + 9: XLA's
    all-reduce combiner merges independent reductions into tuple
    all-reduces — the DP-gradient psums of the 2L layer parameters into
    one, those of w_in/w_out into one, the loss sum with its count, and two
    pairs of backward norm/loss reductions. The combiner arrived with the
    JAX 0.9 toolchain (jax 0.4.37 compiled all 8L + 12 as separate ops);
    no collective went missing. A change to either count means the
    engine's communication structure changed — which is exactly what this
    pin exists to catch."""
    cfg, plan, graph, params = tiny_plan
    loss_fn = fourd.make_loss_fn(plan, train=True)

    def mean_loss(p, g_, s):
        return loss_fn(p, g_, s).mean()

    grad_fn = jax.jit(jax.grad(mean_loss))
    L = cfg.num_layers
    lowered = grad_fn.lower(params, graph, jnp.asarray(0)).as_text()
    assert lowered.count("stablehlo.all_reduce") == 8 * L + 12
    r = comm_report(grad_fn, params, graph, jnp.asarray(0))
    assert r.counts["all-reduce"] == 6 * L + 9, r
    assert r.counts["all-gather"] == 2 * L, r
    assert r.counts["reduce-scatter"] == 2 * L, r
    assert r.counts["all-to-all"] == 0, r
    assert r.counts["collective-permute"] == 0, r
    assert r.kinds() == ("all-reduce", "all-gather", "reduce-scatter")


# ---------------------------------------------------------------------------
# BenchWriter / compare
# ---------------------------------------------------------------------------

def test_bench_writer_roundtrip(tmp_path):
    w = BenchWriter("demo", config={"n": 8})
    w.add("fast", 100.0, p10_us=90.0, p90_us=110.0, derived="x=1")
    w.add("comm", 50.0, comm_bytes=4096)
    path = w.write(str(tmp_path))
    assert os.path.basename(path) == "BENCH_demo.json"
    doc = load_bench(path)
    assert doc["schema"] == 1 and doc["name"] == "demo"
    assert doc["config"] == {"n": 8}
    assert doc["git_sha"] and doc["timestamp"]
    assert doc["entries"][0] == {"name": "fast", "median_us": 100.0,
                                 "p10_us": 90.0, "p90_us": 110.0,
                                 "derived": "x=1"}
    assert doc["entries"][1]["comm_bytes"] == 4096


def test_compare_entries_thresholding():
    base = {"entries": [
        {"name": "a", "median_us": 100.0, "p10_us": 90.0, "p90_us": 110.0},
        {"name": "b", "median_us": 100.0, "p10_us": 90.0, "p90_us": 110.0},
        {"name": "c", "median_us": 100.0, "p10_us": 90.0, "p90_us": 110.0},
        {"name": "gone", "median_us": 5.0},
        {"name": "z0", "median_us": 0.0},
        {"name": "z1", "median_us": 0.0},
    ]}
    cur = {"entries": [
        {"name": "a", "median_us": 200.0},     # 2.0x, above p90 band -> reg
        {"name": "b", "median_us": 120.0},     # within threshold -> ok
        {"name": "c", "median_us": 40.0},      # 0.4x, below p10 band -> imp
        {"name": "new", "median_us": 1.0},     # no baseline -> REPORTED
        {"name": "z0", "median_us": 0.0},      # zero stayed zero -> ok
        {"name": "z1", "median_us": 8.0},      # zero grew -> regression
    ]}
    rows = {r["name"]: r["status"]
            for r in compare_entries(cur, base, threshold=0.30)}
    # "new" used to be dropped silently, letting a renamed metric dodge the
    # gate; a zero baseline means "stays zero" (byte/count metrics)
    assert rows == {"a": "regression", "b": "ok", "c": "improvement",
                    "new": "unbaselined", "z0": "ok", "z1": "regression"}


# ---------------------------------------------------------------------------
# benchmarks.common: Timing + the single csv -> JSON path
# ---------------------------------------------------------------------------

def test_time_fn_returns_timing_tuple():
    f = jax.jit(lambda x: x + 1)
    t = bench_common.time_fn(f, jnp.zeros(4), iters=7)
    assert t.p10 <= t.median <= t.p90
    assert t.median > 0


def test_csv_feeds_the_bench_writer(capsys):
    w = bench_common.set_bench("unit", knob=3)
    try:
        t = bench_common.Timing(median=10.0, p10=9.0, p90=11.0)
        bench_common.csv("row_a", t, "d=x", comm_bytes=128)
        bench_common.csv("row_b", 5.0)          # bare float still accepted
        out = capsys.readouterr().out
        assert "row_a,10.0,d=x" in out and "row_b,5.0," in out
        entries = {e.name: e for e in w.entries}
        assert entries["row_a"].p90_us == 11.0
        assert entries["row_a"].comm_bytes == 128
        assert entries["row_b"].p10_us is None
        doc = w.to_dict()
        assert doc["config"] == {"knob": 3}
        json.dumps(doc)                         # fully serializable
    finally:
        bench_common._WRITER = None             # don't leak into atexit
