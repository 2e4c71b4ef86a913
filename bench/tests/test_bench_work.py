"""Work and peak arithmetic against hand counts (the shared part in
``bench/work.py``, the GCN's in ``bench/models/gcn.py``), the per-layer
metric readers on a hand-made context, and the reference's sampled-block
counts against a count made by brute force."""
import importlib
import math

import numpy as np
import pytest

from benchkit import ROOT  # noqa: F401  (puts the checkout on the path)

work = importlib.import_module("bench.work")
gcn = importlib.import_module("bench.manifest").load_model("gcn")


def test_model_flops_by_hand():
    # B=2, d_in=3, d_h=4, one layer, 5 classes, 6 sampled nonzeros
    w_in, agg, gemm, w_out = 2 * 2 * 3 * 4, 2 * 6 * 4, 2 * 2 * 4 * 4, \
        2 * 2 * 4 * 5
    forward = w_in + agg + gemm + w_out                  # 240
    backward = w_in + agg + 2 * gemm + 2 * w_out         # 384
    assert forward + backward == 624
    assert gcn.model_flops(2, 3, 4, 1, 5, 6) == 624


def test_model_flops_grow_with_layers_by_one_layer_each():
    one = gcn.model_flops(1024, 100, 256, 1, 47, 5000)
    three = gcn.model_flops(1024, 100, 256, 3, 47, 5000)
    # per layer: aggregation forward and transposed, GEMM forward and two
    # gradients
    layer = 2 * (2 * 5000 * 256) + 3 * (2 * 1024 * 256 * 256)
    assert three - one == pytest.approx(2 * layer)


def test_extract_and_spmm_work_by_hand():
    assert work.extract_bytes(3, 10, 4) == 3 * (10 + 4) * 8
    sp = gcn.spmm_work(2, 4, 1, 6)
    assert sp["flops"] == 2 * (2 * 6 * 4)
    assert sp["bytes"] == 2 * (2 * 2 * 4 * 4 + 6 * 8)


def test_least_seconds_takes_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(200.0, 10.0, peak) == 2.0
    assert work.least_seconds(100.0, 50.0, peak) == 5.0


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    p = work.peaks("TPU v5 lite")
    assert p == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def _ctx(scopes, busy=0.75, window=1.0, steps=100):
    return {"trace": {"window_s": window, "busy_s": busy, "devices": 1,
                      "scope_s": scopes},
            "steps": steps,
            "work": {"model_flops": 1e9, "extract_bytes": 1e6,
                     "spmm_flops": 1e8, "spmm_bytes": 1e7},
            "peak": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def test_metric_readers_on_a_hand_context():
    from bench import manifest
    m = {n: manifest.load_metric(n, "").compute
         for n in ("idle_share", "mfu", "extract_ms", "model_ms",
                   "roofline.extract", "roofline.spmm", "sample_ms",
                   "unpack_ms")}
    ctx = _ctx({"extract": 0.5, "spmm": 0.1, "gemm": 0.05, "tail": 0.05,
                "sample": 0.44, "unpack": 0.22})
    assert m["idle_share"](ctx) == pytest.approx(25.0)
    assert m["mfu"](ctx) == pytest.approx(100 * 1e9 * 100 / 1.0 / 1e12)
    assert m["extract_ms"](ctx) == pytest.approx(5.0)
    assert m["model_ms"](ctx) == pytest.approx(2.0)
    # 1e6 bytes at 1e9 B/s = 1 ms per step against 5 ms measured
    assert m["roofline.extract"](ctx) == pytest.approx(20.0)
    # max(1e8/1e12, 1e7/1e9) = 10 ms per step against 1 ms measured
    assert m["roofline.spmm"](ctx) == pytest.approx(1000.0)
    assert m["sample_ms"](ctx) == pytest.approx(4.4)
    assert m["unpack_ms"](ctx) == pytest.approx(2.2)


def test_metric_readers_return_nothing_without_their_scope():
    from bench import manifest
    ctx = _ctx({})
    for n in ("extract_ms", "model_ms", "roofline.extract",
              "roofline.spmm", "sample_ms", "unpack_ms"):
        assert manifest.load_metric(n, "").compute(ctx) is None


def test_reference_block_and_counts_against_brute_force():
    import jax.numpy as jnp
    from bench import graphgen, reference
    g = graphgen.generate(512, 4, 8, 6.0, seed=3)
    a = g.adj_norm
    model = reference.Sizes(n=512, batch=64, max_row_nnz=g.max_row_nnz,
                            program_seed=5)
    s = np.asarray(reference.sample(model, jnp.int32(7)))
    assert s.shape == (64,) and np.all(np.diff(s) > 0)
    arrays = (jnp.asarray(a.indptr), jnp.asarray(a.indices),
              jnp.asarray(a.data))
    blk = np.asarray(reference.block(model, *arrays, jnp.asarray(s)))
    dense = np.zeros((512, 512), np.float64)
    for r in range(512):
        lo, hi = a.indptr[r], a.indptr[r + 1]
        dense[r, a.indices[lo:hi]] = a.data[lo:hi]
    want = dense[np.ix_(s, s)] * (511 / 63)
    want[np.arange(64), np.arange(64)] = np.diag(dense)[s]
    np.testing.assert_allclose(blk, want, rtol=1e-6)
    nnz, rows = reference.block_counts(model, arrays[0], arrays[1],
                                       jnp.asarray(s))
    assert int(nnz) == int(np.count_nonzero(dense[np.ix_(s, s)]))
    assert int(rows) == int(sum(a.indptr[r + 1] - a.indptr[r] for r in s))


def test_generator_is_symmetric_normalised_with_self_loops():
    from bench import graphgen
    g = graphgen.generate(300, 3, 4, 10.0, seed=1)
    a = g.adj_norm
    dense = np.zeros((300, 300))
    for r in range(300):
        lo, hi = a.indptr[r], a.indptr[r + 1]
        dense[r, a.indices[lo:hi]] = a.data[lo:hi]
    np.testing.assert_allclose(dense, dense.T, rtol=1e-6)
    deg = np.diff(a.indptr)
    np.testing.assert_allclose(np.diag(dense), 1.0 / deg, rtol=1e-6)
    assert math.isclose(g.mean_degree, (a.nnz - 300) / 300)
    again = graphgen.generate(300, 3, 4, 10.0, seed=1)
    assert np.array_equal(again.adj_norm.indices, a.indices)
    assert np.array_equal(again.features, g.features)


def test_generator_draws_the_programs_sbm():
    """The benchmark's generator and the program's
    ``make_synthetic_dataset(kind="sbm")`` draw the same graph model: the
    same mean degree, spread of degrees, share of entries inside a
    community, community sizes and feature noise."""
    from bench import graphgen
    from repro.graphs.synthetic import make_synthetic_dataset
    n, k, d, deg = 8192, 8, 16, 24.0
    ours = graphgen.generate(n, k, d, deg, seed=5)
    prog = make_synthetic_dataset(n=n, num_classes=k, d_in=d, kind="sbm",
                                  avg_degree=int(deg), seed=5)

    def stats(indptr, indices, data, labels, feats):
        rows = np.repeat(np.arange(n), np.diff(indptr))
        off = rows != indices
        per_row = np.bincount(rows[off], minlength=n)
        inside = np.mean(labels[rows[off]] == labels[indices[off]])
        sizes = np.bincount(labels, minlength=k) / n
        noise = feats - np.stack([feats[labels == c].mean(0)
                                  for c in range(k)])[labels]
        deg_all = np.diff(indptr).astype(np.float64)
        norm = data / (1.0 / np.sqrt(deg_all[rows] * deg_all[indices]))
        return (per_row.mean(), per_row.std(), inside, sizes.min(),
                sizes.max(), noise.std(), norm.min(), norm.max())

    a, p = ours.adj_norm, prog.adj_norm
    s_ours = stats(a.indptr, a.indices, a.data, ours.labels, ours.features)
    s_prog = stats(p.indptr, p.indices, p.data, prog.labels, prog.features)
    np.testing.assert_allclose(s_ours[:3], s_prog[:3], rtol=0.03)
    np.testing.assert_allclose(s_ours[3:5], s_prog[3:5], atol=0.01)
    np.testing.assert_allclose(s_ours[5], s_prog[5], rtol=0.02)
    np.testing.assert_allclose(s_ours[6:], 1.0, rtol=1e-5)
    np.testing.assert_allclose(s_prog[6:], 1.0, rtol=1e-5)
