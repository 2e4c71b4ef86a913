"""Tests for the unified batch-construction layer (``core.minibatch``) and
the fused Pallas extraction (``kernels/extract_gather.py``).

The pure-JAX extraction is the reference oracle: the fused kernel must
produce *identical* arrays (same floats, same ELL tile layout) on graphs
without duplicate edges, where every output cell receives exactly one
contribution and there is no accumulation-order ambiguity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fourd, gcn_model as M, pipeline as PL, sampling as S
from repro.core.minibatch import (BlockFormat, GraphShards, Minibatch,
                                  MinibatchBuilder)
from repro.graphs import (build_partitioned_graph, csr_to_dense,
                          make_synthetic_dataset)
from repro.kernels.extract_gather import DMA_TILE, extract_dense_fused
from repro.kernels.spmm_ell import (dense_to_block_ell_ranked, ell_to_dense,
                                    spmm_ell_pallas)
from repro.optim import AdamW


@pytest.fixture(scope="module")
def g1_setup():
    """A 1-device 4D plan (g_d = g = 1): the full distributed machinery,
    runnable on a single CPU."""
    ds = make_synthetic_dataset(n=256, num_classes=4, d_in=16,
                                avg_degree=8, seed=0)
    pg = build_partitioned_graph(ds, g=1)
    cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                      dropout=0.0)
    mesh = fourd.make_mesh_4d(1, 1)
    return ds, pg, cfg, mesh


@pytest.fixture(scope="module")
def csr(g1_setup):
    ds = g1_setup[0]
    A = ds.adj_norm
    return {
        "rp": jnp.array(A.indptr), "ci": jnp.array(A.indices),
        "val": jnp.array(A.data), "n": A.n_rows,
        "max_deg": A.max_row_nnz(), "dense": csr_to_dense(A),
    }


# ---------------------------------------------------------------------------
# GraphShards / Minibatch pytrees
# ---------------------------------------------------------------------------

def test_graph_shards_pytree_roundtrip(g1_setup):
    ds, pg, cfg, mesh = g1_setup
    plan = fourd.build_plan(pg, cfg, mesh, batch=64)
    shards = GraphShards.from_graph(plan.shard_graph(pg))
    leaves, treedef = jax.tree.flatten(shards)
    assert len(leaves) == 9                      # 3 planes x (rp, ci, val)
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert isinstance(rebuilt, GraphShards)
    for li in range(3):
        for a, b in zip(shards.plane(li), rebuilt.plane(li)):
            assert a is b
    # plane rotation is mod-3: layer 4 reuses plane 1
    assert shards.plane(4)[0] is shards.plane(1)[0]
    # the spec pytree mirrors the data pytree's structure (PartitionSpec is
    # itself a tuple-pytree, so flatten with it as a leaf)
    from jax.sharding import PartitionSpec
    specs = GraphShards.specs(plan.data_specs)
    assert (jax.tree.structure(
                specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
            == jax.tree.structure(shards))


def test_minibatch_leading_dim_helpers():
    mb = Minibatch(adj=(jnp.ones((4, 4)),), feats=jnp.ones((4, 2)),
                   labels=jnp.zeros((4,), jnp.int32))
    up = mb.add_leading()
    assert up.adj[0].shape == (1, 4, 4) and up.labels.shape == (1, 4)
    down = up.strip_leading()
    assert jax.tree.all(jax.tree.map(jnp.array_equal, mb, down))


# ---------------------------------------------------------------------------
# Fused Pallas extraction == pure-JAX oracle (the tentpole property)
# ---------------------------------------------------------------------------

def _sorted_ids(rng, pool, k, *, keep=()):
    """``k`` sorted distinct ids from ``pool`` that include ``keep``."""
    pool = np.setdiff1d(pool, keep)
    ids = np.concatenate([keep, rng.choice(pool, k - len(keep),
                                           replace=False)])
    return jnp.array(np.sort(ids).astype(np.int32))


def _hand_csr(degrees, rng):
    """A CSR whose row v holds ``degrees[v]`` distinct sorted columns, its
    self-loop among them when it holds any (so the Eq. 24 exemption is
    exercised)."""
    n = len(degrees)
    indptr, indices = [0], []
    for v, d in enumerate(degrees):
        if d:
            others = rng.choice(np.delete(np.arange(n), v), d - 1,
                                replace=False)
            indices.append(np.sort(np.append(others, v)))
        indptr.append(indptr[-1] + d)
    ci = np.concatenate(indices).astype(np.int32)
    return {"rp": jnp.array(np.array(indptr, np.int32)), "ci": jnp.array(ci),
            "val": jnp.array(rng.uniform(0.1, 1.0, ci.shape[0])
                             .astype(np.float32)),
            "n": n, "max_deg": int(max(degrees))}


def _extract_case(case, csr, diag):
    """(graph, rows, cols, rng) of one extraction case, the rng to draw
    its per-column rescale; diagonal cases sample one vertex set for both."""
    if case == "sampled":       # draws kept as the first cases had them
        rng = np.random.default_rng(7)
        n = csr["n"]
        if diag:
            rows = cols = _sorted_ids(rng, np.arange(n), 64)
        else:
            rows = _sorted_ids(rng, np.arange(n), 48)
            cols = _sorted_ids(rng, np.arange(n), 32)
        return csr, rows, cols, rng
    rng = np.random.default_rng(
        ["padded_cell", "unequal_cell", "last_vertex", "wide_cols",
         "hub_row", "long_row"].index(case))
    if case == "unequal_cell":
        # the first grid cell's rows hold 0 .. 90 entries, two of them none
        deg = np.concatenate([[0, 1, 37, 3, 0, 90, 12, 2],
                              rng.integers(1, 11, 88)])
        g = _hand_csr(deg, rng)
        keep = np.arange(8)
        if diag:
            return g, *(2 * [_sorted_ids(rng, np.arange(96), 40,
                                         keep=keep)]), rng
        return (g, _sorted_ids(rng, np.arange(96), 16, keep=keep),
                _sorted_ids(rng, np.arange(96), 60), rng)
    if case in ("hub_row", "long_row"):
        # one row's edges span more than one DMA tile: a 3-tile window
        # leaves SMEM room for two cells' windows, a 4-tile one for one
        n = 3000
        deg = rng.integers(1, 7, n)
        deg[700] = 1300 if case == "hub_row" else 2500
        g = _hand_csr(deg, rng)
        assert g["max_deg"] > DMA_TILE
        keep = np.array([700])
        if diag:
            return g, *(2 * [_sorted_ids(rng, np.arange(n), 160,
                                         keep=keep)]), rng
        return (g, _sorted_ids(rng, np.arange(n), 20, keep=keep),
                _sorted_ids(rng, np.arange(n), 300), rng)
    n = csr["n"]
    n_rows, keep = {"padded_cell": (45, ()),
                    "last_vertex": (24, (n - 1,)),
                    "wide_cols": (24, ())}[case]
    n_cols = 200 if case == "wide_cols" else 40
    if case == "last_vertex":
        # the last vertex's window runs past the CSR's end, so the DMA is
        # clamped to start at e_len - window
        md, start = csr["max_deg"], int(csr["rp"][n - 1])
        window = DMA_TILE * -(-(DMA_TILE - 1 + md) // DMA_TILE)
        e_len = max(window, -(-csr["ci"].shape[0] // DMA_TILE) * DMA_TILE)
        assert start // DMA_TILE * DMA_TILE > e_len - window
    if diag:
        return csr, *(2 * [_sorted_ids(rng, np.arange(n),
                                       max(n_rows, n_cols), keep=keep)]), rng
    return (csr, _sorted_ids(rng, np.arange(n), n_rows, keep=keep),
            _sorted_ids(rng, np.arange(n), n_cols), rng)


# the first four cases keep their ids; the rest name the grid-cell edge
# case they add: a partly padded cell, one cell of very unequal rows (two
# empty), the clamped window of the last vertex, b_c past one lane tile,
# and a row longer than one DMA tile, with the next cell's windows
# prefetched and without
_EXTRACT_CASES = [
    pytest.param(case, scale_kind, diag, id="-".join(
        ([] if case == "sampled" else [case]) + [scale_kind, str(diag)]))
    for case in ["sampled", "padded_cell", "unequal_cell", "last_vertex",
                 "wide_cols", "hub_row", "long_row"]
    for scale_kind in ["scalar", "per_column"]
    for diag in [True, False]]


@pytest.mark.parametrize("case,scale_kind,diag", _EXTRACT_CASES)
def test_fused_extraction_bitmatches_dense_oracle(csr, case, scale_kind,
                                                  diag):
    g, rows, cols, rng = _extract_case(case, csr, diag)
    rp, ci, val, md = g["rp"], g["ci"], g["val"], g["max_deg"]
    b_c = cols.shape[0]
    scale = (2.75 if scale_kind == "scalar" else
             jnp.array(rng.uniform(0.5, 3.0, b_c).astype(np.float32)))
    e_cap = rows.shape[0] * md
    ref = S.extract_dense_block(rp, ci, val, rows, cols, e_cap,
                                rescale_offdiag=scale, is_diag_block=diag)
    got = extract_dense_fused(rp, ci, val, rows, cols, col_scale=scale,
                              diag=diag, max_deg=md)
    assert np.array_equal(np.array(ref), np.array(got))


def test_fused_extraction_bitmatches_ell_oracle(csr):
    """ELL format: fused dense kernel + rank-preserving conversion must
    reproduce the direct-to-ELL extraction's tiles AND colidx exactly."""
    rng = np.random.default_rng(3)
    rp, ci, val = csr["rp"], csr["ci"], csr["val"]
    n, md = csr["n"], csr["max_deg"]
    s = jnp.array(np.sort(rng.choice(n, 64, replace=False)).astype(np.int32))
    e_cap = 64 * md
    tiles_ref, colidx_ref = S.extract_block_ell(
        rp, ci, val, s, s, e_cap, rescale_offdiag=1.9, is_diag_block=True,
        bm=16, bn=16, n_slots=4)
    dense = extract_dense_fused(rp, ci, val, s, s, col_scale=1.9,
                                diag=True, max_deg=md)
    tiles, colidx = dense_to_block_ell_ranked(dense, 16, 16, 4)
    assert np.array_equal(np.array(colidx_ref), np.array(colidx))
    assert np.array_equal(np.array(tiles_ref), np.array(tiles))
    # and both densify back to the dense extraction
    assert np.array_equal(np.array(ell_to_dense(tiles, colidx, 64)),
                          np.array(dense))


def test_builder_backends_agree_all_formats(csr):
    """The four (fmt x impl) builder configurations produce the same
    mathematical block."""
    rng = np.random.default_rng(5)
    n, md = csr["n"], csr["max_deg"]
    s = jnp.array(np.sort(rng.choice(n, 64, replace=False)).astype(np.int32))
    scfg = S.SampleConfig(n_pad=n, g=1, batch=64, e_cap=64 * md)
    outs = {}
    for fmt in (BlockFormat.DENSE, BlockFormat.ELL):
        for impl in ("jax", "pallas"):
            b = MinibatchBuilder(scfg=scfg, mode="exact", fmt=fmt,
                                 impl=impl, ell_tile=16, ell_slots=4,
                                 max_row_nnz=md)
            out = b.extract_block(csr["rp"], csr["ci"], csr["val"], s, s,
                                  col_scale=1.5, diag=True)
            if fmt is BlockFormat.ELL:
                out = ell_to_dense(out[0], out[1], 64)
            outs[(fmt, impl)] = np.array(out)
    base = outs[(BlockFormat.DENSE, "jax")]
    for k, v in outs.items():
        assert np.array_equal(base, v), k


def test_ell_spmm_consistent_with_dense_block(csr):
    """extract-to-ELL -> Pallas SpMM == dense extraction @ X."""
    rng = np.random.default_rng(11)
    n, md = csr["n"], csr["max_deg"]
    s = jnp.array(np.sort(rng.choice(n, 64, replace=False)).astype(np.int32))
    e_cap = 64 * md
    dense = S.extract_dense_block(csr["rp"], csr["ci"], csr["val"], s, s,
                                  e_cap, rescale_offdiag=2.0,
                                  is_diag_block=True)
    tiles, colidx = S.extract_block_ell(
        csr["rp"], csr["ci"], csr["val"], s, s, e_cap, rescale_offdiag=2.0,
        is_diag_block=True, bm=16, bn=16, n_slots=8)
    x = jnp.array(rng.normal(size=(64, 16)).astype(np.float32))
    np.testing.assert_allclose(np.array(spmm_ell_pallas(tiles, colidx, x)),
                               np.array(dense @ x), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The unified 4D path at g = 1 (runs on one CPU device)
# ---------------------------------------------------------------------------

def test_fourd_loss_matches_single_device_oracle(g1_setup):
    ds, pg, cfg, mesh = g1_setup
    plan = fourd.build_plan(pg, cfg, mesh, batch=64)
    params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
    graph = plan.shard_graph(pg)
    loss = jax.jit(fourd.make_loss_fn(plan, train=True))(
        params, graph, jnp.asarray(0))
    A = ds.adj_norm
    mb = S.make_minibatch_stratified(
        S.step_key(0, jnp.asarray(0), 0), jnp.array(A.indptr),
        jnp.array(A.indices), jnp.array(A.data), jnp.array(pg.features),
        jnp.array(pg.labels), plan.scfg)
    ref_params = M.init_params(jax.random.PRNGKey(1), cfg)
    logits = M.forward(ref_params, mb.adj, mb.feats, cfg, train=False)
    ref = float(M.cross_entropy_loss(logits, mb.labels))
    assert abs(float(loss[0]) - ref) < 1e-4


@pytest.mark.parametrize("opts_kw", [
    dict(extract_impl="pallas"),
    dict(extract_impl="pallas", spmm_impl="ell", ell_tile=16, ell_slots=16),
    dict(spmm_impl="ell", ell_tile=16, ell_slots=16),
])
def test_fourd_loss_invariant_to_extraction_backend(g1_setup, opts_kw):
    """Acceptance: every extraction backend/format reproduces the reference
    4D loss through the one unified builder path."""
    ds, pg, cfg, mesh = g1_setup
    plan = fourd.build_plan(pg, cfg, mesh, batch=64)
    params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
    graph = plan.shard_graph(pg)
    l_ref = jax.jit(fourd.make_loss_fn(plan, train=False))(
        params, graph, jnp.asarray(0))
    plan2 = fourd.build_plan(pg, cfg, mesh, batch=64,
                             opts=fourd.TrainOptions(**opts_kw))
    l_got = jax.jit(fourd.make_loss_fn(plan2, train=False))(
        params, graph, jnp.asarray(0))
    np.testing.assert_allclose(np.array(l_got), np.array(l_ref), rtol=1e-5)


def test_prefetch_pipeline_matches_unpipelined_losses(g1_setup):
    """Acceptance: the §V-A prefetched pipeline (now carrying a Minibatch
    pytree) still reproduces the unpipelined loss sequence exactly."""
    ds, pg, cfg, mesh = g1_setup
    plan = fourd.build_plan(pg, cfg, mesh, batch=64)
    params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
    graph = plan.shard_graph(pg)
    opt = AdamW(lr=5e-3)
    opt_state = opt.init(params)
    ts = fourd.make_train_step(plan, opt)
    p0, o0, ref = params, opt_state, []
    for s in range(4):
        p0, o0, loss = ts(p0, o0, graph, jnp.asarray(s))
        ref.append(float(loss))
    sample_fn, step_fn = PL.make_prefetched_train_step(plan, opt)
    state = PL.PrefetchState(params, opt_state,
                             sample_fn(graph, jnp.asarray(0)))
    assert isinstance(state.minibatch, Minibatch)
    got = []
    for s in range(4):
        state, loss = step_fn(state, graph, jnp.asarray(s))
        got.append(float(loss))
    np.testing.assert_allclose(ref, got, rtol=1e-5)


@pytest.mark.parametrize("extract", ["jax", "pallas"])
def test_prefetch_pipeline_matches_unpipelined_losses_ell(g1_setup, extract):
    """The §V-A pipeline carries block-ELL minibatches too (per-leaf tile
    specs in ``pipeline._minibatch_specs``): the pipelined loss sequence
    must equal the unpipelined one exactly, for both extraction backends."""
    ds, pg, cfg, mesh = g1_setup
    plan = fourd.build_plan(pg, cfg, mesh, batch=64,
                            opts=fourd.TrainOptions(spmm_impl="ell",
                                                    ell_tile=16,
                                                    ell_slots=16,
                                                    extract_impl=extract))
    params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
    graph = plan.shard_graph(pg)
    opt = AdamW(lr=5e-3)
    opt_state = opt.init(params)
    ts = fourd.make_train_step(plan, opt)
    p0, o0, ref = params, opt_state, []
    for s in range(4):
        p0, o0, loss = ts(p0, o0, graph, jnp.asarray(s))
        ref.append(float(loss))
    sample_fn, step_fn = PL.make_prefetched_train_step(plan, opt)
    state = PL.PrefetchState(params, opt_state,
                             sample_fn(graph, jnp.asarray(0)))
    got = []
    for s in range(4):
        state, loss = step_fn(state, graph, jnp.asarray(s))
        got.append(float(loss))
    np.testing.assert_allclose(ref, got, rtol=1e-5)


def test_builder_requires_row_bound_for_pallas():
    scfg = S.SampleConfig(n_pad=64, g=1, batch=8, e_cap=8)
    with pytest.raises(AssertionError):
        MinibatchBuilder(scfg=scfg, impl="pallas")       # no max_row_nnz


def test_builder_exact_mode_matches_reference_oracle(csr):
    """Sampling-mode dispatch: builder exact mode == make_minibatch_exact."""
    n, md = csr["n"], csr["max_deg"]
    feats = jnp.array(np.random.default_rng(0).normal(
        size=(n, 8)).astype(np.float32))
    labels = jnp.zeros((n,), jnp.int32)
    scfg = S.SampleConfig(n_pad=n, g=1, batch=32, e_cap=32 * md)
    b = MinibatchBuilder(scfg=scfg, mode="exact")
    key = jax.random.PRNGKey(9)
    mine = b.build_single(key, csr["rp"], csr["ci"], csr["val"], feats,
                          labels)
    ref = S.make_minibatch_exact(key, csr["rp"], csr["ci"], csr["val"],
                                 feats, labels, n, 32, 32 * md)
    assert np.array_equal(np.array(mine.vertex_ids), np.array(ref.vertex_ids))
    np.testing.assert_allclose(np.array(mine.adj), np.array(ref.adj),
                               rtol=1e-6)
    assert np.array_equal(np.array(mine.feats), np.array(ref.feats))
