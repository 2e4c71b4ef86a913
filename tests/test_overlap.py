"""Comm–compute overlap: chunked ring collectives + the pipelined engine.

Three layers of evidence, mirroring how the feature can break:

1. **Numerics** — ``overlap_impl="ring"`` must give BIT-identical losses
   to "none": at grid side <= 2 every ring chunk reduction is a single IEEE
   add. Gradients agree to ``GRAD_ULPS`` ulp of each leaf's largest entry:
   ``ring_psum_gemm``'s custom VJP keeps the backward contractions
   full-width, but the monolithic path's single-participant psums sit
   between the RMSNorm backward and the GEMM transposes, and XLA fuses the
   two programs' elementwise backward differently (measured: at most 0.84
   ulp at (2,2,2), 0.67 ulp at (1,1,1), on jax 0.9.0 CPU).
2. **Bytes** — the ring decomposition must not inflate collective volume
   (``obs.comm_report``); the FP32 loss/norm reductions stay monolithic.
3. **Structure** — the compiled ring program must actually expose compute
   to hide each transfer behind: ``obs.overlap_report`` scores every
   collective by dependence-graph concurrency (scheduler-independent, so
   it holds on the sync-collective CPU backend CI runs on).

The (1,1,1) tests run in-process on the single CPU device; the real
8-device (2,2,2)x1 mesh runs in one forced subprocess (tiny shapes — this
is tier-1, unlike the 16-device tests in test_fourd_multidevice.py).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, fourd, gcn_model as M
from repro.graphs import build_partitioned_graph, make_synthetic_dataset
from repro.obs import OverlapReport, parse_overlap
from repro.optim import (
    AdamW, constant_schedule, cosine_schedule, cosine_schedule_epochs,
    epochs_to_steps, linear_warmup_cosine, linear_warmup_cosine_epochs,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ring vs monolithic gradients: |a - b| <= GRAD_ULPS * eps32 * max|a| per leaf
GRAD_ULPS = 4


def grads_within_ulps(ga, gb, ulps=GRAD_ULPS) -> bool:
    """Every leaf pair agrees to ``ulps`` float32 ulp of the leaf's largest
    magnitude (the scale-aware form of an ulp bound: entries near zero
    have tiny ulps that a reduction-order change alone exceeds)."""
    eps = np.finfo(np.float32).eps
    la, lb = jax.tree.leaves(ga), jax.tree.leaves(gb)
    assert len(la) == len(lb)
    return all(
        np.abs(np.asarray(a) - np.asarray(b)).max()
        <= ulps * eps * np.abs(np.asarray(a)).max()
        for a, b in zip(la, lb))


@pytest.fixture(scope="module")
def tiny_plans():
    """(1,1,1)x1 plans for overlap none vs ring, same graph/params."""
    ds = make_synthetic_dataset(n=256, num_classes=4, d_in=16,
                                avg_degree=8, seed=0)
    pg = build_partitioned_graph(ds, g=1)
    cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                      dropout=0.0)
    mesh = fourd.make_mesh_4d(1, 1)
    plans = {
        impl: fourd.build_plan(pg, cfg, mesh, batch=64,
                               opts=fourd.TrainOptions(overlap_impl=impl))
        for impl in ("none", "ring")
    }
    graph = plans["none"].shard_graph(pg)
    params = plans["none"].shard_params(
        M.init_params(jax.random.PRNGKey(1), cfg))
    return cfg, pg, plans, graph, params


# ---------------------------------------------------------------------------
# 1. numerics: ring == none, bitwise, loss AND grads
# ---------------------------------------------------------------------------

def _loss_and_grads(plan, params, graph):
    loss_fn = fourd.make_loss_fn(plan, train=True)

    def mean_loss(p, g_, s):
        return loss_fn(p, g_, s).mean()

    loss = jax.jit(mean_loss)(params, graph, jnp.asarray(0))
    grads = jax.jit(jax.grad(mean_loss))(params, graph, jnp.asarray(0))
    return loss, grads


def test_ring_bitmatches_none_1x1x1(tiny_plans):
    """Losses bitwise; gradients within GRAD_ULPS (module docstring)."""
    _, _, plans, graph, params = tiny_plans
    l0, g0 = _loss_and_grads(plans["none"], params, graph)
    l1, g1 = _loss_and_grads(plans["ring"], params, graph)
    assert np.array(l0).tobytes() == np.array(l1).tobytes(), (l0, l1)
    assert grads_within_ulps(g0, g1)


def test_ring_bitmatches_none_under_bf16_1x1x1(tiny_plans):
    """The ring path must replicate the bf16 WIRE semantics exactly —
    including the lossy f32->bf16->f32 round-trip at g=1."""
    cfg, pg, _, graph, params = tiny_plans
    mesh = fourd.make_mesh_4d(1, 1)
    mk = lambda impl: fourd.build_plan(  # noqa: E731
        pg, cfg, mesh, batch=64,
        opts=fourd.TrainOptions(overlap_impl=impl, bf16_collectives=True))
    l0, g0 = _loss_and_grads(mk("none"), params, graph)
    l1, g1 = _loss_and_grads(mk("ring"), params, graph)
    assert np.array(l0).tobytes() == np.array(l1).tobytes(), (l0, l1)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        assert np.array(a).tobytes() == np.array(b).tobytes()


# ---------------------------------------------------------------------------
# 2. the overlap-report parser, pinned on synthetic HLO
# ---------------------------------------------------------------------------

SYNC_HLO = """
ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  %ar = f32[8,8] all-reduce(%p0), to_apply=%add, metadata={op_name="spmm/psum"}
  %indep = f32[8,8] dot(%p0, %p0), metadata={op_name="gemm/chunk"}
  %use = f32[8,8] add(%ar, %indep)
  ROOT %out = f32[8,8] dot(%use, %use)
}
"""

SERIAL_HLO = """
ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  %pre = f32[8,8] dot(%p0, %p0)
  %ar = f32[8,8] all-reduce(%pre), to_apply=%add
  ROOT %post = f32[8,8] dot(%ar, %ar)
}
"""

ASYNC_HLO = """
ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8] parameter(0)
  %st = f32[8,8] collective-permute-start(%p0), metadata={op_name="ring_ag/step"}
  %c1 = f32[8,8] dot(%p0, %p0)
  %c2 = f32[8,8] dot(%c1, %c1)
  %dn = f32[8,8] collective-permute-done(%st)
  ROOT %out = f32[8,8] add(%dn, %c2)
}
"""


def test_parse_overlap_sync_concurrent():
    r = parse_overlap(SYNC_HLO)
    assert r.n_collectives == 1
    (site,) = r.sites
    assert site.kind == "all-reduce" and not site.is_async
    # %indep and ROOT... ROOT depends on %use -> %ar: descendant. Only
    # %indep is dependence-eligible; it is also scheduled in the window.
    assert site.concurrent == 1 and site.slack == 1
    assert r.n_overlapped == 1
    assert r.assert_overlapped("spmm") is r


def test_parse_overlap_serialized_chain_scores_zero():
    r = parse_overlap(SERIAL_HLO)
    (site,) = r.sites
    assert site.concurrent == 0 and site.slack == 0
    with pytest.raises(AssertionError, match="overlappable"):
        r.assert_overlapped()


def test_parse_overlap_async_pair():
    r = parse_overlap(ASYNC_HLO)
    assert r.n_collectives == 1            # -start/-done pair counted once
    (site,) = r.sites
    assert site.is_async
    assert site.slack == 2                 # c1, c2 between start and done
    assert site.concurrent == 2
    assert r.for_scope("ring_ag") == r.sites
    assert r.for_scope("nonexistent") == ()
    with pytest.raises(AssertionError, match="no collectives match"):
        r.assert_overlapped("nonexistent")


def test_overlap_report_str():
    r = parse_overlap(ASYNC_HLO)
    assert "collective-permute" in str(r) and "async" in str(r)
    assert "no collectives" in str(OverlapReport(sites=()))


# ---------------------------------------------------------------------------
# 3. epoch-parameterized schedules
# ---------------------------------------------------------------------------

def test_epoch_schedules_bitmatch_step_forms():
    steps = jnp.arange(0, 120, dtype=jnp.int32)
    spe, epochs = 12, 10
    assert epochs_to_steps(epochs, spe) == 120

    a = cosine_schedule(3e-3, 120, final_frac=0.05)(steps)
    b = cosine_schedule_epochs(3e-3, epochs, spe, final_frac=0.05)(steps)
    assert np.array(a).tobytes() == np.array(b).tobytes()

    a = linear_warmup_cosine(3e-3, 24, 120)(steps)
    b = linear_warmup_cosine_epochs(3e-3, warmup_epochs=2.0, epochs=epochs,
                                    steps_per_epoch=spe)(steps)
    assert np.array(a).tobytes() == np.array(b).tobytes()


def test_epoch_schedule_validates():
    with pytest.raises(AssertionError):
        epochs_to_steps(0, 10)


# ---------------------------------------------------------------------------
# 4. full-batch GCN baseline == single-device oracle at (1,1,1)
# ---------------------------------------------------------------------------

def test_fullbatch_gcn_matches_single_device_oracle(tiny_plans):
    cfg, pg, plans, graph, params = tiny_plans
    plan = plans["none"]
    loss_fn = baselines.make_fullbatch_gcn_loss(plan, train=False)
    got = jax.jit(loss_fn)(params, graph, jnp.zeros((), jnp.int32))

    # dense single-device forward over the same padded graph
    n_loc = pg.n_local
    rp, ci, val = pg.block_rp[0, 0], pg.block_ci[0, 0], pg.block_val[0, 0]
    dense = np.zeros((n_loc, n_loc), np.float32)
    rows = np.repeat(np.arange(n_loc), rp[1:] - rp[:-1])
    nz = rp[-1]
    dense[rows, ci[:nz]] = val[:nz]
    ref_params = M.init_params(jax.random.PRNGKey(1), cfg)
    logits = M.forward(ref_params, jnp.asarray(dense),
                       jnp.asarray(pg.features), cfg, train=False)
    ref = M.cross_entropy_loss(logits, jnp.asarray(pg.labels))
    np.testing.assert_allclose(np.array(got[0]), np.array(ref),
                               rtol=1e-5, atol=1e-6)


def test_fullbatch_gcn_step_trains(tiny_plans):
    _, _, plans, graph, params = tiny_plans
    plan = plans["none"]
    opt = AdamW(lr=constant_schedule(1e-2), weight_decay=0.0, grad_clip=1.0)
    step_fn = baselines.make_fullbatch_gcn_step(plan, opt)
    p, o = params, opt.init(params)
    losses = []
    for s in range(4):
        p, o, loss = step_fn(p, o, graph, jnp.asarray(s, jnp.int32))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# 5. XLA flag plumbing
# ---------------------------------------------------------------------------

def test_overlap_flags_sets():
    from repro.launch.xla_flags import (CPU_OVERLAP_FLAGS, GPU_OVERLAP_FLAGS,
                                        overlap_flags)
    assert overlap_flags("gpu") == GPU_OVERLAP_FLAGS
    assert overlap_flags("cpu") == CPU_OVERLAP_FLAGS
    assert set(overlap_flags("all")) == set(GPU_OVERLAP_FLAGS
                                            + CPU_OVERLAP_FLAGS)


def test_enable_overlap_scheduler_refuses_after_backend_init():
    from repro.launch.xla_flags import enable_overlap_scheduler
    jax.devices()                     # ensure the backend is live
    with pytest.raises(RuntimeError, match="backend init"):
        enable_overlap_scheduler("cpu")


# ---------------------------------------------------------------------------
# 6. the real (2,2,2)x1 mesh, one forced 8-device subprocess (tier-1)
# ---------------------------------------------------------------------------

def test_ring_overlap_on_2x2x2_mesh_subprocess():
    """The acceptance gates on a real multidevice mesh, tiny shapes:

    * reshard_permute bit-identical to reshard_gather — as a primitive
      (pure data movement either way) and through the forward loss, plain
      and under bf16_collectives. Gradients agree only to ~1 ulp: the two
      transposes sum the same replica cotangents through different
      reduction trees (gather's reduce-scatter vs permute's routed local
      adds), so backward bit-equality is unattainable by construction;
    * ring loss bit-identical to none (single-add reductions at g=2),
      grads within GRAD_ULPS ulp of each leaf's scale (module docstring);
    * ring does not inflate collective bytes; FP32 loss/norm psums stay;
    * the structural overlap gate: every ring all-gather-phase collective
      in the GEMM scope has compute dependence-eligible to hide it.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    body = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from repro.graphs import make_synthetic_dataset, build_partitioned_graph
    from repro.core import fourd, gcn_model as M
    from repro.obs import comm_report, overlap_report

    ds = make_synthetic_dataset(n=128, num_classes=4, d_in=16, avg_degree=8,
                                seed=0)
    pg = build_partitioned_graph(ds, g=2)
    cfg = M.GCNConfig(d_in=16, d_hidden=16, num_layers=3, num_classes=4,
                      dropout=0.0)
    mesh = fourd.make_mesh_4d(1, 2)

    def lg(opts):
        plan = fourd.build_plan(pg, cfg, mesh, batch=32, opts=opts)
        params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
        graph = plan.shard_graph(pg)
        loss_fn = fourd.make_loss_fn(plan, train=True)
        mean = lambda p, g_, s: loss_fn(p, g_, s).mean()
        loss = jax.jit(mean)(params, graph, jnp.asarray(0))
        grads = jax.jit(jax.grad(mean))(params, graph, jnp.asarray(0))
        return loss, grads, (mean, params, graph)

    def biteq(a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        assert len(la) == len(lb)
        return all(np.array(x).tobytes() == np.array(y).tobytes()
                   for x, y in zip(la, lb))

    O = fourd.TrainOptions
    l_none, g_none, (mean_n, params, graph) = lg(O())
    l_ring, g_ring, (mean_r, _, _) = lg(O(overlap_impl="ring"))
    assert biteq(l_none, l_ring), (l_none, l_ring)
    eps = np.finfo(np.float32).eps
    for a, b in zip(jax.tree.leaves(g_none), jax.tree.leaves(g_ring)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.abs(a - b).max() <= GRAD_ULPS * eps * np.abs(a).max(), (
            "ring grads diverge from monolithic")

    # reshard permute == gather: the primitive itself is bitwise (pure
    # data movement), asserted directly on the (2,2,2) grid...
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.core import pmm3d
    st = pmm3d.initial_state()
    t = jax.random.normal(jax.random.PRNGKey(7), (16, 8), jnp.float32)

    def both(t_):
        a = pmm3d.reshard_gather(t_, st, (st.rep, st.row))
        b = pmm3d.reshard_permute(t_, st, (st.rep, st.row))
        return a, b
    sm = jax.shard_map(both, mesh=mesh, in_specs=(P(),),
                   out_specs=(P("z", "x"), P("z", "x")), check_vma=False)
    a, b = jax.jit(sm)(t)
    assert np.array(a).tobytes() == np.array(b).tobytes(), (
        "reshard_permute routes different bits than reshard_gather")

    # ...and through the forward loss, plain and under the bf16 wire
    # format; grads to ~1 ulp (different transpose reduction trees)
    def close(a_, b_, atol):
        return all(np.allclose(np.array(x), np.array(y), atol=atol)
                   for x, y in zip(jax.tree.leaves(a_), jax.tree.leaves(b_)))
    l_perm, g_perm, _ = lg(O(reshard_impl="permute"))
    assert biteq(l_none, l_perm) and close(g_none, g_perm, 2e-6)
    # bf16 backward reductions re-round per tree shape: grads to bf16 eps
    l_gb, g_gb, _ = lg(O(bf16_collectives=True))
    l_pb, g_pb, _ = lg(O(bf16_collectives=True, reshard_impl="permute"))
    assert biteq(l_gb, l_pb) and close(g_gb, g_pb, 5e-3), (
        "permute reshard diverges from gather under bf16 collectives")

    # bytes: ring must not inflate; monolithic FP32 reductions remain
    step = jnp.asarray(0)
    r_none = comm_report(jax.jit(jax.grad(mean_n)), params, graph, step)
    r_ring = comm_report(jax.jit(jax.grad(mean_r)), params, graph, step)
    assert r_ring.total_bytes <= r_none.total_bytes, (
        r_ring.total_bytes, r_none.total_bytes)
    assert r_ring.counts["collective-permute"] > 0, r_ring
    assert r_ring.counts["all-reduce"] > 0, r_ring   # FP32 loss/norm psums

    # structure: compute is dependence-eligible behind every GEMM-scope
    # ring all-gather step of the compiled (scheduled) program
    rep = overlap_report(jax.jit(mean_r), params, graph, step)
    rep.assert_overlapped("gemm", "ring_ag", what="(2,2,2)x1 ring loss")
    assert not overlap_report(jax.jit(mean_n), params, graph,
                              step).for_scope("ring_ag")
    print("PASS")
    """).replace("GRAD_ULPS", str(GRAD_ULPS))
    r = subprocess.run([sys.executable, "-c", body], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "PASS" in r.stdout, r.stdout
