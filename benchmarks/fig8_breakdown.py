"""Paper Fig. 8: epoch-time breakdown vs data-parallel group count.

Decomposes the step into (sampling+extraction) and (train remainder) by
timing the prefetch sample_fn separately, and isolates the DP gradient
all-reduce by comparing HLO collective bytes between G_d=1 and G_d=2.

Two additions for the comm–compute overlap work (ROADMAP item 4):

* the full step is timed with ``overlap_impl`` off AND on
  (``fig8_gd1_step`` / ``fig8_gd1_step_ring``) — on a host mesh the wall
  delta may be ~0 (sync collectives); the structural interleaving gate is
  ``obs.overlap_report`` in CI, not this number;
* per-phase rows (``fig8_phase_<spmm|gemm|reshard|rotate>_<none|ring>``)
  from ISOLATED jitted per-phase programs with the engine's exact
  per-layer shapes. Host spans inside ``shard_map`` measure trace time
  only, so isolation is the only honest way to a per-phase wall time;
  each row also carries the phase's exact collective bytes
  (``obs.comm_report``), which is where the ring reshard's 2(g-1)/g
  volume saving shows up runtime-independently. ``benchmarks.compare``
  prints the none-vs-ring per-phase delta table from these rows.

``--smoke`` (CI bench-smoke): G_d=1 only (8 host devices), fewer iters.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.common import csv, set_bench, time_fn
from repro.core import fourd, pipeline as PL, pmm3d
from repro.core import gcn_model as GM
from repro.graphs import build_partitioned_graph, make_synthetic_dataset
from repro.launch.roofline import analyze_hlo
from repro.obs import comm_report, get_tracer
from repro.optim import AdamW

PHASES_MEASURED = ("spmm", "gemm", "reshard", "rotate")


def build(gd: int, opts: fourd.TrainOptions):
    ds = make_synthetic_dataset(n=4096, num_classes=8, d_in=64,
                                avg_degree=16, seed=0)
    pg = build_partitioned_graph(ds, g=2)
    cfg = GM.GCNConfig(d_in=64, d_hidden=128, num_layers=3, num_classes=8,
                       dropout=0.1)
    mesh = fourd.make_mesh_4d(gd, 2)
    plan = fourd.build_plan(pg, cfg, mesh, batch=256, opts=opts)
    params = plan.shard_params(GM.init_params(jax.random.PRNGKey(0), cfg))
    graph = plan.shard_graph(pg)
    opt = AdamW(lr=1e-3)
    return plan, params, opt.init(params), graph, opt


def breakdown(gd: int, opts: fourd.TrainOptions, iters: int = 8):
    plan, params, opt_state, graph, opt = build(gd, opts)

    sample_fn, step_fn = PL.make_prefetched_train_step(plan, opt)
    us_sample = time_fn(lambda: sample_fn(graph, jnp.asarray(0)),
                        warmup=2, iters=iters)

    state = PL.PrefetchState(params, opt_state,
                             sample_fn(graph, jnp.asarray(0)))
    def run(i):
        nonlocal state
        state, loss = step_fn(state, graph, jnp.asarray(int(i)))
        return loss
    us_step = time_fn(run, 1, warmup=3, iters=iters)

    loss_fn = fourd.make_loss_fn(plan, train=True)
    lowered = jax.jit(jax.grad(
        lambda p, g_, s: loss_fn(p, g_, s).mean())).lower(
            params, graph, jnp.asarray(0))
    coll = analyze_hlo(lowered.compile().as_text())["coll_total"]
    return us_sample, us_step, coll


def make_phase_programs(plan, opts: fourd.TrainOptions):
    """Jitted single-phase programs with the engine's per-layer shapes.

    Inputs are replicated (P()) — the collectives and matmuls still run at
    exactly the engine's local shapes, which is all a timing needs. The
    reshard output IS device-dependent (each device slices its own
    destination block), so it alone gets a sharded out_spec.
    """
    g = plan.grid_side
    cfg = plan.cfg
    b = plan.scfg.batch // g              # local rows of the batch block
    dloc = cfg.d_hidden // g              # local feature columns
    st = pmm3d.initial_state()
    bf16 = opts.bf16_collectives
    ring = opts.overlap_impl == "ring"

    k = jax.random.PRNGKey(0)
    blk = jax.random.normal(k, (b, b), jnp.float32)
    h = jax.random.normal(k, (b, dloc), jnp.float32)
    w = jax.random.normal(k, (dloc, dloc), jnp.float32)

    def allreduce(x, ax):
        if ring:
            return pmm3d.ring_psum(x, ax, bf16=bf16)
        return pmm3d.psum_maybe_bf16(x, ax, bf16)

    def spmm_body(blk_, h_):
        part = blk_ @ h_
        # ring mode defers the row reduction into the GEMM ring (the
        # engine's fused schedule) — spmm is then collective-free
        return part if ring else allreduce(part, st.row)

    def gemm_body(part_, w_):
        if ring:
            return allreduce(
                pmm3d.ring_psum_gemm(part_, w_, st.row, bf16=bf16), st.col)
        return allreduce(part_ @ w_, st.col)

    def reshard_body(h_):
        return pmm3d.reshard(h_, st, (st.rep, st.row),
                             impl=opts.reshard_impl,
                             overlap=opts.overlap_impl)

    def rotate_body(h_):
        # PlaneState.rotate is a pure relabeling: zero data movement by
        # construction — the row exists so the table says so with a number
        return h_

    def wrap(body, args, out_specs=P()):
        fn = jax.jit(jax.shard_map(body, mesh=plan.mesh,
                                   in_specs=(P(),) * len(args),
                                   out_specs=out_specs, check_vma=False))
        jax.block_until_ready(fn(*args))          # compile outside timing
        return fn, args

    return {
        "spmm": wrap(spmm_body, (blk, h)),
        "gemm": wrap(gemm_body, (h, w)),
        "reshard": wrap(reshard_body, (h,), out_specs=P("z", "x")),
        "rotate": wrap(rotate_body, (h,)),
    }


def measure_phases(plan, opts: fourd.TrainOptions, tag: str,
                   iters: int = 8):
    """Per-phase rows: isolated wall µs + exact collective bytes."""
    tracer = get_tracer()
    byts = {}
    for ph, (fn, args) in make_phase_programs(plan, opts).items():
        us = time_fn(lambda: fn(*args), warmup=2, iters=iters)
        coll = comm_report(fn, *args).total_bytes
        byts[ph] = coll
        tracer.record(f"phase_{ph}_{tag}", us.median / 1e6)
        csv(f"fig8_phase_{ph}_{tag}", us,
            f"isolated phase program; coll_bytes={coll:.3e}",
            comm_bytes=coll)
    return byts


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: G_d=1 only (8 host devices), 3 iters")
    args = ap.parse_args(argv)
    iters = 3 if args.smoke else 8

    set_bench("fig8", batch=256, grid="2x2x2", smoke=args.smoke)
    opts_none = fourd.TrainOptions(dropout=0.1)
    opts_ring = fourd.TrainOptions(dropout=0.1, overlap_impl="ring")

    s1, t1, c1 = breakdown(1, opts_none, iters=iters)
    csv("fig8_gd1_sampling", s1, "sampling+extraction only")
    csv("fig8_gd1_step", t1, f"coll_bytes={c1:.3e}", comm_bytes=int(c1))
    _, t1r, c1r = breakdown(1, opts_ring, iters=iters)
    csv("fig8_gd1_step_ring", t1r, f"coll_bytes={c1r:.3e}",
        comm_bytes=int(c1r))
    assert c1r <= c1, (
        f"ring collectives must not inflate step bytes: {c1r} > {c1}")

    plan, *_ = build(1, opts_none)
    b_none = measure_phases(plan, opts_none, "none", iters=iters)
    plan_r, *_ = build(1, opts_ring)
    b_ring = measure_phases(plan_r, opts_ring, "ring", iters=iters)

    def move_share(b):
        # data-movement phases' share of the layer's collective bytes
        return (b["reshard"] + b["rotate"]) / max(sum(b.values()), 1)
    print(f"# reshard+rotate byte share: {move_share(b_none):.2f} (none) "
          f"-> {move_share(b_ring):.2f} (ring); step bytes "
          f"{c1:.3e} -> {c1r:.3e}")

    if not args.smoke:
        s2, t2, c2 = breakdown(2, opts_none, iters=iters)
        csv("fig8_gd2_sampling", s2, "sampling+extraction only")
        csv("fig8_gd2_step", t2, f"coll_bytes={c2:.3e}", comm_bytes=int(c2))
        print(f"# DP all-reduce adds {c2 - c1:.3e} collective bytes/device "
              f"(paper Fig. 8: DP all-reduce grows with G_d; PMM+sampling "
              f"stay constant)")
        print(f"# sampling time roughly constant across G_d: "
              f"{s1.median:.0f}us -> {s2.median:.0f}us")


if __name__ == "__main__":
    main()
