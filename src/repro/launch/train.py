"""End-to-end GNN training driver (the paper's workload) — a thin CLI over
the ``repro.train`` runtime.

Runs ScaleGNN 4D training on a synthetic stand-in dataset on the local
device set (use XLA_FLAGS=--xla_force_host_platform_device_count=N to get
a multi-device host mesh). The loop itself is ``train.Trainer``:
scan-chunked steps (``--chunk-size``), multi-epoch schedules
(``--epochs`` with ``--sample-mode epoch`` = without-replacement epoch
permutations, communication-free), §V-A prefetch folded into the scan
carry (``--prefetch``, epoch-boundary-crossing), one eval per report
boundary, and full-state checkpointing (``--ckpt-dir``/``--ckpt-every``,
async double-buffered writes unless ``--sync-ckpt``) with ``--resume``
picking up bit-identically from the latest saved ``TrainState`` — the
final state is always persisted by ``run()`` itself. Example::

    XLA_FLAGS=--xla_force_host_platform_device_count=16 \\
    PYTHONPATH=src python -m repro.launch.train \\
        --dataset ogbn-products --vertices 8192 --gd 2 --g 2 \\
        --batch 1024 --steps 300 --target-acc 0.90
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax

from repro.core import fourd, gcn_model as GM
from repro.graphs import build_partitioned_graph, get_dataset
from repro.obs import Tracer, set_tracer
from repro.optim import AdamW, linear_warmup_cosine, linear_warmup_cosine_epochs
from repro.train import Trainer, TrainLoopConfig


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="ogbn-products")
    ap.add_argument("--vertices", type=int, default=8192)
    ap.add_argument("--gd", type=int, default=1, help="data-parallel groups")
    ap.add_argument("--g", type=int, default=2, help="3D PMM cube side")
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--d-hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=3)
    ap.add_argument("--steps", type=int, default=None,
                    help="optimizer steps to run (default 300; mutually "
                         "exclusive with --epochs)")
    ap.add_argument("--epochs", type=int, default=None,
                    help="run whole epochs of n_pad/batch steps instead of "
                         "--steps (the two are mutually exclusive)")
    ap.add_argument("--sample-mode", default="step",
                    choices=["step", "epoch"],
                    help="'step': independent per-step samples (seed, step, "
                         "dp); 'epoch': without-replacement — one "
                         "permutation per (seed, epoch, dp), step t takes "
                         "slice t (still communication-free)")
    ap.add_argument("--sample-kind", default="stratified",
                    choices=["stratified", "partition", "walk"],
                    help="sampling family (all communication-free): "
                         "'stratified' per-range uniform vertices (Alg. 1); "
                         "'partition' whole locality clusters (Cluster-GCN "
                         "— smaller support pool, cheaper extraction); "
                         "'walk' GraphSAINT random-walk batches")
    ap.add_argument("--clusters", type=int, default=0,
                    help="partition kind: locality clusters per vertex "
                         "range (0 with --sample-kind partition defaults "
                         "to n_local/batch-per-range sized clusters)")
    ap.add_argument("--walk-len", type=int, default=4,
                    help="walk kind: steps per root walk")
    ap.add_argument("--walk-k", type=int, default=8,
                    help="walk kind: neighbor-table width (degree cap)")
    ap.add_argument("--mmap-dir", default=None, metavar="DIR",
                    help="ingest the graph from an MmapShardedCSR shard "
                         "set (write one with repro.graphs.datasets."
                         "write_mmap_shards) instead of materializing a "
                         "synthetic dataset in memory; overrides "
                         "--dataset/--vertices")
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--dropout", type=float, default=0.2)
    ap.add_argument("--bf16-collectives", action="store_true")
    ap.add_argument("--compress", default="none",
                    choices=["none", "bf16", "int8", "int4"],
                    help="wire format of the PMM collectives: 'bf16' casts "
                         "sends, 'int8'/'int4' quantize each ring chunk "
                         "(absmax, per-row scales) with error feedback "
                         "carried across steps in the TrainState")
    ap.add_argument("--compress-schedule", default="uniform",
                    choices=["uniform", "variable"],
                    help="'uniform': every layer uses --compress; "
                         "'variable': ramp bf16->int8->int4 by depth, "
                         "capped at --compress (deeper layers compress "
                         "harder)")
    ap.add_argument("--fused-elementwise", action="store_true")
    ap.add_argument("--reshard", default="gather",
                    choices=["gather", "permute"])
    ap.add_argument("--overlap", default="none", choices=["none", "ring"],
                    help="collective implementation in the forward engine: "
                         "'ring' decomposes the PMM psums/gathers into "
                         "per-chunk ppermute steps so each transfer hides "
                         "behind a chunk of SpMM/GEMM compute")
    ap.add_argument("--xla-overlap", action="store_true",
                    help="enable XLA's latency-hiding scheduler flags "
                         "before backend init (see launch/xla_flags.py)")
    ap.add_argument("--prefetch", action="store_true",
                    help="overlap sampling with training (paper §V-A)")
    ap.add_argument("--chunk-size", type=int, default=8,
                    help="optimizer steps per lax.scan dispatch")
    ap.add_argument("--target-acc", type=float, default=None)
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--eval-every-epochs", type=int, default=None,
                    help="evaluate every N epochs instead of every "
                         "--eval-every steps (bit-identical to the step "
                         "form at N * steps-per-epoch)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="steps between full-state checkpoints (0 = only "
                         "the final state)")
    ap.add_argument("--sync-ckpt", action="store_true",
                    help="block on mid-run checkpoint writes instead of "
                         "overlapping them with the next scan chunk")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest TrainState in --ckpt-dir")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump the full RunLog + tracer span summary as "
                         "JSON (for scripted runs)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the run into DIR "
                         "(phase names label the timeline)")
    return ap


def main(argv=None):
    """Run the CLI; returns the run's ``RunLog``."""
    args = build_argparser().parse_args(argv)
    if args.steps is not None and args.epochs is not None:
        raise SystemExit("--steps and --epochs are mutually exclusive")
    if args.epochs is None and args.steps is None:
        args.steps = 300

    if args.xla_overlap:
        # must precede the first device use: XLA reads XLA_FLAGS once.
        # "all" because asking the platform would itself init the backend
        from repro.launch.xla_flags import enable_overlap_scheduler
        enable_overlap_scheduler("all")

    n_need = args.gd * args.g ** 3
    devices = jax.devices()
    if len(devices) < n_need:
        raise SystemExit(
            f"--gd {args.gd} x --g {args.g}^3 needs {n_need} devices; found "
            f"{len(devices)}: {devices}")

    if args.mmap_dir:
        from repro.graphs.datasets import MmapShardedCSR
        shards = MmapShardedCSR.open(args.mmap_dir)
        assert shards.meta["g"] == args.g, (
            f"shard set {args.mmap_dir} was written for g="
            f"{shards.meta['g']}, not --g {args.g}")
        pg = shards.to_partitioned_graph()
        ds_name, num_edges = shards.meta["name"], shards.meta["nnz"]
    else:
        ds = get_dataset(args.dataset, scale_vertices=args.vertices,
                         seed=args.seed)
        clusters = args.clusters
        if args.sample_kind == "partition" and clusters == 0:
            # default: the largest q in {8,4,2,1} that tiles the per-range
            # batch, cluster size b_local/q, count rounded so the epoch
            # schedule's dp-disjoint slicing divides evenly
            b_loc = args.batch // args.g
            q = next(q for q in (8, 4, 2, 1) if b_loc % q == 0)
            cs = b_loc // q
            n_loc0 = -(-ds.num_vertices // args.g)
            clusters = -(-(-(-n_loc0 // cs)) // (q * args.gd)) \
                * (q * args.gd)
        pg = build_partitioned_graph(ds, g=args.g, clusters=clusters)
        ds_name, num_edges = ds.name, ds.num_edges
    cfg = GM.GCNConfig(
        d_in=pg.feature_dim, d_hidden=args.d_hidden,
        num_layers=args.layers, num_classes=pg.num_classes,
        dropout=args.dropout)
    mesh = fourd.make_mesh_4d(args.gd, args.g)
    opts = fourd.TrainOptions(
        bf16_collectives=args.bf16_collectives,
        fused_elementwise=args.fused_elementwise,
        reshard_impl=args.reshard, overlap_impl=args.overlap,
        compress=args.compress, compress_schedule=args.compress_schedule,
        dropout=args.dropout, seed=args.seed,
        sample_mode=args.sample_mode, sample_kind=args.sample_kind,
        clusters=args.clusters, walk_len=args.walk_len, walk_k=args.walk_k)
    plan = fourd.build_plan(pg, cfg, mesh, batch=args.batch, opts=opts)

    graph = plan.shard_graph(pg)
    if args.epochs is not None:
        # epoch-parameterized: warmup/decay track the dataset's epoch
        # length, not a step count that shifts with batch size
        total_steps = args.epochs * plan.scfg.steps_per_epoch
        lr = linear_warmup_cosine_epochs(
            args.lr, warmup_epochs=min(1.0, 20 / plan.scfg.steps_per_epoch),
            epochs=args.epochs, steps_per_epoch=plan.scfg.steps_per_epoch)
    else:
        total_steps = args.steps
        lr = linear_warmup_cosine(args.lr, 20, total_steps)
    opt = AdamW(lr=lr, weight_decay=1e-4, grad_clip=1.0)
    loop = TrainLoopConfig(
        total_steps=None if args.epochs is not None else args.steps,
        epochs=args.epochs, chunk_size=args.chunk_size,
        prefetch=args.prefetch,
        eval_every=None if args.eval_every_epochs else args.eval_every,
        eval_every_epochs=args.eval_every_epochs,
        target_acc=args.target_acc, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, async_ckpt=not args.sync_ckpt)
    # one tracer for the whole run: library phases (sample/extract/engine)
    # report to the global, the Trainer's host boundaries to the same one
    tracer = set_tracer(Tracer(enabled=True, trace_dir=args.trace_dir))
    trainer = Trainer(plan, opt, loop, tracer=tracer)

    state = trainer.init_state(
        plan.shard_params(GM.init_params(jax.random.PRNGKey(args.seed), cfg)),
        graph)
    if args.resume:
        # a silent fresh start would discard the run --resume promised to
        # continue — fail loudly instead
        if not args.ckpt_dir:
            raise SystemExit("--resume requires --ckpt-dir")
        restored = trainer.restore(state, graph=graph)
        if restored is None:
            raise SystemExit(
                f"--resume: no TrainState checkpoint in {args.ckpt_dir}")
        state = restored
        print(f"resumed: step {int(state.step)} epoch {int(state.epoch)}")

    print(f"ScaleGNN 4D: mesh {dict(mesh.shape)}  dataset {ds_name} "
          f"N={pg.n} E={num_edges} batch={args.batch} "
          f"sample-kind={args.sample_kind} sample-mode={args.sample_mode} "
          f"steps={total_steps} (epochs={args.epochs}, "
          f"{plan.scfg.steps_per_epoch}/epoch) "
          f"prefetch={args.prefetch} chunk={args.chunk_size}")

    t0 = time.time()

    def report(step, loss, acc):
        print(f"step {step:5d}  loss {loss:.4f}  "
              f"full-graph acc {acc:.4f}  t={time.time()-t0:.1f}s")

    tracer.start_profile()
    try:
        state, log = trainer.run(state, graph, report=report)
    finally:
        tracer.stop_profile()

    # the final accuracy: reuse the boundary eval when it already covered
    # the last step (never evaluate twice for one report)
    if log.evals and log.evals[-1][0] == int(state.step):
        acc = log.evals[-1][1]
    else:
        acc = float(trainer.eval_fn(state.params, graph))
    dt = time.time() - t0
    print(f"done: steps<= {total_steps}  time {dt:.1f}s  "
          f"full-graph accuracy {acc:.4f}")
    if log.final_ckpt:
        # run() persists the final state itself (boundary-saved or not)
        print("checkpoint:", log.final_ckpt)
    print(f"ms/step {log.ms_per_step:.2f}  eval_s {log.eval_s:.2f}  "
          f"ckpt_overlap_s {log.ckpt_overlap_s:.2f}")

    if args.metrics_json:
        doc = {
            "run": {
                "dataset": ds_name, "mesh": dict(mesh.shape),
                "batch": args.batch, "steps": total_steps,
                "sample_mode": args.sample_mode,
                "sample_kind": args.sample_kind,
                "prefetch": args.prefetch, "chunk_size": args.chunk_size,
                "final_acc": acc, "wall_s": dt,
            },
            "runlog": dataclasses.asdict(log),
            "spans": tracer.summary(),
        }
        with open(args.metrics_json, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print("metrics:", args.metrics_json)
    return log


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
