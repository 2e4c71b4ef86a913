#!/usr/bin/env python3
"""Bring-up check: train the paper-width GCN on a TPU through the normal
runtime, and check its step-0 loss against a plain float32 reference.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the dp=4 path only

Data: an ogbn-products-shaped stand-in generated from ``--seed`` —
262,144 vertices, the registry's average degree (25), 100 features and 47
classes — and a GCN at the paper's widths (d_hidden 256, 3 layers).

* Phase A, the default path: ``repro.launch.train.main`` on a (1,1,1,1)
  mesh (dense SpMM, jax extraction, unfused tail), 16 steps in two scan
  chunks and one full-graph eval.
* Phase B, the kernel path: the same plan through ``train.Trainer`` with
  every Pallas kernel ``TrainOptions`` can select: block-ELL SpMM, fused
  extraction and the fused elementwise tail.
* ``--chips 4``: mesh (4,1,1,1), data parallelism with real gradient
  all-reduces (g=1, so every PMM collective has one participant). The
  per-group losses and the gradients are compared with four single-device
  reference minibatches and their averaged gradients, then a few Trainer
  steps run.

Dropout is 0 in every phase, so a training-mode step 0 computes the same
function as the reference's eval-mode forward.

Tolerances. Phases A and B run at the default matmul precision; the
reference runs at "highest" (true float32). On a TPU v5e with JAX 0.9.0
their step-0 losses agreed to 7.7e-7 relative. The bound, 1e-3, leaves
three orders of magnitude for compiler changes and stays below one bf16
rounding (2^-9, about 2e-3), so float32 is what the check holds. The
four-chip comparison runs both sides at "highest", where only the
reduction order differs, and keeps the multi-device test's bounds: losses
to 1e-4, gradients to 1e-3 of each leaf's largest entry.

Every timing printed here is a bring-up reading, not a benchmark. The last
line of standard output is the JSON result; any failed check exits non-zero
before it. With no TPU the script fails: it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

DATASET = "ogbn-products"
N_VERTICES = 262_144
BATCH = 1024
D_HIDDEN = 256
LAYERS = 3
LOSS_RTOL = 1e-3           # default precision vs the float32 reference
LOSS_ATOL_F32 = 1e-4       # "highest" on both sides (four chips)
GRAD_RTOL_F32 = 1e-3


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class CompileClock:
    """Seconds spent in XLA compilation (or reading a compiled program
    back from the persistent cache), per jitted function name, and the
    number of persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.by_name = {}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.by_name[name] = self.by_name.get(name, 0.0) + duration

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def total(self) -> float:
        return sum(self.by_name.values())


def make_dataset(seed: int):
    from repro.graphs import get_dataset
    t0 = time.perf_counter()
    ds = get_dataset(DATASET, scale_vertices=N_VERTICES, seed=seed)
    print(f"dataset: {ds.name} N={ds.num_vertices} nnz={ds.adj_norm.nnz} "
          f"d_in={ds.feature_dim} classes={ds.num_classes} "
          f"(host generation {time.perf_counter() - t0:.1f} s)")
    return ds


def build_plan(ds, gd: int, seed: int, **opts):
    """The (gd,1,1,1) plan of the smoke at the paper's widths."""
    from repro.core import fourd, gcn_model as GM
    from repro.graphs import build_partitioned_graph

    pg = build_partitioned_graph(ds, g=1)
    cfg = GM.GCNConfig(d_in=pg.feature_dim, d_hidden=D_HIDDEN,
                       num_layers=LAYERS, num_classes=pg.num_classes,
                       dropout=0.0)
    plan = fourd.build_plan(
        pg, cfg, fourd.make_mesh_4d(gd, 1), batch=BATCH,
        opts=fourd.TrainOptions(dropout=0.0, seed=seed, **opts))
    return pg, cfg, plan


def reference(ds, pg, cfg, scfg, seed: int, groups: int):
    """Plain float32 single-device reference: the step-0 loss of each DP
    group's minibatch — ``gcn_model.forward`` + ``cross_entropy_loss`` on
    ``make_minibatch_stratified(step_key(seed, 0, d))`` — and the gradient
    of their mean."""
    import jax
    import jax.numpy as jnp
    from repro.core import gcn_model as GM, sampling as S

    A = ds.adj_norm
    rp, ci, val = (jnp.asarray(A.indptr), jnp.asarray(A.indices),
                   jnp.asarray(A.data))
    feats, labels = jnp.asarray(pg.features), jnp.asarray(pg.labels)
    mbs = [S.make_minibatch_stratified(S.step_key(seed, jnp.asarray(0), d),
                                       rp, ci, val, feats, labels, scfg)
           for d in range(groups)]

    def losses(p):
        return jnp.stack([
            GM.cross_entropy_loss(
                GM.forward(p, mb.adj, mb.feats, cfg, train=False), mb.labels)
            for mb in mbs])

    params = GM.init_params(jax.random.PRNGKey(seed), cfg)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(losses)(params)
        grads = jax.jit(jax.grad(lambda p: losses(p).mean()))(params)
    return [float(x) for x in ref], grads


def loss_check(name: str, got: float, ref: float) -> None:
    rel = abs(got - ref) / abs(ref)
    print(f"{name}: step-0 loss {got:.6f}  reference {ref:.6f}  "
          f"rel err {rel:.3e}  (tol {LOSS_RTOL:g})")
    check(math.isfinite(got), f"{name}: step-0 loss is not finite")
    check(rel <= LOSS_RTOL, f"{name}: step-0 loss off the reference")


def run_trainer(name: str, pg, cfg, plan, seed: int, clock: CompileClock,
                steps: int = 8, chunk: int = 4):
    """``steps`` Trainer steps (compilation included), then one more chunk
    timed on its own. Returns the RunLog and the number of Pallas calls
    in the lowered step."""
    import jax
    import numpy as np
    from repro.core import gcn_model as GM
    from repro.optim import AdamW
    from repro.train import Trainer, TrainLoopConfig

    c0 = clock.total()
    graph = plan.shard_graph(pg)
    trainer = Trainer(plan, AdamW(lr=5e-3),
                      TrainLoopConfig(total_steps=steps, chunk_size=chunk))
    state = trainer.init_state(
        plan.shard_params(GM.init_params(jax.random.PRNGKey(seed), cfg)),
        graph)
    state, log = trainer.run(state, graph)
    kernels = trainer.compiled_chunk(chunk).lower(state, graph).as_text() \
        .count("tpu_custom_call")
    t0 = time.perf_counter()
    state, more = trainer.compiled_chunk(chunk)(state, graph)
    more = np.asarray(jax.block_until_ready(more))
    steady_ms = (time.perf_counter() - t0) * 1e3 / chunk
    losses = np.asarray(log.losses + list(more))
    print(f"{name}: compile {clock.total() - c0:.1f} s  "
          f"steady {steady_ms:.2f} ms/step (bring-up reading, not a "
          f"benchmark)  Pallas calls in the step: {kernels}  "
          f"losses {losses[0]:.4f} -> {losses[-1]:.4f}")
    check(bool(np.all(np.isfinite(losses))), f"{name}: non-finite loss")
    return log, kernels


def phase_a(ds, seed: int, clock: CompileClock) -> None:
    """The default path through the CLI entry point, checked against the
    reference's step-0 loss."""
    from repro.launch import train as cli

    steps = 16
    c0, t0 = dict(clock.by_name), time.perf_counter()
    log = cli.main([
        "--dataset", DATASET, "--vertices", str(N_VERTICES),
        "--gd", "1", "--g", "1", "--d-hidden", str(D_HIDDEN),
        "--layers", str(LAYERS), "--batch", str(BATCH),
        "--steps", str(steps), "--chunk-size", "8", "--eval-every", "16",
        "--dropout", "0", "--seed", str(seed)])
    wall = time.perf_counter() - t0
    spent = {k: v - c0.get(k, 0.0) for k, v in clock.by_name.items()}
    # the first chunk's compile is inside the RunLog's train wall time
    chunk_s = spent.get("jit(chunk)", 0.0)
    steady_ms = (log.ms_per_step * steps - 1e3 * chunk_s) / steps
    print(f"phase A: wall {wall:.1f} s  compile {sum(spent.values()):.1f} s "
          f"(train chunk {chunk_s:.1f} s)  steady "
          f"{steady_ms:.2f} ms/step (RunLog minus compile; bring-up "
          f"reading, not a benchmark)")
    check(len(log.losses) == steps, "phase A: RunLog is missing steps")
    check(all(math.isfinite(x) for x in log.losses),
          "phase A: non-finite loss")
    check(bool(log.evals), "phase A: no full-graph eval ran")
    acc = log.evals[-1][1]
    print(f"phase A: full-graph accuracy {acc:.4f} after {steps} steps")
    check(math.isfinite(acc) and 0.0 <= acc <= 1.0,
          "phase A: eval accuracy is not a fraction")

    pg, cfg, plan = build_plan(ds, 1, seed)
    ref, _ = reference(ds, pg, cfg, plan.scfg, seed, 1)
    loss_check("phase A", log.losses[0], ref[0])


def phase_b(ds, seed: int, clock: CompileClock) -> None:
    """Every selectable Pallas kernel, compiled, through the Trainer."""
    from repro.kernels import backend

    check(not backend.interpret_mode(),
          "phase B: Pallas would run in interpret mode on this backend")
    pg, cfg, plan = build_plan(ds, 1, seed, spmm_impl="ell",
                               extract_impl="pallas",
                               fused_elementwise=True)
    log, kernels = run_trainer("phase B", pg, cfg, plan, seed, clock)
    check(kernels > 0, "phase B: no Pallas kernel in the compiled step")
    ref, _ = reference(ds, pg, cfg, plan.scfg, seed, 1)
    loss_check("phase B", log.losses[0], ref[0])


def phase_four_chips(ds, seed: int, clock: CompileClock) -> None:
    """dp=4 on mesh (4,1,1,1): losses and gradients against the reference,
    then a few Trainer steps."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import fourd, gcn_model as GM

    gd = 4
    pg, cfg, plan = build_plan(ds, gd, seed)
    print(f"four chips: mesh {dict(plan.mesh.shape)} (g=1: the PMM "
          f"collectives have one participant; the gradient all-reduce "
          f"spans {gd} chips)")
    graph = plan.shard_graph(pg)
    params = plan.shard_params(GM.init_params(jax.random.PRNGKey(seed), cfg))
    loss_fn = fourd.make_loss_fn(plan, train=True)
    step = jnp.asarray(0)
    c0 = clock.total()
    with jax.default_matmul_precision("highest"):
        losses = jax.jit(loss_fn)(params, graph, step)
        grads = jax.jit(jax.grad(
            lambda p: loss_fn(p, graph, step).mean()))(params)
    losses = [float(x) for x in np.asarray(losses)]
    print(f"four chips: loss and grad compile {clock.total() - c0:.1f} s")

    ref, ref_grads = reference(ds, pg, cfg, plan.scfg, seed, gd)
    for d in range(gd):
        err = abs(losses[d] - ref[d])
        print(f"four chips: group {d} loss {losses[d]:.6f}  reference "
              f"{ref[d]:.6f}  abs err {err:.3e}  (tol {LOSS_ATOL_F32:g})")
        check(err <= LOSS_ATOL_F32, f"four chips: group {d} loss")
    worst = 0.0
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        a, b = np.asarray(a), np.asarray(b)
        worst = max(worst, float(np.abs(a - b).max()
                                 / (np.abs(b).max() + 1e-30)))
    print(f"four chips: worst gradient leaf rel err {worst:.3e}  "
          f"(tol {GRAD_RTOL_F32:g})")
    check(worst <= GRAD_RTOL_F32, "four chips: gradients off the reference")
    run_trainer("four chips", pg, cfg, plan, seed, clock)


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Bring-up check of the GCN training path on a TPU.")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip data-parallel path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    src = os.path.join(REPO, "src")
    check(os.path.isdir(os.path.join(src, "repro")),
          f"no src/repro beside {__file__}: run from a checkout")
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}  compile cache: {cache_dir}")
    check(dev.platform == "tpu", f"no TPU: JAX found {devices}")
    check(len(devices) >= args.chips,
          f"--chips {args.chips}, but JAX found {len(devices)} devices")

    clock = CompileClock()
    ds = make_dataset(args.seed)
    if args.chips == 4:
        phase_four_chips(ds, args.seed, clock)
    else:
        phase_a(ds, args.seed, clock)
        phase_b(ds, args.seed, clock)
    print(f"compile seconds, all phases: {clock.total():.1f}  "
          f"persistent-cache hits: {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
