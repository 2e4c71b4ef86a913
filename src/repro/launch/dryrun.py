import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count at first init), so this module has no module docstring and
# no `from __future__` import.
#
# Multi-pod dry-run: lower + compile every (architecture x input shape x
# mesh) combination on 512 placeholder host devices. For each combination:
#   compiled.memory_analysis()  — per-device bytes (proves fit / OOM)
#   compiled.cost_analysis()    — HLO FLOPs + bytes for the roofline
#   collective bytes parsed from the partitioned HLO text
# Results land as JSON under experiments/dryrun/. Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
#       [--mesh single|multi] [--gnn]
# (no `from __future__` import: the XLA_FLAGS lines must stay first)
import argparse
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_IDS, INPUT_SHAPES, InputShape, get_config,
                           shape_applicable)
from repro.launch.mesh import make_production_mesh, make_production_mesh_4d
from repro.models import sharding as SH
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import AdamW

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs — never allocated)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def memory_stub_spec(cfg: ModelConfig, batch: int):
    """The modality-frontend stub (DESIGN.md §6): precomputed embeddings."""
    if cfg.family == "vlm":
        return _sds((batch, cfg.n_image_tokens, cfg.d_model),
                    cfg.compute_dtype)
    if cfg.family == "audio":
        return _sds((batch, cfg.encoder.n_frames, cfg.d_model),
                    cfg.compute_dtype)
    return None


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    b, s = shape.global_batch, shape.seq_len
    mem = memory_stub_spec(cfg, b)
    if shape.kind == "train":
        out = {"tokens": _sds((b, s), jnp.int32),
               "targets": _sds((b, s), jnp.int32)}
    elif shape.kind == "prefill":
        out = {"tokens": _sds((b, s), jnp.int32)}
    else:  # decode: ONE new token against a seq_len cache
        cache = jax.eval_shape(
            lambda: T.init_cache(cfg, b, s))
        out = {"token": _sds((b, 1), jnp.int32), "cache": cache}
    if mem is not None and shape.kind != "decode":
        out["memory"] = mem
    if mem is not None and shape.kind == "decode" and cfg.family in (
            "vlm", "audio"):
        pass  # cross-KV already lives inside the cache
    return out


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def build_step(cfg: ModelConfig, shape: InputShape, mesh):
    """Returns (fn, example_inputs, in_shardings, out_shardings)."""
    params = T.abstract_params(cfg)
    big = cfg.num_params() > 3e9
    pspec = SH.param_pspecs(cfg, mesh, params, fsdp=big)
    ns = lambda tree: SH.named(mesh, tree)
    ins = input_specs(cfg, shape)
    dp = SH.batch_pspec(mesh, shape.global_batch, extra_dims=1)
    seq_par = NamedSharding(
        mesh, P(dp[0], "model", None))       # sequence parallelism
    opt = AdamW(lr=1e-4)

    if shape.kind == "train":
        opt_state = jax.eval_shape(opt.init, params)
        opt_spec = {"step": P(), "mu": pspec, "nu": pspec}
        mem = ins.get("memory")
        head_sh = NamedSharding(mesh, P(None, "model"))
        # gradient accumulation: same global batch per optimizer step, but
        # the live activation stack shrinks n_micro-fold — required to fit
        # the ~100B configs' train_4k on 16 GB/chip
        n_micro = 8 if cfg.num_params() > 2e10 else 1
        b = shape.global_batch
        micro_dp = NamedSharding(mesh, P(None, dp[0], None))

        def train_step(p, o, tokens, targets, memory=None):
            with T.run_options(act_sharding=seq_par, remat=True,
                               head_sharding=head_sh):
                def loss_fn(pp, tk, tg, mm):
                    logits, aux = T.forward_train(pp, tk, cfg, memory=mm)
                    return (T.lm_loss(logits, tg, cfg.vocab)
                            + 0.01 * jnp.asarray(aux, jnp.float32))

                if n_micro == 1:
                    loss, grads = jax.value_and_grad(loss_fn)(
                        p, tokens, targets, memory)
                else:
                    tk = jax.lax.with_sharding_constraint(
                        tokens.reshape(n_micro, b // n_micro, -1), micro_dp)
                    tg = jax.lax.with_sharding_constraint(
                        targets.reshape(n_micro, b // n_micro, -1), micro_dp)
                    mm = (None if memory is None else memory.reshape(
                        (n_micro, b // n_micro) + memory.shape[1:]))

                    def micro(acc, xs):
                        g_acc, l_acc = acc
                        tki, tgi = xs[0], xs[1]
                        mi = xs[2] if len(xs) > 2 else None
                        li, gi = jax.value_and_grad(loss_fn)(
                            p, tki, tgi, mi)
                        g_acc = jax.tree.map(
                            lambda a, g_: a + g_.astype(jnp.float32),
                            g_acc, gi)
                        return (g_acc, l_acc + li), None

                    g0 = jax.tree.map(
                        lambda x, sp: jax.lax.with_sharding_constraint(
                            jnp.zeros(x.shape, jnp.float32),
                            NamedSharding(mesh, sp)), p, pspec)
                    xs = (tk, tg) if mm is None else (tk, tg, mm)
                    (grads, loss), _ = jax.lax.scan(
                        micro, (g0, jnp.zeros((), jnp.float32)), xs)
                    grads = jax.tree.map(lambda g_: g_ / n_micro, grads)
                    loss = loss / n_micro
                p2, o2 = opt.update(p, grads, o)
                return p2, o2, loss

        args = [params, opt_state, ins["tokens"], ins["targets"]]
        in_sh = [ns(pspec), ns(opt_spec), ns(dp), ns(dp)]
        out_sh = (ns(pspec), ns(opt_spec), NamedSharding(mesh, P()))
        if mem is not None:
            args.append(mem)
            in_sh.append(NamedSharding(mesh, P(dp[0], None, None)))
        return train_step, args, tuple(in_sh), out_sh

    if shape.kind == "prefill":
        mem = ins.get("memory")
        cache_shape = jax.eval_shape(
            lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len))
        cache_spec = SH.cache_pspecs(cfg, mesh, cache_shape,
                                     shape.global_batch)

        def prefill_step(p, tokens, memory=None):
            with T.run_options(act_sharding=seq_par, remat=False):
                return T.prefill(p, tokens, cfg, max_len=shape.seq_len,
                                 memory=memory)

        args = [params, ins["tokens"]]
        in_sh = [ns(pspec), ns(dp)]
        out_sh = (NamedSharding(mesh, P()), ns(cache_spec))
        if mem is not None:
            args.append(mem)
            in_sh.append(NamedSharding(mesh, P(dp[0], None, None)))
        return prefill_step, args, tuple(in_sh), out_sh

    # decode
    cache_spec = SH.cache_pspecs(cfg, mesh, ins["cache"],
                                 shape.global_batch)

    def serve_step(p, token, cache):
        with T.run_options(act_sharding=None, remat=False):
            return T.decode_step(p, token, cache, cfg)

    args = [params, ins["token"], ins["cache"]]
    in_sh = (ns(pspec), ns(dp), ns(cache_spec))
    out_sh = (NamedSharding(mesh, P()), ns(cache_spec))
    return serve_step, args, in_sh, out_sh


# ---------------------------------------------------------------------------
# Collective-byte extraction from partitioned HLO — delegated to the shared
# analyzer in repro.obs.hlo (same regexes, ONE owner; this module predates
# it and keeps the thin Dict-returning wrapper its reports were built on)
# ---------------------------------------------------------------------------

from repro.obs import hlo as _obs_hlo  # noqa: E402

_COLLECTIVES = _obs_hlo.COLLECTIVES


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes produced by each collective category, parsed from
    the partitioned module (result shapes; a conservative volume proxy)."""
    return dict(_obs_hlo.parse_hlo(hlo_text).bytes)


# ---------------------------------------------------------------------------
# Dry-run driver
# ---------------------------------------------------------------------------

def set_optimized_knobs(mesh, enable: bool = True) -> None:
    """§Perf beyond-paper attention optimizations (EXPERIMENTS.md):
    H1.1 causal q-chunking + H1.3 sequence-sharded q / replicated-KV
    attention layout. Off = paper-faithful baseline path."""
    from repro.models import layers as L
    if not enable:
        L.set_q_chunk(None)
        L.set_attn_sharding(None)
        return
    # batch dim must use ALL DP axes (pod + data) or the constraint fights
    # the batch sharding and GSPMD replicates (measured: 75 GiB temp on
    # the multi-pod prefill with the data-only spec)
    from repro.models.sharding import dp_axes
    dp = dp_axes(mesh)
    dp = dp if len(dp) > 1 else dp[0]
    L.set_q_chunk(2048)
    L.set_attn_sharding((
        NamedSharding(mesh, P(dp, "model", None, None)),
        NamedSharding(mesh, P(dp, None, None, None))))


def run_one(arch: str, shape_name: str, multi_pod: bool,
            save: bool = True, optimized: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = ("multi" if multi_pod else "single") + (
        "_opt" if optimized else "")
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "family": cfg.family, "source": cfg.source,
        "params": cfg.num_params(), "active_params":
            cfg.num_active_params(),
    }
    if not shape_applicable(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = ("full-attention arch: 524k dense KV decode is "
                         "architecturally unsupported (DESIGN.md §6)")
        _save(rec, save)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    set_optimized_knobs(mesh, optimized)
    t0 = time.time()
    try:
        fn, args, in_sh, out_sh = build_step(cfg, shape, mesh)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        from repro.launch.roofline import analyze_hlo
        loop_aware = analyze_hlo(hlo)
        n_dev = int(np.prod(list(mesh.shape.values())))
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "n_devices": n_dev,
            # raw XLA numbers (while bodies counted ONCE — see roofline.py)
            "flops_per_device": float(cost.get("flops", 0.0)),
            "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
            "collective_bytes_per_device": coll,
            # loop-aware per-device costs (trip-count corrected)
            "loop_aware": loop_aware,
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
            },
        })
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        set_optimized_knobs(mesh, False)
    _save(rec, save)
    return rec


def _save(rec, save):
    if not save:
        return
    os.makedirs(OUT_DIR, exist_ok=True)
    fn = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json"
    with open(os.path.join(OUT_DIR, fn), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def run_gnn_dryrun(multi_pod: bool, save: bool = True) -> Dict[str, Any]:
    """Dry-run the paper's own 4D GNN train step at production scale, at
    ogbn-papers100M-like dimensions (batch 131072, d_in 128, d_h 256, 3L)."""
    from repro.core import fourd, gcn_model as GM
    from repro.graphs.partition import PartitionedGraph

    mesh = make_production_mesh_4d(multi_pod=multi_pod)
    g = mesh.shape["x"]
    mesh_name = "multi" if multi_pod else "single"
    n_pad = 111_060_992 // (g * g) * (g * g)  # papers100M scale, padded
    n_pad = (n_pad // g) * g
    n_local = n_pad // g
    avg_deg = 16
    e_pad = n_local * n_local // 1  # placeholder; blocks via SDS only
    # realistic block nnz: edges/blocks * safety
    e_pad = int(1_615_685_872 / (g * g) * 1.5)
    batch = 131_072
    cfg = GM.GCNConfig(d_in=128, d_hidden=256, num_layers=3,
                       num_classes=176 // g * g, dropout=0.1)
    pg = PartitionedGraph(
        n=n_pad, n_pad=n_pad, g=g, n_local=n_local, e_pad=e_pad,
        block_rp=None, block_ci=None, block_val=None,
        max_block_row_nnz=avg_deg * 4,
        features=None, labels=None, train_mask=None,
        num_classes=cfg.num_classes)
    plan = fourd.build_plan(pg, cfg, mesh, batch=batch,
                            opts=fourd.TrainOptions(dropout=0.1),
                            e_cap=(batch // g) * avg_deg * 4)
    from repro.optim import AdamW as _A
    train_step = fourd.make_train_step(plan, _A(lr=1e-3))

    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(
        lambda: GM.init_params(jax.random.PRNGKey(0), cfg))
    opt_state = jax.eval_shape(_A(lr=1e-3).init, params)
    blk = lambda: (sds((g, g, n_local + 1), jnp.int32),
                   sds((g, g, e_pad), jnp.int32),
                   sds((g, g, e_pad), jnp.float32))
    graph = {"adj1": blk(), "adj2": blk(), "adj3": blk(),
             "features": sds((n_pad, cfg.d_in), jnp.float32),
             "labels": sds((n_pad,), jnp.int32)}
    rec = {"arch": "scalegnn-gcn-papers100M", "shape": "minibatch_131k",
           "mesh": mesh_name, "family": "gnn",
           "params": sum(int(np.prod(l.shape))
                         for l in jax.tree.leaves(params))}
    t0 = time.time()
    try:
        # shard the abstract inputs
        ns = lambda sp: NamedSharding(mesh, sp)
        graph_sh = {k: jax.tree.map(lambda s: s, v) for k, v in
                    graph.items()}
        lowered = train_step.lower(params, opt_state, graph_sh,
                                   jnp.zeros((), jnp.int32))
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        from repro.launch.roofline import analyze_hlo
        rec.update({
            "status": "ok", "lower_s": round(t_lower, 1),
            "compile_s": round(time.time() - t0, 1),
            "n_devices": int(np.prod(list(mesh.shape.values()))),
            "flops_per_device": float(
                compiled.cost_analysis().get("flops", 0.0)),
            "bytes_per_device": float(
                compiled.cost_analysis().get("bytes accessed", 0.0)),
            "collective_bytes_per_device":
                collective_bytes(compiled.as_text()),
            "loop_aware": analyze_hlo(compiled.as_text()),
            "memory": {
                "argument_bytes":
                    compiled.memory_analysis().argument_size_in_bytes,
                "temp_bytes":
                    compiled.memory_analysis().temp_size_in_bytes,
            },
        })
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(
                OUT_DIR, f"scalegnn_gcn_{mesh_name}.json"), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default=None, choices=["single", "multi"])
    ap.add_argument("--gnn", action="store_true",
                    help="dry-run the paper's 4D GNN step instead")
    ap.add_argument("--optimized", action="store_true",
                    help="enable the §Perf beyond-paper attention "
                         "optimizations (records saved with _opt suffix)")
    args = ap.parse_args()

    meshes = ([args.mesh] if args.mesh else ["single", "multi"])
    if args.gnn:
        for m in meshes:
            rec = run_gnn_dryrun(multi_pod=(m == "multi"))
            print(json.dumps({k: rec[k] for k in rec
                              if k != "traceback"}, indent=1,
                             default=str))
        return

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    n_ok = n_skip = n_err = 0
    for a in archs:
        for s in shapes:
            for m in meshes:
                rec = run_one(a, s, multi_pod=(m == "multi"),
                              optimized=args.optimized)
                tag = rec["status"]
                if tag == "ok":
                    n_ok += 1
                    print(f"OK    {a:26s} {s:12s} {m:6s} "
                          f"compile={rec['compile_s']:7.1f}s "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB")
                elif tag == "skipped":
                    n_skip += 1
                    print(f"SKIP  {a:26s} {s:12s} {m:6s} ({rec['reason'][:40]})")
                else:
                    n_err += 1
                    print(f"ERROR {a:26s} {s:12s} {m:6s} {rec['error'][:120]}")
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
