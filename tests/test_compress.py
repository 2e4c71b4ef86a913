"""Compressed collectives: quantized wire + error feedback (ROADMAP item 1).

Four layers of evidence, mirroring how the feature can break:

1. **Quantizer numerics** (single device, deterministic seed sweep) —
   absmax int8/int4 round-trips bound the per-element error by half a
   scale step, map finite inputs to finite outputs and zeros to zeros
   exactly, and the int4 nibble pack/unpack is a perfect inverse on
   [-8, 7]. The randomized-input hypothesis versions of these properties
   are in ``tests/test_compress_properties.py`` (module-skips without
   hypothesis; these twins keep the codec covered regardless).
2. **Schedule plumbing** — the ``wire_format`` ladder ramps bf16 -> int8 ->
   int4 by depth, capped at the configured format; ``TrainOptions``
   validation rejects shapes int4 cannot pack.
3. **Degenerate-grid exactness** ((1,1,1)x1, in-process) — at g=1 there is
   no wire, so a quantized plan must produce the BIT-identical loss of the
   uncompressed plan and an all-zero EF residual; ``compress="none"``
   returns through the exact pre-compression code path (2-tuple engine
   contract, no EF state anywhere in the Trainer).
4. **The real (2,2,2)x1 mesh** (one forced 8-device subprocess, tier-1) —
   the explicit backward structure (pad + two tiled reduce-scatters) is
   bitwise the ``jax.vjp`` transpose of the FP32 reshard; the compiled
   int8 train step moves >= 4x fewer reshard bytes than "none" with the
   dominant payload in true s8; int4 halves the s8 payload again; sampling
   stays zero-collective; and a short EF-compensated int8 run lands within
   noise of the FP32 loss.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import forward, fourd, gcn_model as M
from repro.core.precision import (
    absmax_scale, dequantize, pack_int4, quantize, unpack_int4,
)
from repro.graphs import (
    build_partitioned_graph, make_synthetic_dataset,
)
from repro.obs import parse_hlo
from repro.optim import AdamW
from repro.train import Trainer, TrainLoopConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# 1. quantizer round-trip properties (deterministic seed sweep)
# ---------------------------------------------------------------------------

def _rows(seed, shape, log2_mag=0.0):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape)
        * (2.0 ** log2_mag), jnp.float32)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("seed,shape,mag", [
    (0, (1, 2), 0.0), (1, (5, 12), -4.0), (2, (3, 8), 8.0),
    (3, (7, 4), 3.5), (4, (2, 32), -1.0),
])
def test_roundtrip_error_bounded_by_half_scale(bits, seed, shape, mag):
    x = _rows(seed, shape, mag)
    q, sc = quantize(x, bits)
    y = np.asarray(dequantize(q, sc, bits))
    assert np.isfinite(y).all()
    # absmax symmetric rounding: |x - deq(q)| <= scale/2 per row (+ float
    # slack for the scale division itself)
    bound = np.asarray(sc) * 0.5 * (1 + 1e-5) + 1e-12
    assert (np.abs(np.asarray(x) - y) <= bound).all(), (
        np.abs(np.asarray(x) - y).max(), bound.max())


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_quantize_is_idempotent_on_its_own_grid(bits, seed):
    """deq(quant(x)) is a fixed point: re-quantizing moves nothing."""
    q, sc = quantize(_rows(seed, (4, 10), 2.0), bits)
    y = dequantize(q, sc, bits)
    q2, sc2 = quantize(y, bits)
    y2 = np.asarray(dequantize(q2, sc2, bits))
    assert np.allclose(np.asarray(y), y2, rtol=1e-6, atol=1e-12)


@pytest.mark.parametrize("bits", [8, 4])
def test_zero_rows_quantize_exactly(bits):
    x = jnp.zeros((3, 8), jnp.float32)
    q, sc = quantize(x, bits)
    assert np.asarray(sc).tolist() == [[1.0]] * 3      # all-zero guard
    assert (np.asarray(dequantize(q, sc, bits)) == 0).all()
    # mixed: a zero row next to a live one stays exactly zero
    x = x.at[1].set(jnp.arange(8, dtype=jnp.float32))
    q, sc = quantize(x, bits)
    y = np.asarray(dequantize(q, sc, bits))
    assert (y[0] == 0).all() and (y[2] == 0).all()


def test_int4_pack_unpack_inverse():
    # every representable nibble value, both positions in the packed byte
    q = jnp.asarray(np.arange(-8, 8, dtype=np.int8).reshape(1, 16))
    for arr in (q, jnp.roll(q, 1, axis=-1)):
        packed = pack_int4(arr)
        assert packed.dtype == jnp.int8
        assert packed.shape[-1] == arr.shape[-1] // 2  # half-width wire
        assert (np.asarray(unpack_int4(packed)) == np.asarray(arr)).all()


def test_absmax_scale_shapes():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 6)),
                    jnp.float32)
    sc = absmax_scale(x, 8)
    assert sc.shape == (4, 1) and sc.dtype == jnp.float32
    assert (np.asarray(sc) > 0).all()


# ---------------------------------------------------------------------------
# 2. the per-layer wire-format ladder + options validation
# ---------------------------------------------------------------------------

def test_wire_format_ladder():
    wf = forward.wire_format
    # uniform: every layer gets the configured format
    assert [wf("int8", "uniform", li, 4) for li in range(4)] == ["int8"] * 4
    # variable, cap int4: bf16 at the top ramping to int4 at the bottom
    assert wf("int4", "variable", 0, 3) == "bf16"
    assert wf("int4", "variable", 1, 3) == "int8"
    assert wf("int4", "variable", 2, 3) == "int4"
    # variable, cap int8: never reaches int4
    fmts = [wf("int8", "variable", li, 4) for li in range(4)]
    assert fmts[0] == "bf16" and fmts[-1] == "int8" and "int4" not in fmts
    # none/bf16 have nothing to ramp
    assert wf("none", "variable", 2, 3) == "none"
    assert wf("bf16", "variable", 0, 3) == "bf16"
    # single layer: the cap applies immediately
    assert wf("int4", "variable", 0, 1) == "int4"


def test_engine_validates_compress_options():
    """TrainOptions is a plain dataclass; the engine is the validation
    seam (every consumer — train/eval/prefetch/serving — builds one)."""
    cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=2, num_classes=4,
                      dropout=0.0)
    mk = lambda opts, g=1: forward.ForwardEngine.from_options(  # noqa: E731
        cfg, opts, grid_side=g)
    with pytest.raises(AssertionError):
        mk(fourd.TrainOptions(compress="int16"))
    with pytest.raises(AssertionError):
        mk(fourd.TrainOptions(compress="int8", compress_schedule="linear"))
    # int4 needs an even local column count: d_hidden=18, g=2 -> 9 columns
    cfg18 = M.GCNConfig(d_in=16, d_hidden=18, num_layers=2, num_classes=4,
                        dropout=0.0)
    with pytest.raises(AssertionError):
        forward.ForwardEngine.from_options(
            cfg18, fourd.TrainOptions(compress="int4"), grid_side=2)
    # g=1 keeps 18 columns (even) — fine
    forward.ForwardEngine.from_options(
        cfg18, fourd.TrainOptions(compress="int4"), grid_side=1)


def test_engine_ef_sites_cover_quantized_layers():
    ds = make_synthetic_dataset(n=128, num_classes=4, d_in=16, avg_degree=8,
                                seed=0)
    pg = build_partitioned_graph(ds, g=1)
    cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                      dropout=0.0)
    mesh = fourd.make_mesh_4d(1, 1)
    plan = fourd.build_plan(pg, cfg, mesh, batch=32,
                            opts=fourd.TrainOptions(compress="int8"))
    eng = plan.engine()
    assert eng.quantized
    sites = dict(eng.ef_sites())
    assert "proj" in sites and "head" in sites
    for li in range(cfg.num_layers):
        assert f"l{li}_spmm" in sites and f"l{li}_gemm" in sites
    # variable schedule quantizes only the deeper layers
    plan_v = fourd.build_plan(
        pg, cfg, mesh, batch=32,
        opts=fourd.TrainOptions(compress="int8",
                                compress_schedule="variable"))
    fmts = plan_v.engine().wire_formats
    assert fmts[0] == "bf16" and fmts[-1] == "int8"
    sites_v = dict(plan_v.engine().ef_sites())
    assert "l0_spmm" not in sites_v and f"l{cfg.num_layers-1}_spmm" in sites_v


# ---------------------------------------------------------------------------
# 3. degenerate grid: no wire -> exactness; "none" -> pre-compression path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_setup():
    ds = make_synthetic_dataset(n=256, num_classes=4, d_in=16, avg_degree=8,
                                seed=0)
    pg = build_partitioned_graph(ds, g=1)
    cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                      dropout=0.0)
    mesh = fourd.make_mesh_4d(1, 1)
    return pg, cfg, mesh


def _loss_and_ef(pg, cfg, mesh, compress):
    plan = fourd.build_plan(pg, cfg, mesh, batch=64,
                            opts=fourd.TrainOptions(compress=compress,
                                                    dropout=0.0))
    params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
    graph = plan.shard_graph(pg)
    loss_fn = fourd.make_loss_fn(plan, train=True)
    step = jnp.zeros((), jnp.int32)
    if plan.engine().quantized:
        ef = fourd.make_ef(plan)
        losses, new_ef = jax.jit(loss_fn)(params, graph, step, ef=ef)
        return np.asarray(losses), new_ef
    return np.asarray(jax.jit(loss_fn)(params, graph, step)), None


def test_g1_quantized_is_bitwise_exact(tiny_setup):
    """g=1 means zero ring hops: int8/int4 must be the identical program."""
    pg, cfg, mesh = tiny_setup
    l_none, _ = _loss_and_ef(pg, cfg, mesh, "none")
    for compress in ("int8", "int4"):
        l_q, new_ef = _loss_and_ef(pg, cfg, mesh, compress)
        assert l_none.tobytes() == l_q.tobytes(), (compress, l_none, l_q)
        assert all((np.asarray(v) == 0).all()
                   for v in jax.tree.leaves(new_ef)), (
            f"{compress}: EF residual nonzero at g=1 (no wire, no error)")


def test_none_mode_has_no_ef_state(tiny_setup):
    pg, cfg, mesh = tiny_setup
    plan = fourd.build_plan(pg, cfg, mesh, batch=64,
                            opts=fourd.TrainOptions(dropout=0.0))
    assert not plan.engine().quantized
    assert fourd.ef_specs(plan) is None and fourd.make_ef(plan) is None
    tr = Trainer(plan, AdamW(lr=1e-3),
                 TrainLoopConfig(total_steps=2, chunk_size=2, eval_every=0))
    params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
    graph = plan.shard_graph(pg)
    state = tr.init_state(params, graph)
    assert state.comm_ef is None
    state, log = tr.run(state, graph)
    assert state.comm_ef is None and len(log.losses) == 2


def test_trainer_carries_and_checkpoints_ef(tiny_setup, tmp_path):
    """The EF carry survives the scan, a save -> restore cycle, and
    restoring a pre-compression checkpoint backfills zero accumulators."""
    pg, cfg, mesh = tiny_setup
    plan = fourd.build_plan(pg, cfg, mesh, batch=64,
                            opts=fourd.TrainOptions(compress="int8",
                                                    dropout=0.0))
    loop = TrainLoopConfig(total_steps=4, chunk_size=2, eval_every=0,
                           ckpt_dir=str(tmp_path / "ef"))
    tr = Trainer(plan, AdamW(lr=1e-3), loop)
    # the compiled chunk donates its input state (params included), so each
    # init_state call needs fresh arrays
    fresh = lambda: plan.shard_params(  # noqa: E731
        M.init_params(jax.random.PRNGKey(1), cfg))
    graph = plan.shard_graph(pg)
    state = tr.init_state(fresh(), graph)
    assert state.comm_ef is not None
    state, _ = tr.run(state, graph)
    tr.save(state, sync=True)
    restored = tr.restore(tr.init_state(fresh(), graph))
    assert int(restored.step) == 4
    for a, b in zip(jax.tree.leaves(state.comm_ef),
                    jax.tree.leaves(restored.comm_ef)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()

    # pre-compression checkpoint (no comm_ef leaves) -> zero-EF backfill
    plan_n = fourd.build_plan(pg, cfg, mesh, batch=64,
                              opts=fourd.TrainOptions(dropout=0.0))
    loop_n = TrainLoopConfig(total_steps=2, chunk_size=2, eval_every=0,
                             ckpt_dir=str(tmp_path / "pre"))
    tr_n = Trainer(plan_n, AdamW(lr=1e-3), loop_n)
    st_n = tr_n.init_state(fresh(), graph)
    st_n, _ = tr_n.run(st_n, graph)
    tr_n.save(st_n, sync=True)
    loop_q = TrainLoopConfig(total_steps=4, chunk_size=2, eval_every=0,
                             ckpt_dir=str(tmp_path / "pre"))
    tr_q = Trainer(plan, AdamW(lr=1e-3), loop_q)
    back = tr_q.restore(tr_q.init_state(fresh(), graph))
    assert int(back.step) == 2 and back.comm_ef is not None
    assert all((np.asarray(v) == 0).all()
               for v in jax.tree.leaves(back.comm_ef))
    # and the backfilled state trains on
    back, log = tr_q.run(back, graph)
    assert int(back.step) == 4 and np.isfinite(log.losses).all()


def test_parse_hlo_attributes_sites_and_dtypes():
    """The byte-attribution seam the comm-bytes lane asserts through."""
    hlo = textwrap.dedent("""
    ENTRY %main {
      %p = f32[8,4]{1,0} parameter(0)
      %ag = s8[8,8]{1,0} all-gather(%p), metadata={op_name="jit(f)/reshard/ag"}
      %ar = f32[8,1]{1,0} all-reduce(%p), metadata={op_name="jit(f)/scales"}
    }
    """)
    rep = parse_hlo(hlo)
    assert rep.counts["all-gather"] == 1 and rep.counts["all-reduce"] == 1
    assert rep.bytes_by_dtype() == {"s8": 64, "f32": 32}
    assert rep.bytes_for_scope("reshard") == 64
    assert rep.bytes_for_scope("nope") == 0
    assert len(rep.for_scope("jit(f)")) == 2


# ---------------------------------------------------------------------------
# 4. the real (2,2,2)x1 mesh, one forced 8-device subprocess (tier-1)
# ---------------------------------------------------------------------------

def test_compressed_wire_on_2x2x2_mesh_subprocess():
    """The acceptance gates on a real multidevice mesh, tiny shapes:

    * the explicit transpose structure the quantized backward mirrors
      (pad + two tiled reduce-scatters) is BITWISE ``jax.vjp`` of the FP32
      reshard-gather;
    * the compiled int8 fwd+bwd step moves >= 4x fewer reshard-scope bytes
      than "none" and the dominant payload is true s8; int4 halves the s8
      payload again (nibble packing is real on the wire);
    * sampling remains zero-collective under compression;
    * a short int8 run with the EF carry lands within noise of FP32 loss.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    body = textwrap.dedent("""
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.graphs import make_synthetic_dataset, build_partitioned_graph
    from repro.core import fourd, pmm3d, pipeline as PL, gcn_model as M
    from repro.obs import comm_report
    from repro.optim import AdamW
    from repro.train import Trainer, TrainLoopConfig

    ds = make_synthetic_dataset(n=512, num_classes=4, d_in=16, avg_degree=8,
                                seed=0)
    pg = build_partitioned_graph(ds, g=2)
    cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                      dropout=0.0)
    mesh = fourd.make_mesh_4d(1, 2)

    # -- the backward structure: explicit pad + two tiled reduce-scatters
    #    == jax.vjp of the FP32 reshard-gather, bitwise
    st = pmm3d.initial_state()
    to_plane = (st.rep, st.row)
    br, bc = 8, 6
    def local(t, dout):
        _, vjp = jax.vjp(lambda v: pmm3d.reshard_gather(v, st, to_plane), t)
        (ref,) = vjp(dout)
        g = jax.lax.axis_size(st.row)
        i = jax.lax.axis_index(to_plane[0])
        j = jax.lax.axis_index(to_plane[1])
        d_full = jnp.zeros((g*br, g*bc), dout.dtype)
        d_full = jax.lax.dynamic_update_slice(d_full, dout, (i*br, j*bc))
        d1 = jax.lax.psum_scatter(d_full, st.col, scatter_dimension=1,
                                  tiled=True)
        mine = jax.lax.psum_scatter(d1, st.row, scatter_dimension=0,
                                    tiled=True)
        return ref, mine
    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(st.row, st.col), P(to_plane[0], to_plane[1])),
                      out_specs=(P(st.row, st.col), P(st.row, st.col)),
                      check_vma=False)
    t = jax.random.normal(jax.random.PRNGKey(0), (2*br, 2*bc))
    dout = jax.random.normal(jax.random.PRNGKey(1), (2*br, 2*bc))
    ref, mine = jax.jit(f)(t, dout)
    assert np.asarray(ref).tobytes() == np.asarray(mine).tobytes(), (
        "explicit reshard transpose structure diverged from jax.vjp")

    # -- compiled-step bytes + short-run convergence per mode
    def build(compress):
        opts = fourd.TrainOptions(compress=compress, dropout=0.0, seed=0)
        plan = fourd.build_plan(pg, cfg, mesh, batch=64, opts=opts)
        params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
        graph = plan.shard_graph(pg)
        return plan, params, graph

    def step_rep(plan, params, graph):
        loss_fn = fourd.make_loss_fn(plan, train=True)
        step = jnp.zeros((), jnp.int32)
        if plan.engine().quantized:
            ef = fourd.make_ef(plan)
            def mean(p, g_, e):
                l, ne = loss_fn(p, g_, step, ef=e)
                return l.mean(), ne
            return comm_report(jax.grad(mean, has_aux=True),
                               params, graph, ef)
        return comm_report(
            jax.grad(lambda p, g_: loss_fn(p, g_, step).mean()),
            params, graph)

    reps, losses = {}, {}
    for mode in ("none", "int8", "int4"):
        plan, params, graph = build(mode)
        reps[mode] = step_rep(plan, params, graph)
        # sampling stays communication-free under compression
        sample_fn, _ = PL.make_pipeline_fns(plan)
        comm_report(lambda g_: sample_fn(g_, jnp.zeros((), jnp.int32)),
                    graph).assert_no_collectives(f"sampling[{mode}]")
        tr = Trainer(plan, AdamW(lr=5e-3, grad_clip=1.0),
                     TrainLoopConfig(total_steps=10, chunk_size=5,
                                     eval_every=0))
        state = tr.init_state(params, graph)
        state, log = tr.run(state, graph)
        losses[mode] = float(log.losses[-1])

    r_n, r_8, r_4 = reps["none"], reps["int8"], reps["int4"]
    reshard_ratio = (r_8.bytes_for_scope("reshard")
                     / r_n.bytes_for_scope("reshard"))
    assert reshard_ratio <= 0.25, (
        f"int8 reshard bytes only {1/reshard_ratio:.2f}x smaller "
        f"(claim: >= 4x); {r_8.bytes_for_scope('reshard')} vs "
        f"{r_n.bytes_for_scope('reshard')}")
    d8 = r_8.bytes_by_dtype()
    assert d8.get("s8", 0) > d8.get("f32", 0), d8
    assert r_4.bytes_by_dtype()["s8"] * 2 == d8["s8"], (
        r_4.bytes_by_dtype(), d8)

    # EF keeps the compressed run within noise of FP32
    assert abs(losses["int8"] - losses["none"]) < 0.1, losses
    assert np.isfinite(losses["int4"]), losses
    print("PASS", losses, "reshard_ratio", reshard_ratio)
    """)
    r = subprocess.run([sys.executable, "-c", body], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "PASS" in r.stdout, r.stdout
