"""Multi-device integration tests for the 4D ScaleGNN path.

jax fixes the device count at first init, so these run in subprocesses
with XLA_FLAGS=--xla_force_host_platform_device_count=16. Each subprocess
asserts internally and prints a sentinel on success.

On a single-host CPU box these are skipped by default: each subprocess
emulates 16 devices in software, which is minutes of compile per test and
red-by-environment under tight CI budgets, not a code signal. Run them
anyway (any device count — the subprocesses force their own) with

    REPRO_RUN_MULTIDEVICE=1 ./tier1.sh -k fourd_multidevice
"""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_DEV_REQUIRED = 16
FORCE = os.environ.get("REPRO_RUN_MULTIDEVICE", "0") == "1"

pytestmark = pytest.mark.skipif(
    not FORCE and jax.device_count() < N_DEV_REQUIRED,
    reason=f"needs {N_DEV_REQUIRED} devices; subprocess emulation on a "
           "single CPU host is outside the tier-1 budget — set "
           "REPRO_RUN_MULTIDEVICE=1 to force-run")


def _run(body: str, n_dev: int = 16, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "PASS" in r.stdout, r.stdout
    return r.stdout


COMMON = """
import numpy as np, jax, jax.numpy as jnp
from repro.graphs import make_synthetic_dataset, build_partitioned_graph
from repro.core import fourd, sampling as S, gcn_model as M
ds = make_synthetic_dataset(n=512, num_classes=4, d_in=16, avg_degree=8,
                            seed=0)
pg = build_partitioned_graph(ds, g=2)
cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                  dropout=0.0)
mesh = fourd.make_mesh_4d(2, 2)
plan = fourd.build_plan(pg, cfg, mesh, batch=128)
params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
graph = plan.shard_graph(pg)
"""


@pytest.mark.slow
def test_distributed_loss_and_grads_match_reference():
    _run(COMMON + """
loss_fn = fourd.make_loss_fn(plan, train=True)
loss = jax.jit(loss_fn)(params, graph, jnp.asarray(0))

A = ds.adj_norm
rp, ci, val = jnp.array(A.indptr), jnp.array(A.indices), jnp.array(A.data)
feats, labels = jnp.array(pg.features), jnp.array(pg.labels)
scfg = S.SampleConfig(n_pad=pg.n_pad, g=2, batch=128, e_cap=plan.scfg.e_cap)
ref_params = M.init_params(jax.random.PRNGKey(1), cfg)
for d in range(2):
    mb = S.make_minibatch_stratified(
        S.step_key(0, jnp.asarray(0), d), rp, ci, val, feats, labels, scfg)
    logits = M.forward(ref_params, mb.adj, mb.feats, cfg, train=False)
    ref = float(M.cross_entropy_loss(logits, mb.labels))
    assert abs(float(loss[d]) - ref) < 1e-4, (d, float(loss[d]), ref)

def mean_loss(p, g_, s): return loss_fn(p, g_, s).mean()
gd = jax.jit(jax.grad(mean_loss))(params, graph, jnp.asarray(0))
# reference grad: average of the two DP groups' reference grads
import functools
def ref_loss(p):
    tot = 0.0
    for d in range(2):
        mb = S.make_minibatch_stratified(
            S.step_key(0, jnp.asarray(0), d), rp, ci, val, feats, labels,
            scfg)
        lg = M.forward(p, mb.adj, mb.feats, cfg, train=False)
        tot = tot + M.cross_entropy_loss(lg, mb.labels)
    return tot / 2
gr = jax.grad(ref_loss)(ref_params)
for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gr)):
    err = np.abs(np.array(a) - np.array(b)).max()
    rel = err / (np.abs(np.array(b)).max() + 1e-9)
    assert rel < 1e-3, rel
print("PASS")
""")


@pytest.mark.slow
def test_sampling_phase_has_no_collectives():
    """The paper's central claim: sampling + subgraph construction is
    communication-free. We compile ONLY the sampling/extraction shard_map
    and assert (via obs.comm_report) it issues zero collective ops."""
    _run(COMMON + """
from repro.core import pipeline as PL
from repro.obs import assert_no_collectives
from repro.optim import AdamW
sample_fn, _ = PL.make_prefetched_train_step(plan, AdamW(lr=1e-3))
assert_no_collectives(sample_fn, graph, jnp.asarray(0),
                      what="sampling/extraction")
print("PASS")
""")


@pytest.mark.slow
def test_training_converges_and_variants_agree():
    _run(COMMON + """
from repro.optim import AdamW
import numpy as np
opt = AdamW(lr=5e-3)
opt_state = opt.init(params)
train_step = fourd.make_train_step(plan, opt)
p = params
for step in range(60):
    p, opt_state, loss = train_step(p, opt_state, graph, jnp.asarray(step))
eval_step = fourd.make_eval_step(plan)
acc = float(eval_step(p, graph))
assert acc > 0.8, acc

# optimization variants must not change the math
base = fourd.make_loss_fn(plan, train=False)
l0 = np.array(jax.jit(base)(p, graph, jnp.asarray(0)))
for kw, tol in [(dict(bf16_collectives=True), 2e-2),
                (dict(reshard_impl="permute"), 1e-6),
                (dict(fused_elementwise=True), 1e-4)]:
    plan2 = fourd.build_plan(pg, cfg, mesh, batch=128,
                             opts=fourd.TrainOptions(**kw))
    l2 = np.array(jax.jit(fourd.make_loss_fn(plan2, train=False))(
        p, graph, jnp.asarray(0)))
    assert np.allclose(l2, l0, rtol=tol), (kw, l2, l0)
print("PASS")
""")


@pytest.mark.slow
def test_prefetch_pipeline_equivalence():
    _run(COMMON + """
from repro.core import pipeline as PL
from repro.optim import AdamW
import numpy as np
opt = AdamW(lr=5e-3)
opt_state = opt.init(params)
ts = fourd.make_train_step(plan, opt)
p0, o0 = params, opt_state
ref = []
for s in range(4):
    p0, o0, l = ts(p0, o0, graph, jnp.asarray(s)); ref.append(float(l))
sample_fn, step_fn = PL.make_prefetched_train_step(plan, opt)
state = PL.PrefetchState(params, opt_state, sample_fn(graph, jnp.asarray(0)))
got = []
for s in range(4):
    state, l = step_fn(state, graph, jnp.asarray(s)); got.append(float(l))
assert np.allclose(ref, got, rtol=1e-5), (ref, got)
print("PASS")
""")


@pytest.mark.slow
def test_gnn_production_dryrun_small():
    """The 4D GNN train step lowers + compiles on a (2,2,2,2) mesh with
    abstract inputs (miniature of the production dry-run)."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import fourd, gcn_model as M
from repro.graphs.partition import PartitionedGraph
from repro.optim import AdamW
g = 2
n_pad, n_local = 4096, 2048
e_pad = 40000
cfg = M.GCNConfig(d_in=32, d_hidden=64, num_layers=3, num_classes=8,
                  dropout=0.1)
pg = PartitionedGraph(n=n_pad, n_pad=n_pad, g=g, n_local=n_local,
                      e_pad=e_pad, block_rp=None, block_ci=None,
                      block_val=None, max_block_row_nnz=32, features=None,
                      labels=None, train_mask=None, num_classes=8)
mesh = fourd.make_mesh_4d(2, 2)
plan = fourd.build_plan(pg, cfg, mesh, batch=256,
                        opts=fourd.TrainOptions(dropout=0.1),
                        e_cap=128 * 32)
opt = AdamW(lr=1e-3)
ts = fourd.make_train_step(plan, opt)
sds = jax.ShapeDtypeStruct
params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
opt_state = jax.eval_shape(opt.init, params)
blk = lambda: (sds((g, g, n_local + 1), jnp.int32),
               sds((g, g, e_pad), jnp.int32),
               sds((g, g, e_pad), jnp.float32))
graph = {"adj1": blk(), "adj2": blk(), "adj3": blk(),
         "features": sds((n_pad, 32), jnp.float32),
         "labels": sds((n_pad,), jnp.int32)}
lowered = ts.lower(params, opt_state, graph, jnp.zeros((), jnp.int32))
compiled = lowered.compile()
assert compiled.memory_analysis().temp_size_in_bytes > 0
print("PASS")
""")


@pytest.mark.slow
def test_prefetch_pipeline_equivalence_block_ell():
    """§V-A prefetch with block-ELL minibatches on the real 16-device mesh:
    the per-leaf (tiles, colidx) specs must round-trip between the sampling
    shard_map's out_specs and the loss shard_map's in_specs."""
    _run(COMMON + """
from repro.core import pipeline as PL
from repro.optim import AdamW
import numpy as np
plan_e = fourd.build_plan(pg, cfg, mesh, batch=128,
    opts=fourd.TrainOptions(spmm_impl="ell", ell_tile=16, ell_slots=16))
params_e = plan_e.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
opt = AdamW(lr=5e-3)
opt_state = opt.init(params_e)
ts = fourd.make_train_step(plan_e, opt)
p0, o0, ref = params_e, opt_state, []
for s in range(3):
    p0, o0, l = ts(p0, o0, graph, jnp.asarray(s)); ref.append(float(l))
sample_fn, step_fn = PL.make_prefetched_train_step(plan_e, opt)
state = PL.PrefetchState(params_e, opt_state,
                         sample_fn(graph, jnp.asarray(0)))
got = []
for s in range(3):
    state, l = step_fn(state, graph, jnp.asarray(s)); got.append(float(l))
assert np.allclose(ref, got, rtol=1e-5), (ref, got)
print("PASS")
""")


@pytest.mark.slow
def test_epoch_schedule_communication_free_and_dp_identical():
    """ISSUE-5 acceptance: the without-replacement epoch sample is a pure
    function of (seed, epoch, step, dp_index) — identical on every device
    of a DP group (asserted on the materialized per-device ids), distinct
    across DP groups, without-replacement within each epoch, and the
    sampling program lowers with ZERO collectives. A 2-epoch prefetch run
    through the real Trainer then crosses the boundary inside the scan."""
    _run(COMMON + """
from jax.sharding import PartitionSpec as P
from repro.core import pipeline as PL
from repro.optim import AdamW
from repro.train import Trainer, TrainLoopConfig
plan_e = fourd.build_plan(pg, cfg, mesh, batch=128,
                          opts=fourd.TrainOptions(sample_mode="epoch"))
builder = plan_e.builder
spe = plan_e.scfg.steps_per_epoch
assert spe == 4, spe                     # 512 / 128

def local_ids(step, epoch):
    s2d = builder.sample_ids(step, epoch, jax.lax.axis_index("d"))
    return s2d[None, None, None, None]   # (1,1,1,1,g,b) per device

ids_fn = jax.shard_map(local_ids, mesh=plan_e.mesh, in_specs=(P(), P()),
                       out_specs=P("d", "x", "y", "z"), check_vma=False)
per_epoch = []
for t in range(spe):
    ids = np.array(ids_fn(jnp.asarray(t), jnp.asarray(0)))  # (2,2,2,2,g,b)
    flat = ids.reshape(2, 8, -1)         # (d, devices-in-group, g*b)
    for d in range(2):
        # every device of a DP group derives the IDENTICAL sample...
        assert (flat[d] == flat[d][0]).all(), (t, d)
    # ...and the two DP groups train on different mini-batches
    assert not (flat[0][0] == flat[1][0]).all(), t
    per_epoch.append(flat[:, 0])
for d in range(2):                       # without replacement per epoch
    got = np.sort(np.concatenate([e[d] for e in per_epoch]))
    assert (got == np.arange(512)).all(), d

from repro.obs import assert_no_collectives
sample_fn, _ = PL.make_pipeline_fns(plan_e)
assert_no_collectives(sample_fn, graph, jnp.asarray(0), jnp.asarray(0),
                      what="epoch sampling")

params_e = plan_e.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
opt = AdamW(lr=5e-3)
tr = Trainer(plan_e, opt, TrainLoopConfig(epochs=2, chunk_size=3,
                                          prefetch=True))
state, log = tr.run(tr.init_state(params_e, graph), graph)
assert int(state.step) == 8 and int(state.epoch) == 2
assert all(np.isfinite(log.losses)), log.losses
print("PASS")
""")


@pytest.mark.slow
def test_comm_report_byte_accurate_on_2x2x2x2_mesh():
    """ISSUE-6 acceptance: ``obs.comm_report`` byte totals match
    hand-computed collective sizes on the real (2,2,2)x2 mesh.

    Three one-collective shard_map programs with arithmetic-derivable
    result shapes pin the per-category accounting exactly (result bytes
    per device: all-reduce/permute = local shape, all-gather = gathered
    shape); the full (2,2,2)x2 loss program is then sanity-checked for the
    expected collective mix (PMM all-reduces present, no all-to-all) and
    the sampling phase for ZERO collectives — via the same analyzer."""
    _run(COMMON + """
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.obs import comm_report
sm = partial(jax.shard_map, mesh=mesh, check_vma=False)

x = jnp.ones((64, 32), jnp.float32)      # local block (32, 32) on z/x/y

psum_z = sm(lambda a: jax.lax.psum(a, "z"),
            in_specs=(P("z", None),), out_specs=P(None, None))
r = comm_report(jax.jit(psum_z), x)
assert r.counts == {"all-reduce": 1, "all-gather": 0, "reduce-scatter": 0,
                    "all-to-all": 0, "collective-permute": 0}, r
assert r.bytes["all-reduce"] == 32 * 32 * 4, r     # local (32,32) f32

gather_x = sm(lambda a: jax.lax.all_gather(a, "x", tiled=True),
              in_specs=(P("x", None),), out_specs=P(None, None))
r = comm_report(jax.jit(gather_x), x)
assert r.counts["all-gather"] == 1 and r.total_count == 1, r
assert r.bytes["all-gather"] == 64 * 32 * 4, r     # gathered (64,32) f32

perm_y = sm(lambda a: jax.lax.ppermute(a, "y", perm=[(0, 1), (1, 0)]),
            in_specs=(P("y", None),), out_specs=P("y", None))
r = comm_report(jax.jit(perm_y), x)
assert r.counts["collective-permute"] == 1 and r.total_count == 1, r
assert r.bytes["collective-permute"] == 32 * 32 * 4, r

# the full (2,2,2)x2 plan: PMM psums present, nothing exotic; sampling
# still communication-free through the same analyzer
loss_fn = fourd.make_loss_fn(plan, train=True)
rl = comm_report(jax.jit(loss_fn), params, graph, jnp.asarray(0))
assert rl.counts["all-reduce"] > 0, rl
assert rl.counts["all-to-all"] == 0, rl
assert rl.total_bytes > 0, rl
from repro.core import pipeline as PL
sample_fn, _ = PL.make_pipeline_fns(plan)
rs = comm_report(jax.jit(sample_fn), graph, jnp.asarray(0), jnp.asarray(0))
rs.assert_no_collectives("sampling at (2,2,2)x2")
print("PASS")
""")


@pytest.mark.slow
def test_ring_overlap_bitmatches_monolithic_2x2x2x2():
    """overlap_impl="ring" on the full (2,2,2)x2 mesh: loss AND grads
    bit-identical to the monolithic collectives (single-add chunk
    reductions at g=2 + the full-width custom-VJP backward), across the
    plain, bf16-wire, and permute-reshard variants; and the ring program
    moves no more collective bytes than the monolithic one."""
    _run(COMMON + """
from repro.obs import comm_report

def lg(opts):
    plan_o = fourd.build_plan(pg, cfg, mesh, batch=128, opts=opts)
    loss_fn = fourd.make_loss_fn(plan_o, train=True)
    mean = lambda p, g_, s: loss_fn(p, g_, s).mean()
    loss = jax.jit(mean)(params, graph, jnp.asarray(0))
    grads = jax.jit(jax.grad(mean))(params, graph, jnp.asarray(0))
    return loss, grads, mean

def biteq(a, b):
    return all(np.array(x).tobytes() == np.array(y).tobytes()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

O = fourd.TrainOptions
for kw in [dict(), dict(bf16_collectives=True),
           dict(reshard_impl="permute")]:
    l0, g0, mean0 = lg(O(**kw))
    l1, g1, mean1 = lg(O(overlap_impl="ring", **kw))
    assert biteq(l0, l1), (kw, l0, l1)
    assert biteq(g0, g1), ("ring grads diverge", kw)

l0, g0, mean0 = lg(O())
l1, g1, mean1 = lg(O(overlap_impl="ring"))
r0 = comm_report(jax.jit(jax.grad(mean0)), params, graph, jnp.asarray(0))
r1 = comm_report(jax.jit(jax.grad(mean1)), params, graph, jnp.asarray(0))
assert r1.total_bytes <= r0.total_bytes, (r1.total_bytes, r0.total_bytes)
assert r1.counts["collective-permute"] > 0, r1
print("PASS")
""")


@pytest.mark.slow
def test_compressed_collectives_bytes_and_loss_2x2x2x2():
    """Compressed-collective acceptance on the full (2,2,2)x2 mesh: the
    compiled int8 fwd+bwd step moves >= 4x fewer reshard+rotate bytes than
    the uncompressed plan (the ROADMAP item-1 claim, asserted on compiled
    HLO via the per-site scope attribution), the dominant int8 payload is
    true s8 on the wire, sampling stays zero-collective in every compress
    mode, and a short EF-compensated int8 run lands within noise of the
    FP32 loss."""
    _run(COMMON + """
from repro.core import pipeline as PL
from repro.obs import comm_report
from repro.optim import AdamW
from repro.train import Trainer, TrainLoopConfig

def build(compress):
    opts = fourd.TrainOptions(compress=compress, seed=0)
    plan_c = fourd.build_plan(pg, cfg, mesh, batch=128, opts=opts)
    return plan_c, plan_c.shard_graph(pg)

def step_rep(plan_c, graph_c):
    p = plan_c.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
    loss_fn = fourd.make_loss_fn(plan_c, train=True)
    step = jnp.zeros((), jnp.int32)
    if plan_c.engine().quantized:
        ef = fourd.make_ef(plan_c)
        def mean(pp, gg, ee):
            l, ne = loss_fn(pp, gg, step, ef=ee)
            return l.mean(), ne
        return comm_report(jax.grad(mean, has_aux=True), p, graph_c, ef)
    return comm_report(
        jax.grad(lambda pp, gg: loss_fn(pp, gg, step).mean()), p, graph_c)

reps, losses = {}, {}
for mode in ("none", "int8"):
    plan_c, graph_c = build(mode)
    reps[mode] = step_rep(plan_c, graph_c)
    sample_fn, _ = PL.make_pipeline_fns(plan_c)
    comm_report(jax.jit(sample_fn), graph_c, jnp.asarray(0),
                jnp.asarray(0)).assert_no_collectives(
        f"sampling[{mode}] at (2,2,2)x2")
    p = plan_c.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
    tr = Trainer(plan_c, AdamW(lr=5e-3, grad_clip=1.0),
                 TrainLoopConfig(total_steps=10, chunk_size=5))
    state, log = tr.run(tr.init_state(p, graph_c), graph_c)
    losses[mode] = float(log.losses[-1])

rn, r8 = reps["none"], reps["int8"]
ratio = r8.bytes_for_scope("reshard") / rn.bytes_for_scope("reshard")
assert ratio <= 0.25, (
    f"int8 reshard bytes only {1/ratio:.2f}x smaller (claim: >= 4x); "
    f"{r8.bytes_for_scope('reshard')} vs {rn.bytes_for_scope('reshard')}")
d8 = r8.bytes_by_dtype()
assert d8.get("s8", 0) > d8.get("f32", 0), d8
assert abs(losses["int8"] - losses["none"]) < 0.1, losses
print("PASS", losses, "reshard_ratio", ratio)
""")


@pytest.mark.slow
def test_partition_mode_communication_free_and_dp_disjoint():
    """ISSUE-9 acceptance on the real (2,2,2)x2 mesh: partition-mode
    sampling (epoch schedule) compiles to ZERO collectives, every device
    of a DP group derives the identical cluster slice, the two DP groups'
    slices are disjoint and jointly cover every vertex exactly once per
    epoch, the tightened e_cap is strictly below the uniform bound, and a
    2-epoch Trainer run (prefetch on, crossing the boundary in-scan)
    bit-matches prefetch off."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.graphs import make_synthetic_dataset, build_partitioned_graph
from repro.core import fourd, pipeline as PL, gcn_model as M
from repro.obs import assert_no_collectives
from repro.optim import AdamW
from repro.train import Trainer, TrainLoopConfig
ds = make_synthetic_dataset(n=512, num_classes=4, d_in=16, avg_degree=8,
                            seed=0)
pg = build_partitioned_graph(ds, g=2, clusters=16)   # cluster_size 16
cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                  dropout=0.0)
mesh = fourd.make_mesh_4d(2, 2)
opts = fourd.TrainOptions(sample_kind="partition", sample_mode="epoch",
                          clusters=16)
plan = fourd.build_plan(pg, cfg, mesh, batch=128, opts=opts)
assert plan.scfg.dp_groups == 2 and plan.scfg.clusters_per_step == 4
assert plan.scfg.e_cap < 64 * pg.max_block_row_nnz   # tightened bound
spe = plan.scfg.steps_per_epoch
assert spe == 2                                      # 512 / (128 * 2)
graph = plan.shard_graph(pg)
builder = plan.builder

def local_ids(step, epoch):
    s2d = builder.sample_ids(step, epoch, jax.lax.axis_index("d"))
    return s2d[None, None, None, None]
ids_fn = jax.shard_map(local_ids, mesh=plan.mesh, in_specs=(P(), P()),
                       out_specs=P("d", "x", "y", "z"), check_vma=False)
per_epoch = []
for t in range(spe):
    ids = np.array(ids_fn(jnp.asarray(t), jnp.asarray(0)))
    flat = ids.reshape(2, 8, -1)
    for d in range(2):               # identical within each DP group
        assert (flat[d] == flat[d][0]).all(), (t, d)
    assert not np.intersect1d(flat[0][0], flat[1][0]).size, t  # disjoint
    per_epoch.append(flat[:, 0])
got = np.sort(np.concatenate([e.reshape(-1) for e in per_epoch]))
assert (got == np.arange(512)).all()     # jointly cover, exactly once

sample_fn, _ = PL.make_pipeline_fns(plan)
assert_no_collectives(sample_fn, graph, jnp.asarray(0), jnp.asarray(0),
                      what="partition-mode sampling")
plan_s = fourd.build_plan(pg, cfg, mesh, batch=128,
    opts=fourd.TrainOptions(sample_kind="partition", clusters=16))
sample_s, _ = PL.make_pipeline_fns(plan_s)
assert_no_collectives(sample_s, plan_s.shard_graph(pg), jnp.asarray(0),
                      jnp.asarray(0), what="partition step-mode sampling")

opt = AdamW(lr=5e-3)
mk = lambda: plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
loss_seqs = {}
for pf in (False, True):
    tr = Trainer(plan, opt, TrainLoopConfig(epochs=2, chunk_size=3,
                                            prefetch=pf))
    state, log = tr.run(tr.init_state(mk(), graph), graph)
    assert int(state.step) == 2 * spe and int(state.epoch) == 2
    loss_seqs[pf] = log.losses
assert loss_seqs[True] == loss_seqs[False], loss_seqs
assert all(np.isfinite(loss_seqs[True]))
print("PASS")
""")


@pytest.mark.slow
def test_walk_mode_communication_free():
    """Walk (GraphSAINT) mode on the real mesh: the replicated neighbor
    table keeps walk gathers device-local — the sampling program compiles
    to ZERO collectives — every device of a DP group derives the same
    batch, and a short train run moves finite losses."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.graphs import make_synthetic_dataset, build_partitioned_graph
from repro.core import fourd, pipeline as PL, gcn_model as M
from repro.obs import assert_no_collectives
from repro.optim import AdamW
ds = make_synthetic_dataset(n=512, num_classes=4, d_in=16, avg_degree=8,
                            seed=0)
pg = build_partitioned_graph(ds, g=2)
cfg = M.GCNConfig(d_in=16, d_hidden=32, num_layers=3, num_classes=4,
                  dropout=0.0)
mesh = fourd.make_mesh_4d(2, 2)
opts = fourd.TrainOptions(sample_kind="walk", walk_len=3, walk_k=8)
plan = fourd.build_plan(pg, cfg, mesh, batch=128, opts=opts)
assert plan.scfg.walk_roots == 16                    # 64 / (3 + 1)
graph = plan.shard_graph(pg)
assert set(graph["walk"]) == {"nbr", "p"}

sample_fn, _ = PL.make_pipeline_fns(plan)
assert_no_collectives(sample_fn, graph, jnp.asarray(0), jnp.asarray(0),
                      what="walk-mode sampling")

builder = plan.builder
def local_ids(step, epoch, aux):
    s2d = builder.sample_ids(step, epoch, jax.lax.axis_index("d"), aux=aux)
    return s2d[None, None, None, None]
ids_fn = jax.shard_map(local_ids, mesh=plan.mesh,
                       in_specs=(P(), P(), plan.aux_specs),
                       out_specs=P("d", "x", "y", "z"), check_vma=False)
ids = np.array(ids_fn(jnp.asarray(0), jnp.asarray(0),
                      graph["walk"])).reshape(2, 8, -1)
for d in range(2):
    assert (ids[d] == ids[d][0]).all(), d            # identical per group
assert not (ids[0][0] == ids[1][0]).all()            # groups independent

opt = AdamW(lr=5e-3)
params = plan.shard_params(M.init_params(jax.random.PRNGKey(1), cfg))
ts = fourd.make_train_step(plan, opt)
o = opt.init(params)
for s in range(2):
    params, o, loss = ts(params, o, graph, jnp.asarray(s))
    assert np.isfinite(float(loss)), s
print("PASS")
""", timeout=900)


@pytest.mark.slow
def test_block_ell_spmm_path_matches_dense():
    """§Perf H3.4: the block-ELL extraction + Pallas SpMM path produces
    the same distributed loss and gradients as the dense-block path."""
    _run(COMMON + """
import numpy as np
plan_e = fourd.build_plan(pg, cfg, mesh, batch=128,
    opts=fourd.TrainOptions(spmm_impl="ell", ell_tile=16, ell_slots=16))
ld = jax.jit(fourd.make_loss_fn(plan, train=False))(
    params, graph, jnp.asarray(0))
le = jax.jit(fourd.make_loss_fn(plan_e, train=False))(
    params, graph, jnp.asarray(0))
assert np.allclose(np.array(ld), np.array(le), rtol=1e-4), (ld, le)
gd = jax.jit(jax.grad(lambda p, g_, s: fourd.make_loss_fn(
    plan, train=False)(p, g_, s).mean()))(params, graph, jnp.asarray(0))
ge = jax.jit(jax.grad(lambda p, g_, s: fourd.make_loss_fn(
    plan_e, train=False)(p, g_, s).mean()))(params, graph, jnp.asarray(0))
for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(ge)):
    assert np.abs(np.array(a) - np.array(b)).max() < 1e-4
print("PASS")
""")
