"""4D parallel mini-batch GNN training (ScaleGNN §IV).

The total device grid is ``(d, x, y, z)`` — data parallelism × 3D PMM, with
``gx = gy = gz = g`` (the paper's near-cube recommendation; DESIGN.md §4).
One ``shard_map`` over the full 4D mesh contains the *entire* training step:

  1. communication-free sampling + local subgraph extraction (§III-D/Alg.2)
     — provably collective-free (asserted by tests on the lowered HLO),
  2. 3D-PMM forward (input projection, L GCN layers with layer rotation,
     output head) — one all-reduce per matmul (§IV-C),
  3. distributed cross-entropy (FP32 reductions, §V-B),
  4. backward pass — generated by ``jax.grad`` *through* the shard_map, so
     the transpose all-reduces (Eqs. 15-17) and the data-parallel gradient
     all-reduce (§IV-A) are inserted by JAX's partitioner exactly where the
     paper places them by hand.

The distributed layer loop itself (SpMM -> GEMM -> reshard -> elementwise
tail -> rotate) lives in ``repro/core/forward.py`` (``ForwardEngine``) —
this module builds the mesh/spec plumbing and the jitted train/eval steps
around it. Optimization toggles (paper §V) are carried in ``TrainOptions``
(defined next to the engine, re-exported here):
  * ``bf16_collectives``  — §V-B low-precision PMM all-reduces
  * ``fused_elementwise`` — §V-C Pallas fused RMSNorm/ReLU/dropout/residual
  * ``reshard_impl``      — residual reshard: "gather" (baseline) or
                            "permute" (optimized; §Perf hillclimb)
  * prefetched sampling (§V-A) lives in ``repro/core/pipeline.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import pmm3d
from repro.core import sampling as smp
from repro.core.forward import ForwardEngine, TrainOptions
from repro.core.gcn_model import GCNConfig
from repro.core.minibatch import GraphShards, MinibatchBuilder
from repro.graphs.partition import PartitionedGraph, build_walk_tables
from repro.obs.tracer import phase

AXES_4D = ("d", "x", "y", "z")


def make_mesh_4d(g_d: int, g: int,
                 devices: Optional[np.ndarray] = None) -> Mesh:
    """The paper's 4D virtual grid: G_d x g x g x g."""
    n_dev = g_d * g * g * g
    if devices is None:
        devices = np.array(jax.devices()[:n_dev])
    assert devices.size == n_dev, (
        f"need {n_dev} devices, have {devices.size}")
    return Mesh(devices.reshape(g_d, g, g, g), AXES_4D)


# ---------------------------------------------------------------------------
# Parameter / data sharding specs
# ---------------------------------------------------------------------------

def param_specs(num_layers: int) -> Dict[str, Any]:
    """PartitionSpecs mirroring the pytree of ``gcn_model.init_params``.

    w_in on (z, y); layer-l weight on (c_l, r_l); rms scale over r_l;
    w_out on (c_L, p_L) — see DESIGN.md §4.
    """
    st = pmm3d.initial_state(("x", "y", "z"))
    layers = []
    for _ in range(num_layers):
        wp = st.weight_plane
        layers.append({"w": P(*wp), "rms_scale": P(st.row)})
        st = st.rotate()
    return {
        "w_in": P("z", "y"),
        "w_out": P(st.col, st.rep),
        "layers": layers,
    }


def graph_data_specs() -> Dict[str, P]:
    """Specs for the three adjacency passes + features. The same block array
    is passed three times, sharded over the three rotation planes — this is
    how the paper's 'three adjacency shards per GPU' appears in JAX."""
    return {
        # block arrays are (g, g, ...) stacked; dims 0/1 shard over the plane
        "adj1": P("z", "x", None),   # layer state (x, y, z): A on (z, x)
        "adj2": P("y", "z", None),   # state (z, x, y): A on (y, z)
        "adj3": P("x", "y", None),   # state (y, z, x): A on (x, y)
        "features": P("x", "z"),     # IN rows over x, cols over z
    }


def label_spec(num_layers: int) -> P:
    return P(pmm3d.state_after_layers(num_layers).row)


def shard_graph_arrays(mesh: Mesh, pg: PartitionedGraph,
                       data_specs: Dict[str, P],
                       label_sp: Optional[P] = None) -> Dict[str, Any]:
    """Device-put the partitioned-graph arrays with their plane specs.

    The block CSR arrays are shared: the same buffers are placed three times
    with specs differing only in mesh-axis assignment (the paper's 'three
    adjacency shards per GPU'). Labels are optional — serving has none.
    """
    ns = lambda sp: NamedSharding(mesh, sp)
    rp = jnp.asarray(pg.block_rp)
    ci = jnp.asarray(pg.block_ci)
    val = jnp.asarray(pg.block_val)
    out: Dict[str, Any] = {}
    for k in ("adj1", "adj2", "adj3"):
        out[k] = (jax.device_put(rp, ns(data_specs[k])),
                  jax.device_put(ci, ns(data_specs[k])),
                  jax.device_put(val, ns(data_specs[k])))
    out["features"] = jax.device_put(
        jnp.asarray(pg.features), ns(data_specs["features"]))
    if label_sp is not None:
        out["labels"] = jax.device_put(
            jnp.asarray(pg.labels), ns(label_sp))
    return out


def padded_class_count(num_classes: int, g: int) -> int:
    """Class count ceil-padded to the grid side — the ONE definition of how
    the output head pads (shared by training plans, ``pad_output_head``,
    and the serving slice that strips the padding back off)."""
    return -(-num_classes // g) * g


def pad_output_head(params, num_classes: int, g: int):
    """Zero-pad ``w_out``'s class columns to a multiple of the grid side so
    the head GEMM shards evenly over the rep axis. Padded columns produce
    logits the caller slices off; real columns are untouched, so padding is
    arithmetic-neutral. Returns (params', padded class count)."""
    n_cls_pad = padded_class_count(num_classes, g)
    if n_cls_pad == num_classes:
        return params, n_cls_pad
    w = params["w_out"]
    w_pad = jnp.zeros((w.shape[0], n_cls_pad), w.dtype).at[:, :num_classes]\
        .set(w)
    out = dict(params)
    out["w_out"] = w_pad
    return out, n_cls_pad


# ---------------------------------------------------------------------------
# Public API: build the jitted 4D train / eval steps
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FourDPlan:
    """Everything needed to run 4D training: mesh, specs, jitted steps."""

    mesh: Mesh
    cfg: GCNConfig
    scfg: smp.SampleConfig
    opts: TrainOptions
    builder: MinibatchBuilder
    p_specs: Any
    data_specs: Dict[str, P]
    label_sp: P
    num_classes_padded: int

    @property
    def grid_side(self) -> int:
        """The static 3D-grid side g."""
        return self.scfg.g

    def engine(self, *, backend: Optional[str] = None,
               csr_rows: int = 0) -> ForwardEngine:
        """The ONE layer-loop executor for this plan (``core/forward.py``):
        backend defaults to the mini-batch block format
        (``TrainOptions.spmm_impl``); eval passes ``backend="csr"``."""
        return ForwardEngine.from_options(
            self.cfg, self.opts, grid_side=self.grid_side,
            backend=backend, csr_rows=csr_rows)

    @property
    def shards_specs(self) -> GraphShards:
        return GraphShards.specs(self.data_specs)

    @property
    def aux_specs(self) -> Dict[str, P]:
        """Specs of the sampling aux pytree: walk mode's neighbor table and
        inclusion estimate are REPLICATED (P()) so the walk's gathers stay
        device-local — the zero-sampling-collectives invariant holds for
        every mode. Empty for the other modes (an empty-dict pytree
        threads through shard_map against an empty-dict spec)."""
        if self.builder.mode == "walk":
            return {"nbr": P(), "p": P()}
        return {}

    def shard_params(self, params):
        return jax.device_put(params, jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), self.p_specs,
            is_leaf=lambda x: isinstance(x, P)))

    def shard_graph(self, pg: PartitionedGraph):
        """Device-put the partitioned graph arrays with their specs (shared
        helper: ``shard_graph_arrays``). Walk mode additionally replicates
        its sampling tables under ``graph['walk']`` — the in-range
        degree-capped neighbor table and the per-vertex inclusion estimate
        ``p = min(1, b_local * p_tilde)`` (SAINT's rescale input)."""
        out = shard_graph_arrays(self.mesh, pg, self.data_specs,
                                 self.label_sp)
        if self.builder.mode == "walk":
            nbr, p_tilde = build_walk_tables(pg, k=self.scfg.walk_k)
            b = self.scfg.batch // self.grid_side
            rep = NamedSharding(self.mesh, P())
            out["walk"] = {
                "nbr": jax.device_put(jnp.asarray(nbr), rep),
                "p": jax.device_put(
                    jnp.minimum(1.0, b * jnp.asarray(p_tilde)), rep),
            }
        return out


def build_plan(pg: PartitionedGraph, cfg: GCNConfig, mesh: Mesh,
               batch: int, opts: TrainOptions = TrainOptions(),
               e_cap: Optional[int] = None) -> FourDPlan:
    g = mesh.shape["x"]
    assert mesh.shape["y"] == g and mesh.shape["z"] == g, (
        "ScaleGNN path requires a cube 3D grid (paper §VII-C)")
    assert pg.g == g, "graph partitioned for a different grid side"
    assert batch % g == 0, "batch must divide evenly across vertex ranges"
    assert cfg.d_hidden % g == 0 and cfg.d_in % g == 0, (
        "d_in / d_hidden must be divisible by the grid side")
    b = batch // g
    kind = getattr(opts, "sample_kind", "stratified")
    clusters = walk_len = walk_k = 0
    dp_groups = 1
    if kind == "partition":
        clusters = opts.clusters or pg.clusters
        assert clusters > 0, (
            "sample_kind='partition' needs a clustered graph: either "
            "build_partitioned_graph(..., clusters=C) or set "
            "TrainOptions.clusters")
        cs = pg.n_local // max(clusters, 1)
        if e_cap is None and cs > 0 and b % cs == 0 \
                and pg.max_cluster_block_nnz > 0:
            # q whole clusters per range bound the block nnz far tighter
            # than b * max_block_row_nnz — the extraction-cost win
            e_cap = max((b // cs) * pg.max_cluster_block_nnz, 1)
        if getattr(opts, "sample_mode", "step") == "epoch":
            # DP ranks take interleaved slices of one shared cluster
            # permutation: disjoint coverage of every cluster per epoch
            dp_groups = mesh.shape["d"]
    elif kind == "walk":
        walk_len, walk_k = opts.walk_len, opts.walk_k
    e_cap = e_cap or b * max(pg.max_block_row_nnz, 1)
    scfg = smp.SampleConfig(n_pad=pg.n_pad, g=g, batch=batch, e_cap=e_cap,
                            clusters=clusters, dp_groups=dp_groups,
                            walk_len=walk_len, walk_k=walk_k).validate()
    n_cls_pad = padded_class_count(cfg.num_classes, g)
    builder = MinibatchBuilder.from_options(
        scfg, opts, max_row_nnz=max(pg.max_block_row_nnz, 1))
    return FourDPlan(
        mesh=mesh, cfg=cfg, scfg=scfg, opts=opts, builder=builder,
        p_specs=param_specs(cfg.num_layers),
        data_specs=graph_data_specs(),
        label_sp=label_spec(cfg.num_layers),
        num_classes_padded=n_cls_pad,
    )


# ---------------------------------------------------------------------------
# Error-feedback accumulators (compressed collectives, ROADMAP item 1)
# ---------------------------------------------------------------------------
#
# Every quantized collective site of the ForwardEngine carries one residual
# tensor per device (``engine.ef_sites``). Globally each accumulator is an
# (gd, g, g, g, *local_shape) array sharded P("d","x","y","z") — one local
# block per device regardless of which plane the activation lives on — so
# the EF pytree threads through shard_map with ONE uniform spec, rides the
# TrainState scan carry, and checkpoints like any other leaf.

def ef_local_shapes(plan: "FourDPlan") -> Dict[str, tuple]:
    """site -> per-device EF shape for this plan's training mini-batch."""
    return plan.engine().ef_site_shapes(plan.scfg.batch // plan.grid_side)


def ef_specs(plan: "FourDPlan") -> Optional[Dict[str, P]]:
    """shard_map specs of the EF pytree; None when compression is off."""
    shapes = ef_local_shapes(plan)
    if not shapes:
        return None
    return {site: P(*AXES_4D) for site in shapes}


def make_ef(plan: "FourDPlan"):
    """Zero-initialized, mesh-sharded EF accumulators for the plan's
    quantized sites; None when the engine sends no quantized wire."""
    shapes = ef_local_shapes(plan)
    if not shapes:
        return None
    gd = plan.mesh.shape["d"]
    g = plan.grid_side
    return {
        site: jax.device_put(
            jnp.zeros((gd, g, g, g) + shp, jnp.float32),
            NamedSharding(plan.mesh, P(*AXES_4D)))
        for site, shp in shapes.items()
    }


def _ef_squeeze(ef):
    """Local (1,1,1,1,*shape) shard_map view -> the engine's *shape."""
    return {k: v.reshape(v.shape[4:]) for k, v in ef.items()}


def _ef_expand(ef):
    """Engine residuals -> the (1,1,1,1,*shape) view out_specs scatter."""
    return {k: v.reshape((1, 1, 1, 1) + v.shape) for k, v in ef.items()}


def make_loss_fn(plan: FourDPlan, *, train: bool = True):
    """Returns loss(params, graph, step, epoch=None, ef=None).

    Without ``ef`` the result is the (G_d,) per-group losses. When the plan
    compresses collectives (``TrainOptions.compress`` int8/int4) the caller
    passes the error-feedback pytree (``make_ef``) and gets
    ``(losses, new_ef)`` — the scan-chunked runtime carries ``new_ef`` in
    ``TrainState.comm_ef``. ``epoch`` defaults to the epoch the global step
    falls in (identical for the 'step' schedule, which ignores it); the
    scan-chunked runtime passes its carried counter through explicitly.

    The shard_map wraps sampling + forward + loss; jax.grad is applied
    OUTSIDE, so shard_map's transpose inserts the backward all-reduces and
    the DP gradient psum automatically (DESIGN.md §4).
    """
    cfg, builder = plan.cfg, plan.builder
    mesh = plan.mesh
    ds = plan.data_specs
    engine = plan.engine()
    e_specs = ef_specs(plan)

    def local_step(params, shards: GraphShards, feats, labels, step, epoch,
                   aux, ef=None):
        # the (g, g, ...) stacked block arrays arrive as (1, 1, ...) shards
        mb = builder.build_local(shards.squeeze_blocks(), feats, labels,
                                 step, cfg.num_layers, epoch=epoch, aux=aux)
        if ef is None:
            logits, st = engine(params, mb.adj, mb.feats, step=step,
                                train=train)
            new_ef = None
        else:
            logits, st, new_ef = engine(params, mb.adj, mb.feats, step=step,
                                        train=train, ef=_ef_squeeze(ef))
        with phase("loss"):
            nll_sum, cnt = pmm3d.parallel_cross_entropy(
                logits, mb.labels, class_axis=st.rep, row_axis=st.row,
                n_classes=cfg.num_classes)
            loss = nll_sum / jnp.maximum(cnt, 1.0)
        if ef is None:
            return loss[None]                # (1,) -> gathered over 'd'
        return loss[None], _ef_expand(new_ef)

    in_specs = (
        plan.p_specs,
        plan.shards_specs,
        ds["features"], plan.label_sp, P(), P(), plan.aux_specs,
    )
    sharded = jax.shard_map(
        local_step, mesh=mesh, in_specs=in_specs, out_specs=P("d"),
        check_vma=False)
    sharded_ef = None
    if e_specs is not None:
        sharded_ef = jax.shard_map(
            local_step, mesh=mesh, in_specs=in_specs + (e_specs,),
            out_specs=(P("d"), e_specs), check_vma=False)

    def loss_fn(params, graph, step, epoch=None, ef=None):
        if epoch is None:
            epoch = builder.epoch_of(step)
        args = (params, GraphShards.from_graph(graph),
                graph["features"], graph["labels"], step, epoch,
                graph.get("walk", {}))
        if ef is None:
            return sharded(*args)
        assert sharded_ef is not None, (
            "loss_fn got an EF pytree but the plan's TrainOptions.compress "
            "sends no quantized wire")
        return sharded_ef(*args, ef)
    return loss_fn


def make_train_step(plan: FourDPlan, optimizer):
    """(params, opt_state, graph, step) -> (params, opt_state, loss).

    Note: under a quantized ``TrainOptions.compress`` this simple step runs
    the compression STATELESS (zero error feedback every step — the byte
    reduction shows, the residual correction does not). The EF carry lives
    in the scan-chunked runtime (``train.Trainer`` / ``TrainState.comm_ef``).
    """
    loss_fn = make_loss_fn(plan, train=True)

    def mean_loss(params, graph, step):
        return loss_fn(params, graph, step).mean()

    @jax.jit
    def train_step(params, opt_state, graph, step):
        loss, grads = jax.value_and_grad(mean_loss)(params, graph, step)
        params, opt_state = optimizer.update(params, grads, opt_state)
        return params, opt_state, loss

    return train_step


def make_eval_step(plan: FourDPlan):
    """Distributed full-graph evaluation (paper Table II): a single 3D-PMM
    forward over the *whole* graph — no sampling — returning (correct,
    total) over true vertices. The same ``ForwardEngine`` as training, with
    the "csr" aggregation backend (no densification of full
    n_local x n_local blocks)."""
    cfg = plan.cfg
    mesh = plan.mesh
    ds = plan.data_specs
    engine = plan.engine(backend="csr", csr_rows=plan.scfg.n_local)

    def local_eval(params, shards: GraphShards, feats, labels):
        shards = shards.squeeze_blocks()
        planes = tuple(shards.plane(li)
                       for li in range(min(3, cfg.num_layers)))
        logits, st = engine(params, planes, feats,
                            step=jnp.zeros((), jnp.int32), train=False)
        correct, total = pmm3d.parallel_argmax_correct(
            logits, labels, class_axis=st.rep, row_axis=st.row,
            n_classes=cfg.num_classes)
        return correct[None], total[None]

    in_specs = (
        plan.p_specs,
        plan.shards_specs,
        ds["features"], plan.label_sp,
    )
    sharded = jax.shard_map(
        local_eval, mesh=mesh, in_specs=in_specs,
        out_specs=(P("d"), P("d")), check_vma=False)

    @jax.jit
    def eval_step(params, graph):
        c, t = sharded(params, GraphShards.from_graph(graph),
                       graph["features"], graph["labels"])
        return c[0] / jnp.maximum(t[0], 1.0)
    return eval_step
