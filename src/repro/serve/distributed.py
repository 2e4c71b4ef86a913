"""Multi-host serving over the 3D PMM mesh.

The single-device engine assembles one ``(total, total)`` block and runs the
reference forward. This module fans that same work out across the paper's
3D PMM grid (optionally x a data axis), exactly like ``core/fourd.py``'s
eval step:

* the request batch is planned host-side into ``total/g`` vertices per
  contiguous vertex range (``assembler.plan_batch_ranges``) — the serving
  analogue of stratified sampling, so every device's block has a static
  shape;
* inside ONE ``shard_map`` over ``(d, x, y, z)``, each device runs the
  communication-free Alg.-2 extraction of its local ``(b_loc, b_loc)``
  adjacency block through ``MinibatchBuilder.extract_block`` (the identical
  per-device assembly the 4D train step uses — ROADMAP 'one step closer'),
  then the 3D-PMM GCN forward (the ONE ``core/forward.py`` engine) with
  one all-reduce per matmul;
* the ``d`` axis serves ``dp`` *independent stacked micro-batches* per
  device call — continuous batching across data-parallel groups, which is
  what the threaded driver keeps fed.

The support set is communication-free by construction: the per-range support
pools are pure functions of ``(seed, range)``, so any replica planning the
same micro-batch derives the identical batch with zero coordination.

Everything reuses the training machinery — ``param_specs`` /
``graph_data_specs`` / ``GraphShards`` / ``ForwardEngine``. A ``(1, 1, 1)`` mesh is the single-device special case and the
correctness oracle (``tests/test_serve_distributed.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import fourd, pmm3d
from repro.core import sampling as smp
from repro.core.forward import ForwardEngine
from repro.core.gcn_model import GCNConfig
from repro.core.minibatch import GraphShards, MinibatchBuilder
from repro.graphs.csr import CSRMatrix
from repro.graphs.partition import PartitionedGraph, partition_csr_2d
from repro.serve import assembler as asm


def make_serve_mesh(g: int, dp: int = 1,
                    devices: Optional[np.ndarray] = None) -> Mesh:
    """The serving mesh: ``dp`` data-parallel groups x a cube ``g^3`` PMM
    grid — the same ``(d, x, y, z)`` axes as training."""
    return fourd.make_mesh_4d(dp, g, devices)


def partition_for_serving(A: CSRMatrix, features: np.ndarray,
                          g: int) -> PartitionedGraph:
    """g x g padded-CSR block partition of the serving graph (no labels —
    inference only; ghosts carry zero features and no edges)."""
    n = A.n_rows
    n_local = -(-n // g)
    n_pad = n_local * g
    block_rp, block_ci, block_val, e_pad, max_row_nnz = partition_csr_2d(
        A, g, n_pad)
    feats = np.zeros((n_pad, features.shape[1]), np.float32)
    feats[:n] = features
    return PartitionedGraph(
        n=n, n_pad=n_pad, g=g, n_local=n_local, e_pad=e_pad,
        block_rp=block_rp, block_ci=block_ci, block_val=block_val,
        max_block_row_nnz=max_row_nnz, features=feats,
        labels=np.full((n_pad,), -1, np.int32),
        train_mask=np.zeros((n_pad,), bool), num_classes=0)


@dataclasses.dataclass
class DistributedServePlan:
    """Everything the engine needs to serve over the mesh: the partitioned
    graph, per-range support pools, and ONE jitted sharded step serving
    ``dp`` stacked micro-batches per call."""

    mesh: Mesh
    cfg: GCNConfig
    spec: asm.AssemblySpec
    pg: PartitionedGraph
    builder: MinibatchBuilder
    pools: List[np.ndarray]
    p_specs: Any
    data_specs: Dict[str, P]
    num_classes_padded: int
    step: Any                       # (params, graph, ids3d, scale3d) -> logits

    @property
    def g(self) -> int:
        return int(self.mesh.shape["x"])

    @property
    def dp(self) -> int:
        return int(self.mesh.shape["d"])

    @property
    def b_local(self) -> int:
        return self.spec.total // self.g

    def shard_params(self, params):
        """Pad the output head to the grid side and place every parameter on
        its training-plane sharding."""
        padded, _ = fourd.pad_output_head(params, self.cfg.num_classes,
                                          self.g)
        return jax.device_put(padded, jax.tree.map(
            lambda sp: NamedSharding(self.mesh, sp), self.p_specs,
            is_leaf=lambda x: isinstance(x, P)))

    def shard_graph(self) -> Dict[str, Any]:
        return fourd.shard_graph_arrays(self.mesh, self.pg, self.data_specs)


def build_serve_plan(A: CSRMatrix, features: np.ndarray, cfg: GCNConfig,
                     mesh: Mesh, spec: asm.AssemblySpec, *,
                     extract_impl: str = "jax", support_seed: int = 0,
                     opts: Optional[fourd.TrainOptions] = None
                     ) -> DistributedServePlan:
    """Build the shard_map'd serving step over ``mesh``.

    The per-device body is ``MinibatchBuilder.extract_block`` per rotation
    plane (communication-free — the batch ids are replicated, the adjacency
    shard is local) followed by the ``ForwardEngine`` layer program; the
    only collectives are the PMM all-reduces of the forward itself.
    """
    g = int(mesh.shape["x"])
    assert mesh.shape["y"] == g and mesh.shape["z"] == g, (
        "serving uses the paper's cube 3D grid")
    assert spec.total % g == 0, (spec.total, g)
    assert spec.slots <= spec.total // g, (
        f"slots={spec.slots} can overflow one vertex range (capacity "
        f"{spec.total // g}); raise support so total/g >= slots")
    assert cfg.d_in % g == 0 and cfg.d_hidden % g == 0, (
        "d_in / d_hidden must divide by the grid side")
    opts = opts or fourd.TrainOptions()
    pg = partition_for_serving(A, features, g)
    b_loc = spec.total // g
    max_rn = max(pg.max_block_row_nnz, 1)
    builder = MinibatchBuilder(
        scfg=smp.SampleConfig(n_pad=pg.n_pad, g=g, batch=spec.total,
                              e_cap=b_loc * max_rn),
        mode="exact", impl=extract_impl, max_row_nnz=max_rn)
    pools = asm.make_support_pools(pg.n, pg.n_pad, g, support_seed,
                                   min_size=b_loc)

    p_specs = fourd.param_specs(cfg.num_layers)
    ds = fourd.graph_data_specs()
    n_cls_pad = fourd.padded_class_count(cfg.num_classes, g)
    st_f = pmm3d.state_after_layers(cfg.num_layers)
    # serving blocks are extracted dense (builder fmt above), whatever
    # opts.spmm_impl says about training
    engine = ForwardEngine.from_options(cfg, opts, grid_side=g,
                                        backend="dense")

    def local_serve(params, shards: GraphShards, feats, ids, scale):
        # ids/scale arrive (1, g, b_loc) per device: one micro-batch per DP
        # group, replicated within the 3D grid
        shards = shards.squeeze_blocks()
        ids, scale = ids[0], scale[0]
        # THE training extraction loop (MinibatchBuilder) with the planner's
        # per-column rescale in place of the sampling constants
        blocks = builder.extract_plane_blocks(
            shards, ids, cfg.num_layers,
            col_scale_fn=lambda i, j: scale[j])
        x_local = builder.local_rows(feats, ids, "x")
        logits, _ = engine(params, blocks, x_local,
                           step=jnp.zeros((), jnp.int32), train=False)
        return logits[None]                   # re-add the 'd' dim

    in_specs = (p_specs, GraphShards.specs(ds), ds["features"],
                P("d"), P("d"))
    sharded = jax.shard_map(local_serve, mesh=mesh, in_specs=in_specs,
                        out_specs=P("d", st_f.row, st_f.rep),
                        check_vma=False)

    @jax.jit
    def step(params, graph, ids3d, scale3d):
        """(dp, g, b_loc) ids/scales -> (dp, total, n_cls_pad) logits, rows
        in flat (range-major = globally sorted) batch order."""
        return sharded(params, GraphShards.from_graph(graph),
                       graph["features"], ids3d, scale3d)

    return DistributedServePlan(
        mesh=mesh, cfg=cfg, spec=spec, pg=pg, builder=builder, pools=pools,
        p_specs=p_specs, data_specs=ds, num_classes_padded=n_cls_pad,
        step=step)
