"""Unified mini-batch construction — ONE owner for Alg. 2 end to end.

Every consumer of a sampled sub-adjacency in this repo — the 4D train step,
the full-graph eval step, the §V-A prefetched pipeline, the baseline
samplers, and the serving assembler — used to thread raw ``rp, ci, val``
CSR triples by hand and call the extraction primitives directly. This
module is the single batch-construction layer they all go through now:

* ``GraphShards``    — a registered pytree bundling the three per-plane
                       padded-CSR triples (one per layer-rotation plane,
                       §IV-C) that previously traveled as 9 flat arrays
                       through every ``shard_map``.
* ``Minibatch``      — a registered pytree for one constructed batch (the
                       per-plane adjacency blocks + feature/label slices);
                       the §V-A pipeline carries it across steps.
* ``BlockFormat``    — the extracted block's layout: ``DENSE`` (MXU tiles)
                       or ``ELL`` (block-ELL for the Pallas SpMM kernel).
* ``MinibatchBuilder`` — owns sampling-mode dispatch (``exact`` |
                       ``stratified``), per-plane block extraction,
                       the rescale constants (Eq. 23-24), the per-column
                       rescale serving needs, and the extraction backend
                       (pure JAX or the fused Pallas kernel).

Mapping to the paper's Alg. 2 (four phases):

  phase 1 (range location)  — ``sample()``: stratified samples are *born*
                              range-local, so the binary search of Alg. 2
                              line 3 is replaced by construction;
  phase 2 (row extraction)  — ``extract_block()``: prefix-sum vectorized
                              CSR row gather (``sampling._extract_triples``
                              lines 6-10, or fused in
                              ``kernels/extract_gather.py``);
  phase 3 (column filter)   — same call: binary-search membership filter +
                              compact remap (lines 11-14);
  phase 4 (rescale/assembly)— same call: the unbiased Eq. 24 rescale with
                              the self-loop exemption, assembled into the
                              requested ``BlockFormat``.

The Pallas backend (``impl='pallas'``) fuses phases 2-4 into one kernel so
the extracted edges never round-trip through HBM as COO triples; the pure
JAX path is the reference oracle and the property tests assert both produce
identical blocks in both formats.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import pmm3d
from repro.core import sampling as smp
from repro.obs.tracer import phase


class BlockFormat(enum.Enum):
    """Layout of an extracted mini-batch adjacency block."""

    DENSE = "dense"
    ELL = "ell"

    @classmethod
    def from_spmm_impl(cls, impl: str) -> "BlockFormat":
        """Map ``TrainOptions.spmm_impl`` ('dense' | 'ell')."""
        return cls(impl)


# ---------------------------------------------------------------------------
# Pytrees
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphShards:
    """This device's adjacency state: one padded-CSR triple per rotation
    plane (the paper's 'three adjacency shards per GPU', §IV-C3). The same
    underlying blocks, sharded three ways — see ``fourd.graph_data_specs``.
    """

    rp: Tuple[jax.Array, ...]
    ci: Tuple[jax.Array, ...]
    val: Tuple[jax.Array, ...]

    @property
    def num_planes(self) -> int:
        return len(self.rp)

    def plane(self, li: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """The CSR triple for layer ``li`` (plane rotation is mod-3)."""
        li = li % len(self.rp)
        return self.rp[li], self.ci[li], self.val[li]

    def squeeze_blocks(self) -> "GraphShards":
        """Strip the (1, 1) leading dims that ``shard_map`` leaves on the
        stacked (g, g, ...) block arrays once they arrive per-device, under
        the ``unpack`` scope (on the chip the strip can be a copy of every
        CSR entry)."""
        sq = lambda a: a[0, 0]
        with phase("unpack"):
            return GraphShards(rp=tuple(sq(a) for a in self.rp),
                               ci=tuple(sq(a) for a in self.ci),
                               val=tuple(sq(a) for a in self.val))

    @classmethod
    def from_graph(cls, graph: Dict[str, Any]) -> "GraphShards":
        """Bundle the ``shard_graph`` output dict (adj1/adj2/adj3 triples)."""
        a1, a2, a3 = graph["adj1"], graph["adj2"], graph["adj3"]
        return cls(rp=(a1[0], a2[0], a3[0]),
                   ci=(a1[1], a2[1], a3[1]),
                   val=(a1[2], a2[2], a3[2]))

    @classmethod
    def specs(cls, data_specs: Dict[str, Any]) -> "GraphShards":
        """The matching ``in_specs`` pytree: every component of plane l
        carries that plane's PartitionSpec."""
        s = (data_specs["adj1"], data_specs["adj2"], data_specs["adj3"])
        return cls(rp=s, ci=s, val=s)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Minibatch:
    """One constructed mini-batch: per-plane adjacency blocks (dense array
    or block-ELL (tiles, colidx) tuple per plane), local feature rows on
    plane (x, z), and local label rows on the final row axis."""

    adj: Tuple[Any, ...]
    feats: jax.Array
    labels: jax.Array

    def add_leading(self) -> "Minibatch":
        """Re-add the leading device dim so shard_map out_specs can scatter
        the carried batch onto the mesh (§V-A pipeline state)."""
        return jax.tree.map(lambda a: a[None], self)

    def strip_leading(self) -> "Minibatch":
        """Drop the leading device dim of the carried batch as it arrives
        per-device, under the ``unpack`` scope as ``squeeze_blocks``."""
        with phase("unpack"):
            return jax.tree.map(lambda a: a[0], self)


# ---------------------------------------------------------------------------
# The builder
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MinibatchBuilder:
    """Owns every decision between 'a seed/step or vertex set' and 'the
    blocks the model consumes'. All fields are static (jit-stable).

    ``impl='pallas'`` requires ``max_row_nnz`` (the static per-row edge
    bound, e.g. ``PartitionedGraph.max_block_row_nnz`` or
    ``CSRMatrix.max_row_nnz()``) — the fused kernel walks each sampled
    row's edges up to that bound instead of using the COO-level ``e_cap``.
    """

    scfg: smp.SampleConfig
    mode: str = "stratified"          # 'stratified' | 'exact' | 'partition'
                                      # | 'walk'
    schedule: str = "step"            # 'step' | 'epoch' (without-replacement)
    fmt: BlockFormat = BlockFormat.DENSE
    impl: str = "jax"                 # 'jax' | 'pallas'
    block_dtype: Any = jnp.float32
    ell_tile: int = 128               # (bm = bn) MXU-aligned tile side
    ell_slots: int = 16               # max nonzero col-tiles per row-block
    max_row_nnz: int = 0              # static per-row nnz bound (pallas)
    seed: int = 0

    def __post_init__(self):
        assert self.mode in ("exact", "stratified", "partition", "walk"), \
            self.mode
        assert self.schedule in ("step", "epoch"), self.schedule
        assert self.impl in ("jax", "pallas"), self.impl
        self.scfg.validate()
        if self.mode == "partition":
            assert self.scfg.clusters > 0, (
                "partition mode needs SampleConfig.clusters — partition the "
                "graph with build_partitioned_graph(..., clusters=C)")
        if self.mode == "walk":
            assert self.scfg.walk_len > 0 and self.scfg.walk_k > 0, (
                "walk mode needs SampleConfig.walk_len/walk_k (the "
                "replicated neighbor table from graphs.build_walk_tables)")
        if self.mode in ("partition", "walk"):
            assert self.impl == "jax", (
                f"{self.mode} mode rescales per edge ((b, b) matrix) — the "
                "fused Pallas extraction only supports scalar/per-column "
                "rescale; use extract_impl='jax'")
        if self.impl == "pallas":
            assert self.max_row_nnz > 0, (
                "the fused Pallas extraction needs the static per-row edge "
                "bound (max_row_nnz)")

    @classmethod
    def from_options(cls, scfg: smp.SampleConfig, opts,
                     max_row_nnz: int = 0) -> "MinibatchBuilder":
        """Build from ``fourd.TrainOptions`` (duck-typed to avoid a cycle)."""
        return cls(
            scfg=scfg, mode=getattr(opts, "sample_kind", "stratified"),
            schedule=getattr(opts, "sample_mode", "step"),
            fmt=BlockFormat.from_spmm_impl(opts.spmm_impl),
            impl=getattr(opts, "extract_impl", "jax"),
            block_dtype=(jnp.bfloat16 if opts.block_dtype == "bf16"
                         else jnp.float32),
            ell_tile=opts.ell_tile, ell_slots=opts.ell_slots,
            max_row_nnz=max_row_nnz, seed=opts.seed)

    # -- phase 1: sampling ---------------------------------------------------

    @property
    def steps_per_epoch(self) -> int:
        return self.scfg.steps_per_epoch

    def epoch_of(self, step: jax.Array) -> jax.Array:
        """The epoch a global step falls in — epoch boundaries sit at fixed
        multiples of ``steps_per_epoch``, so the counter is derivable from
        the step alone (callers that carry an explicit epoch, e.g. the
        ``TrainState`` runtime, pass it through instead)."""
        return jnp.asarray(step, jnp.int32) // self.steps_per_epoch

    def sample(self, key: jax.Array, t: jax.Array | None = None,
               aux: Optional[Dict[str, jax.Array]] = None) -> jax.Array:
        """(g, b) global vertex ids — sampling-mode dispatch. ``t`` is the
        step *within* the epoch (required under the 'epoch' schedule, where
        ``key`` is the epoch key and the sample is permutation slice ``t``;
        ignored under 'step', where ``key`` is the per-step key). ``aux``
        carries the replicated walk tables (walk mode only)."""
        if self.mode == "walk":
            nbr = aux["nbr"]
            if self.schedule == "epoch":
                assert t is not None, \
                    "the epoch schedule needs the in-epoch step"
                return smp.sample_walk_stratified(key, self.scfg, nbr, t=t)
            return smp.sample_walk_stratified(key, self.scfg, nbr)
        if self.schedule == "epoch":
            assert t is not None, "the epoch schedule needs the in-epoch step"
            if self.mode == "exact":
                s = smp.sample_epoch_exact(key, self.scfg.n_pad,
                                           self.scfg.batch, t)
                return s[None]                   # one range at g = 1
            if self.mode == "partition":
                return smp.sample_partition_epoch(key, self.scfg, t)
            return smp.sample_epoch_stratified(key, self.scfg, t)
        if self.mode == "exact":
            s = smp.sample_uniform_exact(key, self.scfg.n_pad,
                                         self.scfg.batch)
            return s[None]                       # one range at g = 1
        if self.mode == "partition":
            return smp.sample_partition_stratified(key, self.scfg)
        return smp.sample_stratified(key, self.scfg)

    def sample_ids(self, step: jax.Array, epoch: jax.Array | None,
                   dp_index: jax.Array | int,
                   aux: Optional[Dict[str, jax.Array]] = None) -> jax.Array:
        """Key derivation + schedule dispatch in one place: the (g, b)
        sample as a pure function of ``(seed, epoch, step, dp_index)`` —
        identical on every device of a DP group, zero communication.

        Partition + epoch with ``dp_groups > 1`` is special: the DP groups
        share the UN-dp-folded epoch key and take interleaved slices of the
        SAME cluster permutation, so together they cover every cluster
        exactly once per epoch, disjointly (the paper's without-replacement
        guarantee extended across the DP axis)."""
        step = jnp.asarray(step, jnp.int32)
        if self.schedule == "epoch":
            epoch = self.epoch_of(step) if epoch is None else epoch
            t = step - epoch * self.steps_per_epoch
            if self.mode == "partition" and self.scfg.dp_groups > 1:
                return smp.sample_partition_epoch(
                    smp.epoch_key(self.seed, epoch, 0), self.scfg, t,
                    dp_slot=dp_index)
            return self.sample(smp.epoch_key(self.seed, epoch, dp_index), t,
                               aux)
        return self.sample(smp.step_key(self.seed, step, dp_index), aux=aux)

    def rescale_constants(self) -> Tuple[float, float]:
        """(1/p_same, 1/p_cross): Eq. 23, range-dependent under
        stratification, the paper's single constant in exact mode."""
        if self.mode == "exact":
            n, b = self.scfg.n_pad, self.scfg.batch
            inv = (n - 1) / (b - 1) if b > 1 else 1.0
            return inv, inv
        return smp.rescale_constants(self.scfg)

    def col_scale_fn(self, s2d: jax.Array,
                     aux: Optional[Dict[str, jax.Array]] = None):
        """The per-mode off-diagonal rescale as a ``(i, j) -> scale``
        closure over the (g, b) sample (``extract_plane_blocks``'s
        contract). Scalar for exact/stratified (Eq. 23); a (b, b) per-pair
        matrix for partition (tri-level: cluster / range / cross) and walk
        (the SAINT 1/q_uv edge normalization)."""
        if self.mode == "partition":
            inv_cc, inv_cr = smp.partition_rescale_constants(self.scfg)
            return lambda i, j: smp.partition_col_scale(
                s2d[i], s2d[j], i, j, self.scfg, inv_cc, inv_cr)
        if self.mode == "walk":
            p_incl = aux["p"]
            return lambda i, j: smp.walk_col_scale(s2d[i], s2d[j], p_incl)
        inv_same, inv_cross = self.rescale_constants()
        return lambda i, j: smp.stratified_col_scale(
            i, j, inv_same, inv_cross)

    # -- phases 2-4: block extraction ---------------------------------------

    def extract_block(
        self,
        rp: jax.Array, ci: jax.Array, val: jax.Array,
        rows_local: jax.Array, cols_local: jax.Array,
        *,
        col_scale: jax.Array | float,
        diag: jax.Array | bool,
        e_cap: Optional[int] = None,
        fmt: Optional[BlockFormat] = None,
        dtype: Any = None,
    ):
        """Extract ONE rescaled block in the configured format/backend.

        ``col_scale`` is the off-diagonal rescale: a scalar (training,
        Eq. 23 — possibly traced, e.g. the stratified same/cross-range
        select) or a (b_c,) per-column vector (serving: requested vertices
        at p=1, support at p_support). ``diag`` marks coinciding row/column
        vertex sets, enabling the Eq. 24 self-loop exemption; it may be a
        traced scalar inside shard_map.
        """
        e_cap = self.scfg.e_cap if e_cap is None else e_cap
        fmt = self.fmt if fmt is None else fmt
        dtype = self.block_dtype if dtype is None else dtype

        if self.impl == "pallas":
            # the fused kernel bounds edges per row (max_row_nnz), the jax
            # path in total (e_cap); they are equivalent only when neither
            # truncates — reject configs where the jax path would drop edges
            assert e_cap >= rows_local.shape[0] * self.max_row_nnz, (
                f"e_cap={e_cap} truncates ({rows_local.shape[0]} rows x "
                f"max_row_nnz={self.max_row_nnz}): the fused kernel would "
                "not, so the backends would diverge")
            from repro.kernels.extract_gather import extract_dense_fused
            dense = extract_dense_fused(
                rp, ci, val, rows_local, cols_local,
                col_scale=col_scale, diag=diag,
                max_deg=self.max_row_nnz, dtype=dtype)
            if fmt is BlockFormat.DENSE:
                return dense
            from repro.kernels.spmm_ell import dense_to_block_ell_ranked
            return dense_to_block_ell_ranked(
                dense, self.ell_tile, self.ell_tile, self.ell_slots)

        if fmt is BlockFormat.ELL:
            return smp.extract_block_ell(
                rp, ci, val, rows_local, cols_local, e_cap,
                rescale_offdiag=col_scale, is_diag_block=diag,
                bm=self.ell_tile, bn=self.ell_tile,
                n_slots=self.ell_slots, dtype=dtype)
        return smp.extract_dense_block(
            rp, ci, val, rows_local, cols_local, e_cap,
            rescale_offdiag=col_scale, is_diag_block=diag, dtype=dtype)

    # -- the distributed path (inside shard_map) -----------------------------

    def extract_plane_blocks(self, shards: GraphShards, ids2d: jax.Array,
                             num_layers: int, *, col_scale_fn,
                             fmt: Optional[BlockFormat] = None
                             ) -> Tuple[Any, ...]:
        """The rotation-plane extraction loop shared by training
        (``build_local``) and the shard_map'd serving step
        (``serve/distributed.py``): for each of the first ``min(3,
        num_layers)`` planes, this device extracts its (i, j) block of the
        batch adjacency — ``ids2d`` is the (g, b) per-range global vertex
        ids, i/j the device's row/col vertex-range coords on that plane.
        ``col_scale_fn(i, j)`` supplies the off-diagonal rescale (a traced
        scalar, or a (b,) per-column vector for serving)."""
        n_loc = self.scfg.n_local
        st = pmm3d.initial_state()
        blocks = []
        for li in range(min(3, num_layers)):
            pr, pc = st.adj_plane                    # (p, r)
            i = jax.lax.axis_index(pr)               # row vertex range
            j = jax.lax.axis_index(pc)               # col vertex range
            rp, ci, val = shards.plane(li)
            blocks.append(self.extract_block(
                rp, ci, val, ids2d[i] - i * n_loc, ids2d[j] - j * n_loc,
                col_scale=col_scale_fn(i, j), diag=i == j, fmt=fmt))
            st = st.rotate()
        return tuple(blocks)

    def local_rows(self, rows_global: jax.Array, ids2d: jax.Array,
                   axis: str) -> jax.Array:
        """This device's slice of per-vertex rows (features/labels) sharded
        over mesh axis ``axis``: the rows of its range's batch vertices."""
        i = jax.lax.axis_index(axis)
        return rows_global[ids2d[i] - i * self.scfg.n_local]

    def build_local(self, shards: GraphShards, feats_loc: jax.Array,
                    labels_loc: jax.Array, step: jax.Array,
                    num_layers: int, *, epoch: jax.Array | None = None,
                    dp_axis: str = "d",
                    aux: Optional[Dict[str, jax.Array]] = None) -> Minibatch:
        """Alg. 2: communication-free construction of this device's batch.

        Every device derives the identical sample from (seed, epoch, step,
        dp_index) — per-step key under the 'step' schedule, epoch-
        permutation slice under 'epoch' (``epoch`` defaults to the one the
        global step falls in) — and extracts its local adjacency block for
        each of the three rotation planes, plus its feature/label slices.
        ``aux`` holds walk mode's REPLICATED tables ({'nbr', 'p'} from
        ``graphs.build_walk_tables``), so its gathers stay device-local.
        NO collectives in ANY mode — asserted by tests on the lowered HLO.
        """
        with phase("sample"):
            s2d = self.sample_ids(step, epoch, jax.lax.axis_index(dp_axis),
                                  aux)                      # (g, b) ids
        with phase("extract"):
            blocks = self.extract_plane_blocks(
                shards, s2d, num_layers,
                col_scale_fn=self.col_scale_fn(s2d, aux))
            # features on plane (x, z): rows = sample of range x_coord
            x_local = self.local_rows(feats_loc, s2d, "x")
            # labels sharded over the final row axis
            r_f = pmm3d.state_after_layers(num_layers).row
            y_local = self.local_rows(labels_loc, s2d, r_f)
        return Minibatch(adj=blocks, feats=x_local, labels=y_local)

    # -- the single-device path (oracles, baselines, ablations) --------------

    def build_single(self, key: jax.Array, rp: jax.Array, ci: jax.Array,
                     val: jax.Array, features: jax.Array,
                     labels: jax.Array) -> smp.MiniBatch:
        """One-device batch in the configured sampling mode (Alg. 1)."""
        assert self.mode in ("exact", "stratified"), (
            f"build_single supports the Alg. 1 modes; {self.mode} mode is "
            "distributed-only (build_local) — its single-device oracle is "
            "core/baselines.py")
        if self.mode == "exact":
            s = self.sample(key)[0]
            inv_p, _ = self.rescale_constants()
            adj = self.extract_block(rp, ci, val, s, s,
                                     col_scale=inv_p, diag=True,
                                     fmt=BlockFormat.DENSE)
            return smp.MiniBatch(adj=adj, feats=features[s],
                                 labels=labels[s], vertex_ids=s)
        return smp.make_minibatch_stratified(key, rp, ci, val, features,
                                             labels, self.scfg)

    # -- the serving path (arbitrary requested vertex sets) ------------------

    def assemble(self, rp: jax.Array, ci: jax.Array, val: jax.Array,
                 batch_ids: jax.Array, col_scale: jax.Array,
                 e_cap: Optional[int] = None, dtype: Any = None):
        """Serving assembly: row and column sets coincide (diag block), the
        rescale is the planner's per-column vector (requested at p=1,
        support at p_support — ``serve/assembler.py``)."""
        return self.extract_block(rp, ci, val, batch_ids, batch_ids,
                                  col_scale=col_scale, diag=True,
                                  e_cap=e_cap, fmt=BlockFormat.DENSE,
                                  dtype=dtype)
