"""The ONE distributed layer-loop executor (ScaleGNN §III/§IV).

Every consumer of the 3D-PMM GCN program — the 4D train step
(``fourd.make_loss_fn``), full-graph eval (``fourd.make_eval_step``), the
§V-A prefetched pipeline (``core/pipeline.py``), and distributed serving
(``serve/distributed.py``) — used to carry its own copy of the layer loop
(SpMM -> GEMM -> residual reshard -> elementwise tail -> rotate), with the
SpMM backend picked by ``isinstance(blk, tuple)`` checks and a mid-loop
import. CAGNET-style work (Tripathy et al.) shows this exact loop is where
1.5D/3D aggregation variants plug in, so it lives in ONE place now:

``ForwardEngine`` runs the layer program parameterized by

* an **aggregation backend** — how one layer's ``A @ H`` is computed:
    - ``"dense"``  the mini-batch block is a dense (b, b) array; plain PMM
                   matmul + psum (Eq. 27),
    - ``"ell"``    the block is a block-ELL ``(tiles, colidx)`` pair; the
                   Pallas SpMM kernel + psum (§Perf H3.4),
    - ``"csr"``    the block is a padded-CSR ``(rp, ci, val)`` triple over
                   the *full local* graph shard; local sparse SpMM + psum
                   (full-graph eval, where densifying an
                   (n_local, n_local) block would be wasteful);
* a **precision policy** — bf16 PMM all-reduces (§V-B) via
  ``TrainOptions.bf16_collectives`` (FP32 loss/norm reductions stay FP32
  inside ``pmm3d``);
* the **elementwise tail** — RMSNorm -> ReLU -> dropout -> residual
  (Eqs. 7-10), either as separate jnp ops (reference) or through the §V-C
  fused Pallas kernel (``TrainOptions.fused_elementwise``): fully fused
  when the RMSNorm reduction is device-local (``grid_side == 1`` or
  RMSNorm off), otherwise the distributed norm (FP32 psum) followed by the
  fused ReLU/dropout/residual kernel.

The engine runs *inside* ``shard_map`` over the ``(x, y, z)`` PMM axes
(with the DP axis ``d`` wrapped around it by the callers); all fields are
static so an engine instance is jit-stable.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import pmm3d
from repro.core.gcn_model import GCNConfig
from repro.core.precision import WIRE_FORMATS
from repro.kernels import ops as kops
from repro.obs.tracer import phase

BACKENDS = ("dense", "ell", "csr")
OVERLAPS = ("none", "ring")
COMPRESS_SCHEDULES = ("uniform", "variable")
# formats with a quantized (int) wire — the ones that carry error feedback
QUANTIZED_FORMATS = ("int8", "int4")


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    """Optimization toggles for the distributed step (paper §V)."""

    bf16_collectives: bool = False     # §V-B
    fused_elementwise: bool = False    # §V-C
    reshard_impl: str = "gather"       # §IV-C4 / §Perf
    dropout: float = 0.0               # dropout inside the distributed model
    seed: int = 0
    # Sampling schedule: "step" draws an independent per-step sample
    # (seed, step, dp); "epoch" runs without replacement within an epoch —
    # one permutation per (seed, epoch, dp), step t takes slice t
    # (core/sampling.py; still communication-free).
    sample_mode: str = "step"          # "step" | "epoch"
    # Sampling family (ROADMAP item 2): "stratified" draws per-range
    # vertices uniformly (the paper's Alg. 1); "partition" draws whole
    # locality clusters (Cluster-GCN-style — shrinks the off-diagonal
    # support pool and tightens e_cap to q * max_cluster_block_nnz);
    # "walk" grows GraphSAINT random-walk batches over a replicated
    # in-range neighbor table. All three stay communication-free: the
    # sample is a pure function of (seed, epoch, step, dp).
    sample_kind: str = "stratified"    # "stratified" | "partition" | "walk"
    clusters: int = 0                  # partition: clusters per range
                                       # (0 = take PartitionedGraph.clusters)
    walk_len: int = 4                  # walk: steps per root walk
    walk_k: int = 8                    # walk: neighbor-table width
    # §Perf H3.3 (beyond-paper): dtype of the extracted dense mini-batch
    # adjacency blocks. bf16 halves the dominant memory stream of the 4D
    # step (the B x B blocks) while the SpMM accumulates in f32.
    block_dtype: str = "f32"           # "f32" | "bf16"
    # §Perf H3.4 (beyond-paper): extract the mini-batch adjacency directly
    # into block-ELL and run the SpMM through the Pallas kernel — at
    # production scale the sampled blocks are >99% tile-sparse, so this
    # cuts the dominant memory term by the tile-density factor.
    spmm_impl: str = "dense"           # "dense" | "ell"
    ell_tile: int = 128                # (bm = bn) MXU-aligned tile side
    ell_slots: int = 16                # max nonzero col-tiles per row-block
    # Extraction backend for the mini-batch blocks: "jax" (reference, COO
    # triples through HBM) or "pallas" (kernels/extract_gather.py — Alg. 2
    # phases 2-4 fused in one kernel).
    extract_impl: str = "jax"          # "jax" | "pallas"
    # Comm–compute overlap (§V / ROADMAP item 4): "ring" decomposes the PMM
    # all-reduces into chunked ppermute rings and software-pipelines the
    # layer body (residual reshard issued first, reduced SpMM chunks GEMMed
    # on arrival) so each transfer step hides behind a chunk of compute.
    # Bit-identical to "none" at grid sides <= 2 (single-add reductions);
    # the FP32 loss/norm reductions stay monolithic either way.
    overlap_impl: str = "none"         # "none" | "ring"
    # Compressed collectives (ROADMAP item 1): the STRONGEST wire format
    # the engine may use for the PMM all-reduces and the residual reshard.
    # "bf16" = bf16 wire everywhere (reshard gathers included — beyond the
    # psum-only bf16_collectives knob); "int8"/"int4" send absmax-quantized
    # ring chunks (4x / 8x fewer payload bytes) with per-site error-feedback
    # accumulators carried in TrainState so accuracy holds. FP32 loss/norm
    # reductions and gradient collectives stay uncompressed.
    compress: str = "none"             # "none" | "bf16" | "int8" | "int4"
    # Per-layer ratio schedule (the gnn_compress "variable" scheme):
    # "uniform" puts `compress` on every layer; "variable" ramps the ladder
    # bf16 -> int8 -> int4 with depth (early layers carry the least-settled
    # activations, so they compress the least; deeper layers hardest),
    # capped at `compress`.
    compress_schedule: str = "uniform"  # "uniform" | "variable"


def wire_format(compress: str, schedule: str, layer: int,
                num_layers: int) -> str:
    """The wire format layer ``layer`` (0-based) uses under the compression
    knobs: "uniform" applies ``compress`` everywhere; "variable" ramps the
    bf16 -> int8 -> int4 ladder with depth, capped at ``compress``."""
    assert compress in WIRE_FORMATS, compress
    assert schedule in COMPRESS_SCHEDULES, schedule
    if compress in ("none", "bf16") or schedule == "uniform":
        return compress
    ladder = ["bf16", "int8", "int4"]
    cap = ladder.index(compress)
    if num_layers <= 1:
        return compress
    return ladder[int(layer * cap / (num_layers - 1) + 0.5)]


def _dropout_key(opts: TrainOptions, step: jax.Array, layer: int,
                 row_axis: str, rep_axis: str,
                 dp_axis: Optional[str]) -> jax.Array:
    """Per-block dropout key. Folded with the (row, rep) block coords only —
    replicas along the col axis MUST use the identical mask, or the psum
    replicas diverge (DESIGN.md §4)."""
    k = jax.random.PRNGKey(opts.seed + 1)
    k = jax.random.fold_in(k, step)
    k = jax.random.fold_in(k, layer)
    k = jax.random.fold_in(k, jax.lax.axis_index(row_axis))
    k = jax.random.fold_in(k, jax.lax.axis_index(rep_axis))
    if dp_axis is not None:
        k = jax.random.fold_in(k, jax.lax.axis_index(dp_axis))
    return k


def _fused_row_tile(b: int) -> int:
    """Largest kernel row tile that divides the local batch (the Pallas tail
    requires rows % tile == 0; mini-batch blocks are powers of two in
    practice, so this is 256 except for tiny/odd test shapes)."""
    return 256 if b % 256 == 0 else b


@dataclasses.dataclass(frozen=True)
class ForwardEngine:
    """The §III/§IV layer program: input projection, L layers of
    [aggregate -> GEMM -> tail], output head. See module docstring.

    ``grid_side`` is the static 3D-grid side ``g``: it decides whether the
    fused tail may own the RMSNorm reduction (the feature dim is whole on
    every device iff g == 1). ``csr_rows`` is the local row count of the
    CSR shards (backend "csr" only).
    """

    cfg: GCNConfig
    opts: TrainOptions
    backend: str = "dense"            # "dense" | "ell" | "csr"
    grid_side: int = 1
    csr_rows: int = 0
    dp_axis: Optional[str] = "d"      # dropout-key fold; None = no DP axis

    def __post_init__(self):
        assert self.backend in BACKENDS, self.backend
        assert self.opts.overlap_impl in OVERLAPS, self.opts.overlap_impl
        assert self.opts.compress in WIRE_FORMATS, self.opts.compress
        assert self.opts.compress_schedule in COMPRESS_SCHEDULES, (
            self.opts.compress_schedule)
        if self.backend == "csr":
            assert self.csr_rows > 0, (
                "backend 'csr' needs the static local row count (csr_rows)")
        fmts = self.wire_formats
        if "int4" in fmts:
            # int4 packs two nibbles per byte along the feature axis
            assert (self.cfg.d_hidden // self.grid_side) % 2 == 0, (
                "int4 compression needs an even local feature width "
                f"(d_hidden={self.cfg.d_hidden} / g={self.grid_side})")
        if fmts[-1] == "int4":
            ncl = -(-self.cfg.num_classes // self.grid_side)
            assert ncl % 2 == 0, (
                "int4 head compression needs an even local class width "
                f"(padded classes/g = {ncl}); use int8 or pad num_classes")

    @classmethod
    def from_options(cls, cfg: GCNConfig, opts: TrainOptions, *,
                     grid_side: int,
                     backend: Optional[str] = None,
                     csr_rows: int = 0,
                     dp_axis: Optional[str] = "d") -> "ForwardEngine":
        """The standard construction: the aggregation backend follows the
        mini-batch block format (``TrainOptions.spmm_impl``) unless
        overridden (eval passes ``backend="csr"``)."""
        return cls(cfg=cfg, opts=opts, backend=backend or opts.spmm_impl,
                   grid_side=grid_side, csr_rows=csr_rows, dp_axis=dp_axis)

    # -- the compressible-collective layer (ROADMAP item 1) ------------------

    @property
    def wire_formats(self) -> Tuple[str, ...]:
        """Per-layer wire format under the compress/schedule knobs. Layer
        ``li``'s format covers its SpMM + GEMM psums and residual reshard;
        the input projection follows layer 0, the head the last layer."""
        L = self.cfg.num_layers
        return tuple(
            wire_format(self.opts.compress, self.opts.compress_schedule,
                        li, L) for li in range(L))

    @property
    def quantized(self) -> bool:
        """True when any collective site sends an int8/int4 wire — exactly
        the condition under which the engine carries error feedback."""
        return bool(self.ef_sites())

    def ef_sites(self) -> Tuple[Tuple[str, str], ...]:
        """The ordered (site_name, fmt) pairs that carry an error-feedback
        accumulator: every quantized collective site, in consumption order.
        This is the ONE definition both ``__call__`` and the TrainState
        EF-leaf construction (``fourd.make_ef``) derive from."""
        fmts = self.wire_formats
        sites = []
        if fmts[0] in QUANTIZED_FORMATS:
            sites.append(("proj", fmts[0]))
        for li, f in enumerate(fmts):
            if f not in QUANTIZED_FORMATS:
                continue
            if self.cfg.use_residual:
                sites.append((f"l{li}_reshard", f))
            sites.append((f"l{li}_spmm", f))
            sites.append((f"l{li}_gemm", f))
        if fmts[-1] in QUANTIZED_FORMATS:
            sites.append(("head", fmts[-1]))
        return tuple(sites)

    def ef_site_shapes(self, batch_local: int) -> dict:
        """Local (per-device) shape of each EF accumulator for a training
        mini-batch of ``batch_local`` rows per vertex range."""
        dloc = self.cfg.d_hidden // self.grid_side
        ncl = -(-self.cfg.num_classes // self.grid_side)
        return {site: (batch_local, ncl if site == "head" else dloc)
                for site, _ in self.ef_sites()}

    # -- the three aggregation backends (one layer's A @ H + psum) -----------

    def aggregate_local(self, blk: Any, h: jax.Array) -> jax.Array:
        """The backend-dispatched LOCAL A @ H partial product — before the
        row-axis all-reduce, so both the monolithic and the chunked-ring
        reduction paths consume the same partial."""
        if self.backend == "ell":                 # block-ELL (tiles, colidx)
            return kops.spmm_ell(blk[0], blk[1], h)
        if self.backend == "csr":                 # padded-CSR (rp, ci, val)
            rp, ci, val = blk
            return pmm3d.csr_spmm_local(rp, ci, val, h, self.csr_rows)
        return blk @ h

    def aggregate(self, blk: Any, h: jax.Array,
                  st: pmm3d.PlaneState) -> jax.Array:
        """SpMM (Eq. 5 / 27): A (p, r) @ H (r, c) -> psum r -> (p, c)."""
        return self._allreduce(self.aggregate_local(blk, h), st.row)

    def _allreduce(self, x: jax.Array, axis: str) -> jax.Array:
        """The PMM all-reduce under the overlap knob: one monolithic
        ``psum`` ("none") or the chunked ppermute ring ("ring")."""
        if self.opts.overlap_impl == "ring":
            return pmm3d.ring_psum(x, axis, bf16=self.opts.bf16_collectives)
        return pmm3d.psum_maybe_bf16(x, axis, self.opts.bf16_collectives)

    # -- the elementwise tail (Eqs. 7-10), reference or fused §V-C -----------

    def tail(self, conv: jax.Array, residual: Optional[jax.Array],
             scale: jax.Array, st: pmm3d.PlaneState,
             dropout_key: Optional[jax.Array], train: bool) -> jax.Array:
        """RMSNorm -> ReLU -> dropout -> residual on the local block.

        ``conv`` is on plane (p, r): rows over p, cols over r (rep c).
        RMSNorm reduces over r. The residual arrives already resharded to
        (p, r)."""
        cfg, opts = self.cfg, self.opts
        residual = residual if cfg.use_residual else None
        dropping = train and opts.dropout > 0 and dropout_key is not None

        if opts.fused_elementwise:
            mask = None
            if dropping:
                mask = jax.random.bernoulli(dropout_key, 1.0 - opts.dropout,
                                            conv.shape)
            if not cfg.use_rmsnorm or self.grid_side == 1:
                # feature dim whole on-device: one fused HBM round-trip
                return kops.fused_layer_tail(
                    conv, residual, scale, dropout_mask=mask,
                    dropout_rate=opts.dropout, eps=cfg.rms_eps,
                    use_rmsnorm=cfg.use_rmsnorm, use_relu=cfg.use_relu,
                    row_tile=_fused_row_tile(conv.shape[0]))
            # feature dim sharded over r: the mean-of-squares needs the FP32
            # psum (§V-B), then the fused kernel owns ReLU/dropout/residual
            h = pmm3d.parallel_rmsnorm(conv, scale, st.row, cfg.d_hidden,
                                       cfg.rms_eps)
            return kops.fused_layer_tail(
                h, residual, scale, dropout_mask=mask,
                dropout_rate=opts.dropout, eps=cfg.rms_eps,
                use_rmsnorm=False, use_relu=cfg.use_relu,
                row_tile=_fused_row_tile(conv.shape[0]))

        # reference: separate jnp ops (XLA decides the fusion)
        if cfg.use_rmsnorm:
            h = pmm3d.parallel_rmsnorm(conv, scale, st.row, cfg.d_hidden,
                                       cfg.rms_eps)
        else:
            h = conv
        if cfg.use_relu:
            h = jax.nn.relu(h)
        if dropping:
            keep = jax.random.bernoulli(dropout_key, 1.0 - opts.dropout,
                                        h.shape)
            h = jnp.where(keep, h / (1.0 - opts.dropout), 0.0)
        if residual is not None:
            h = h + residual
        return h

    # -- the layer program ---------------------------------------------------

    def __call__(self, params, adj_blocks: Sequence[Any], x_local: jax.Array,
                 *, step: jax.Array, train: bool,
                 ef: Optional[dict] = None):
        """§III forward under 3D PMM. ``adj_blocks[l % len]`` is this
        device's adjacency block for layer l's rotation plane, in the
        backend's format (dense array, ELL pair, or CSR triple).
        ``x_local`` is the local feature block on plane (x, z).

        ``ef`` carries the error-feedback accumulators for the quantized
        collective sites (``ef_sites``): when given, each quantized send
        compresses ``x + ef[site]`` and the call returns
        ``(logits, state, new_ef)`` with the fresh residuals; when ``None``
        (eval / serving / the stateless make_train_step path) quantization
        runs without feedback and the return is ``(logits, state)``.
        """
        cfg, opts = self.cfg, self.opts
        ring = opts.overlap_impl == "ring"
        st = pmm3d.initial_state()
        fmts = self.wire_formats
        collect = {} if ef is not None else None

        def take_ef(site: str, like: jax.Array) -> jax.Array:
            if ef is None:
                return jnp.zeros_like(like, dtype=jnp.float32)
            assert site in ef, f"missing EF accumulator for site '{site}'"
            return ef[site]

        def put_ef(site: str, resid: jax.Array) -> None:
            if collect is not None:
                collect[site] = resid

        def ar(x, axis, fmt, site):
            """The PMM all-reduce under the overlap + compression knobs:
            quantized ring (with EF) for int formats, otherwise the PR-7
            ring or the monolithic psum with an optionally-bf16 wire."""
            if fmt in QUANTIZED_FORMATS:
                y, r = pmm3d.compressed_psum(
                    x, axis, fmt, take_ef(site, x),
                    bwd_bf16=opts.bf16_collectives)
                put_ef(site, r)
                return y
            bf = fmt == "bf16" or opts.bf16_collectives
            if ring:
                return pmm3d.ring_psum(x, axis, bf16=bf)
            return pmm3d.psum_maybe_bf16(x, axis, bf)

        # input projection (Eq. 4): IN (x, z) @ W_in (z, y) -> psum z ->
        # F (x, y)
        with phase("proj"):
            h = ar(x_local @ params["w_in"], "z", fmts[0], "proj")

        # Fig. 8 phase scopes (obs.tracer.PHASES): jax.named_scope labels
        # land in the HLO metadata / profiler timeline.
        #
        # Software-pipelined schedule (overlap_impl="ring"): the residual
        # reshard is issued FIRST — it depends only on h, so each of its
        # ring steps is concurrency-eligible against the entire SpMM/GEMM
        # chain; the SpMM all-reduce is a chunked ring whose reduced chunks
        # are GEMMed on arrival (chunk c's matmul hides chunk c+1's
        # ppermute). The whole body is plain lax ops, so it stays
        # lax.scan-compatible inside the Trainer's chunked step loop.
        # obs.overlap_report asserts the interleaving structurally on the
        # compiled HLO.
        for li, layer in enumerate(params["layers"]):
            blk = adj_blocks[li % len(adj_blocks)]
            fmt = fmts[li]
            quant = fmt in QUANTIZED_FORMATS
            # residual must move (r, c) -> (p, r) (paper §IV-C4)
            res = None
            if cfg.use_residual:
                with phase("reshard"):
                    if quant:
                        res, r = pmm3d.reshard_compressed(
                            h, st, (st.rep, st.row), fmt,
                            take_ef(f"l{li}_reshard", h),
                            impl=opts.reshard_impl)
                        put_ef(f"l{li}_reshard", r)
                    elif fmt == "bf16":
                        # bf16 wire on the reshard gathers too (beyond the
                        # psum-only bf16_collectives knob)
                        res = pmm3d.reshard(
                            h.astype(jnp.bfloat16), st, (st.rep, st.row),
                            impl=opts.reshard_impl,
                            overlap=opts.overlap_impl).astype(h.dtype)
                    else:
                        res = pmm3d.reshard(h, st, (st.rep, st.row),
                                            impl=opts.reshard_impl,
                                            overlap=opts.overlap_impl)
            with phase("spmm"):
                part = self.aggregate_local(blk, h)
                if not ring and not quant:
                    part = ar(part, st.row, fmt, None)
            # GEMM (Eq. 6 / 28): H (p, c) @ W (c, r) -> psum c -> conv (p, r)
            with phase("gemm"):
                if quant:
                    # quantized rings are inherently chunked, so the fused
                    # reduce+GEMM pipeline applies at either overlap_impl
                    conv_r, r = pmm3d.compressed_psum_gemm(
                        part, layer["w"], st.row, fmt,
                        take_ef(f"l{li}_spmm", part),
                        bwd_bf16=opts.bf16_collectives)
                    put_ef(f"l{li}_spmm", r)
                    conv = ar(conv_r, st.col, fmt, f"l{li}_gemm")
                elif ring:
                    bf = fmt == "bf16" or opts.bf16_collectives
                    conv = ar(
                        pmm3d.ring_psum_gemm(part, layer["w"], st.row,
                                             bf16=bf),
                        st.col, fmt, None)
                else:
                    conv = ar(part @ layer["w"], st.col, fmt, None)
            dk = (_dropout_key(opts, step, li, st.rep, st.row, self.dp_axis)
                  if train and opts.dropout > 0 else None)
            with phase("tail"):
                h = self.tail(conv, res, layer["rms_scale"], st, dk, train)
            with phase("rotate"):
                st = st.rotate()

        # output head (Eq. 11): X (r, c) @ W_out (c, p) -> psum c ->
        # logits (r, p) rep c
        with phase("head"):
            logits = ar(h @ params["w_out"], st.col, fmts[-1], "head")
        if ef is not None:
            return logits, st, collect
        return logits, st
