"""Overlapping sampling with training (ScaleGNN §V-A).

The paper runs sampling for step t+1 on a dedicated CUDA stream concurrently
with the fwd/bwd of step t, synchronized by an event, and carries the overlap
across epoch boundaries. TPUs have no user streams — the jax-native
equivalent (DESIGN.md §3) is to make the *next* step's mini-batch a
data-independent computation inside the *current* jitted step, carried in the
loop state. XLA's latency-hiding scheduler can then interleave the sampling
gathers with the backward pass's all-reduces: sampling leaves the critical
path, which is the paper's goal.

Concretely the carried state is ``(params, opt_state, minibatch_t)`` — the
batch a ``core.minibatch.Minibatch`` pytree — and one step computes::

    grads   = grad(loss)(params, minibatch_t)         # consume batch t
    batch'  = builder.build_local(step + 1)           # produce batch t+1
    params' = optimizer(params, psum_d(grads))

The two top lines share no data, so the compiler is free to overlap them.
Tests assert the prefetched pipeline computes the identical loss sequence as
the unpipelined step (shifted by the warm-up batch).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import fourd as fourd_ef
from repro.core import pmm3d
from repro.core.fourd import FourDPlan
from repro.core.minibatch import BlockFormat, GraphShards, Minibatch
from repro.obs.tracer import phase


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PrefetchState:
    params: Any
    opt_state: Any
    minibatch: Minibatch     # batch t, carried into step t (global arrays)


def _minibatch_specs(plan: FourDPlan) -> Minibatch:
    """Sharding specs of the carried mini-batch (device-local blocks live in
    stacked global arrays), as a ``Minibatch``-shaped spec pytree.

    Per-leaf: a dense plane is one (1, b, b) array; a block-ELL plane is a
    (tiles, colidx) pair — (1, n_rb, n_slots, bm, bn) and (1, n_rb,
    n_slots). Both carry the same ``P('d', plane_row, plane_col)`` spec:
    the carried arrays are pure round-trip carriers between the sampling
    shard_map's out_specs and the loss shard_map's in_specs, so any spec
    that names every axis the leaf varies over (d and the two plane axes —
    blocks are replicated over the third) reassembles identically,
    regardless of which tensor dims the plane axes land on."""
    st = pmm3d.initial_state()
    ell = plan.builder.fmt is BlockFormat.ELL
    adj_specs = []
    for _ in range(min(3, plan.cfg.num_layers)):
        pr, pc = st.adj_plane
        # leading 'd': DP groups sample independent mini-batches (§IV-A),
        # so the blocks are NOT replicated across d
        sp = P("d", pr, pc)
        adj_specs.append((sp, sp) if ell else sp)
        st = st.rotate()
    r_f = pmm3d.state_after_layers(plan.cfg.num_layers).row
    return Minibatch(adj=tuple(adj_specs), feats=P("d", "x", "z"),
                     labels=P("d", r_f))


def make_pipeline_fns(plan: FourDPlan):
    """The two un-jitted halves of the §V-A pipeline, shared by the legacy
    per-step ``make_prefetched_train_step`` and the scan-chunked runtime
    (``repro.train``), which folds the prefetch carry into its scan state:

    * ``sample_fn(graph, step, epoch=None) -> Minibatch`` — materialize
      batch ``step`` (the sharded sampling shard_map; warm-up and in-step
      prefetch). ``epoch`` defaults to the epoch the step falls in, so the
      §V-A carry survives epoch boundaries inside the scan: prefetching
      batch ``t+1`` from the last step of an epoch derives the NEXT epoch's
      permutation — the paper's carry-across-epochs behavior.
    * ``loss_fn(params, minibatch, step, ef=None) -> (G_d,)`` — consume a
      carried batch through the ONE ``ForwardEngine`` (``core/forward.py``).
      When the plan compresses collectives, pass the error-feedback pytree
      (``fourd.make_ef``) and receive ``(losses, new_ef)`` — same contract
      as ``fourd.make_loss_fn``.
    """
    cfg, builder = plan.cfg, plan.builder
    mesh = plan.mesh
    ds = plan.data_specs
    mb_specs = _minibatch_specs(plan)
    engine = plan.engine()
    e_specs = fourd_ef.ef_specs(plan)

    def local_sample(shards: GraphShards, feats, labels, step,
                     epoch, aux) -> Minibatch:
        mb = builder.build_local(shards.squeeze_blocks(), feats, labels,
                                 step, cfg.num_layers, epoch=epoch, aux=aux)
        # re-add leading dims so out_specs can scatter them on the mesh
        return mb.add_leading()

    sample_sharded = jax.shard_map(
        local_sample, mesh=mesh,
        in_specs=(plan.shards_specs, ds["features"], plan.label_sp, P(),
                  P(), plan.aux_specs),
        out_specs=mb_specs, check_vma=False)

    def sample_fn(graph, step, epoch=None) -> Minibatch:
        if epoch is None:
            epoch = builder.epoch_of(step)
        # "sample" is a Fig. 8 phase: wall time is real here when called
        # eagerly (warm-up), trace time when called under jit (prefetch).
        with phase("sample"):
            return sample_sharded(GraphShards.from_graph(graph),
                                  graph["features"], graph["labels"], step,
                                  epoch, graph.get("walk", {}))

    def local_loss(params, mb: Minibatch, step, ef=None):
        mb = mb.strip_leading()
        if ef is None:
            logits, st = engine(params, mb.adj, mb.feats, step=step,
                                train=True)
            new_ef = None
        else:
            logits, st, new_ef = engine(
                params, mb.adj, mb.feats, step=step, train=True,
                ef=fourd_ef._ef_squeeze(ef))
        nll_sum, cnt = pmm3d.parallel_cross_entropy(
            logits, mb.labels, class_axis=st.rep, row_axis=st.row,
            n_classes=cfg.num_classes)
        loss = (nll_sum / jnp.maximum(cnt, 1.0))[None]
        if ef is None:
            return loss
        return loss, fourd_ef._ef_expand(new_ef)

    loss_sharded = jax.shard_map(
        local_loss, mesh=mesh,
        in_specs=(plan.p_specs, mb_specs, P()),
        out_specs=P("d"), check_vma=False)
    loss_sharded_ef = None
    if e_specs is not None:
        loss_sharded_ef = jax.shard_map(
            local_loss, mesh=mesh,
            in_specs=(plan.p_specs, mb_specs, P(), e_specs),
            out_specs=(P("d"), e_specs), check_vma=False)

    def loss_fn(params, minibatch, step, ef=None):
        if ef is None:
            return loss_sharded(params, minibatch, step)
        assert loss_sharded_ef is not None, (
            "loss_fn got an EF pytree but the plan's TrainOptions.compress "
            "sends no quantized wire")
        return loss_sharded_ef(params, minibatch, step, ef)
    return sample_fn, loss_fn


def make_prefetched_train_step(plan: FourDPlan, optimizer):
    """Build (sample_fn, step_fn):

    * ``sample_fn(graph, step)`` materializes mini-batch ``step`` (used once
      for warm-up).
    * ``step_fn(state, graph, step)`` consumes the carried batch, prefetches
      batch ``step + 1`` inside the same XLA program, and applies the
      optimizer. Returns (state', loss).
    """
    sample_fn, loss_fn = make_pipeline_fns(plan)

    @jax.jit
    def step_fn(state: PrefetchState, graph, step):
        def mean_loss(p):
            return loss_fn(p, state.minibatch, step).mean()
        loss, grads = jax.value_and_grad(mean_loss)(state.params)
        # prefetch: data-independent of the grads above -> overlappable
        next_mb = sample_fn(graph, step + 1)
        params, opt_state = optimizer.update(state.params, grads,
                                             state.opt_state)
        return PrefetchState(params, opt_state, next_mb), loss

    return sample_fn, step_fn
