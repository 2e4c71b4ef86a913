"""Baseline sampling algorithms the paper compares against (Table I, Fig. 6).

* GraphSAINT node sampler (Zeng et al. 2019) — degree-proportional node
  sampling with the standard independent-inclusion normalization of the
  aggregator and the loss.
* GraphSAGE neighbor sampler (Hamilton et al. 2017) — node-wise fan-out
  sampling with mean aggregation; the sampler used by DistDGL / MassiveGNN /
  SALIENT++.

Both are implemented as jit-able, static-shape JAX functions over the same
padded-CSR graph representation as the paper's sampler, so the Table I /
Fig. 6 comparisons isolate the *sampling algorithm* (identical model,
optimizer, hardware). DESIGN.md §9.5 records that the baseline *systems*
are represented by their algorithms, not their codebases.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.minibatch import BlockFormat, GraphShards, MinibatchBuilder
from repro.core.sampling import SampleConfig


# ---------------------------------------------------------------------------
# GraphSAINT node sampler
# ---------------------------------------------------------------------------

class SaintBatch(NamedTuple):
    adj: jax.Array         # (B, B) dense normalized induced adjacency
    feats: jax.Array       # (B, d_in)
    labels: jax.Array      # (B,)
    loss_weights: jax.Array  # (B,) 1/(B * p_v) loss normalization
    vertex_ids: jax.Array


def saint_node_sample(
    key: jax.Array,
    rp: jax.Array, ci: jax.Array, val: jax.Array,
    features: jax.Array, labels: jax.Array,
    degrees: jax.Array,       # (N,) float32 degree (sampling distribution)
    n: int, batch: int, e_cap: int,
    builder: Optional[MinibatchBuilder] = None,
) -> SaintBatch:
    """GraphSAINT-node: sample B vertices with p_v ∝ deg(v) (without
    replacement via Gumbel top-k), build the induced subgraph, and normalize:

      aggregator: a_uv / q_uv with q_uv = 1 - (1-p̃_u)(1-p̃_v) ≈ p̃_u + p̃_v,
                  p̃_v = min(1, B * p_v)  (independent-inclusion estimate)
      loss:       weight 1/(B * p_v) per sampled vertex.

    The induced subgraph goes through the shared batch-construction layer
    (``core.minibatch``): pass a ``builder`` to select the extraction
    backend (e.g. the fused Pallas kernel); SAINT's own normalization is
    applied on top of an unrescaled block (col_scale = 1).
    """
    if builder is None:
        builder = MinibatchBuilder(
            scfg=SampleConfig(n_pad=n, g=1, batch=batch, e_cap=e_cap),
            mode="exact")
    logp = jnp.log(jnp.maximum(degrees, 1e-9))
    gumbel = -jnp.log(-jnp.log(
        jax.random.uniform(key, (n,), minval=1e-9, maxval=1.0)))
    s = jnp.sort(jax.lax.top_k(logp + gumbel, batch)[1])

    p_v = degrees / jnp.maximum(degrees.sum(), 1e-9)
    p_incl = jnp.minimum(1.0, batch * p_v)                    # (N,)

    adj = builder.extract_block(rp, ci, val, s, s, col_scale=1.0,
                                diag=True, e_cap=e_cap,
                                fmt=BlockFormat.DENSE, dtype=jnp.float32)
    pu = p_incl[s]                                            # (B,)
    q = jnp.clip(pu[:, None] + pu[None, :] - pu[:, None] * pu[None, :],
                 1e-9, 1.0)
    eye = jnp.eye(batch, dtype=adj.dtype)
    adj = adj * ((1.0 - eye) / q + eye)                       # keep self-loops

    w = 1.0 / jnp.maximum(batch * p_v[s], 1e-9)
    w = w / jnp.maximum(w.sum(), 1e-9) * batch                # normalize mean
    return SaintBatch(adj=adj, feats=features[s], labels=labels[s],
                      loss_weights=w, vertex_ids=s)


# ---------------------------------------------------------------------------
# GraphSAGE neighbor sampler
# ---------------------------------------------------------------------------

class SageBatch(NamedTuple):
    """Layered neighbor-sampled batch for an L-layer SAGE network.

    ``frontiers[l]`` are the global vertex ids needed at layer input l
    (frontiers[0] is the innermost = target batch). Each frontier *contains
    its inner frontier as a prefix* (self vertices), so previous-layer self
    embeddings are always available: ``frontiers[l+1] = concat(frontiers[l],
    sampled_neighbors_of_frontiers[l])``. ``neighbors[l]`` maps each
    frontier-l vertex to ``fanout_l`` sampled neighbor *positions within
    frontier l+1* (already offset past the self prefix).
    """

    frontiers: Tuple[jax.Array, ...]     # sizes B, B*(1+k1), ...
    neighbors: Tuple[jax.Array, ...]     # [(B, k1), (B*(1+k1), k2), ...]
    feats: jax.Array                     # features of outermost frontier
    labels: jax.Array                    # labels of target batch


def _sample_row_neighbors(key, rp, ci, row, fanout, n_local):
    """Sample `fanout` neighbors of `row` with replacement (self if isolated)."""
    deg = rp[row + 1] - rp[row]
    r = jax.random.randint(key, (fanout,), 0, jnp.maximum(deg, 1))
    nbr = ci[rp[row] + jnp.where(deg > 0, r, 0)]
    return jnp.where(deg > 0, nbr, row)


def sage_sample(
    key: jax.Array,
    rp: jax.Array, ci: jax.Array,
    features: jax.Array, labels: jax.Array,
    n: int, batch: int, fanouts: Sequence[int],
) -> SageBatch:
    """Node-wise neighbor sampling with fan-outs ``fanouts`` (innermost
    first), exhibiting the paper's 'neighborhood explosion': the outermost
    frontier has B * prod(fanouts) vertices."""
    key, sk = jax.random.split(key)
    targets = jnp.sort(jax.random.permutation(sk, n)[:batch])

    frontiers = [targets]
    neighbor_maps = []
    cur = targets
    for li, k in enumerate(fanouts):
        key, sk = jax.random.split(key)
        keys = jax.random.split(sk, cur.shape[0])
        nbrs = jax.vmap(
            lambda kk, row: _sample_row_neighbors(kk, rp, ci, row, k, n)
        )(keys, cur)                                   # (|cur|, k) global ids
        flat = nbrs.reshape(-1)
        # next frontier = self prefix + sampled neighbors; neighbor positions
        # are offset past the prefix (duplicates fine for mean aggregation)
        offset = cur.shape[0]
        neighbor_maps.append(
            offset + jnp.arange(flat.shape[0], dtype=jnp.int32)
            .reshape(nbrs.shape))
        nxt = jnp.concatenate([cur, flat])
        frontiers.append(nxt)
        cur = nxt
    return SageBatch(
        frontiers=tuple(frontiers),
        neighbors=tuple(neighbor_maps),
        feats=features[frontiers[-1]],
        labels=labels[targets],
    )


def sage_aggregate(h_next: jax.Array, neighbor_map: jax.Array) -> jax.Array:
    """GCN-style mean over {self} ∪ sampled neighbors:
    (|F_{l+1}|, d) -> (|F_l|, d). The self embedding is the prefix of
    ``h_next`` (see SageBatch invariant)."""
    n_inner, k = neighbor_map.shape
    h_self = h_next[:n_inner]                        # (|F_l|, d)
    nbr_mean = h_next[neighbor_map].mean(axis=1)     # (|F_l|, d)
    return (h_self + k * nbr_mean) / (k + 1.0)


# ---------------------------------------------------------------------------
# Full-batch GCN (the no-sampling baseline)
# ---------------------------------------------------------------------------
#
# The classic full-graph training regime every sampling paper compares
# against: one forward/backward over ALL vertices per optimizer step. It
# runs through the SAME ``ForwardEngine`` as the paper's path — the "csr"
# aggregation backend over the partitioner's adjacency shards, exactly the
# program ``fourd.make_eval_step`` uses for full-graph evaluation — so the
# fig5/fig8 comparison isolates mini-batching itself (identical kernels,
# collectives, and precision knobs on both sides).

def make_fullbatch_gcn_loss(plan, *, train: bool = True):
    """loss(params, graph, step) -> (G_d,) per-group losses for one
    full-graph GCN step on a ``fourd.FourDPlan``.

    No sampling, no extraction: the engine consumes the resident CSR
    adjacency shards directly (``backend="csr"``). ``jax.grad`` composes
    from outside exactly as with ``fourd.make_loss_fn``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.core import pmm3d

    cfg = plan.cfg
    engine = plan.engine(backend="csr", csr_rows=plan.scfg.n_local)

    def local_loss(params, shards, feats, labels, step):
        shards = shards.squeeze_blocks()
        planes = tuple(shards.plane(li)
                       for li in range(min(3, cfg.num_layers)))
        logits, st = engine(params, planes, feats, step=step, train=train)
        nll_sum, cnt = pmm3d.parallel_cross_entropy(
            logits, labels, class_axis=st.rep, row_axis=st.row,
            n_classes=cfg.num_classes)
        return (nll_sum / jnp.maximum(cnt, 1.0))[None]

    in_specs = (
        plan.p_specs,
        plan.shards_specs,
        plan.data_specs["features"], plan.label_sp, P(),
    )
    sharded = jax.shard_map(local_loss, mesh=plan.mesh, in_specs=in_specs,
                            out_specs=P("d"), check_vma=False)

    def loss_fn(params, graph, step):
        return sharded(params, GraphShards.from_graph(graph),
                       graph["features"], graph["labels"], step)
    return loss_fn


def make_fullbatch_gcn_step(plan, optimizer):
    """(params, opt_state, graph, step) -> (params, opt_state, loss):
    the jitted full-batch training step (mirrors ``fourd.make_train_step``
    with the full-graph loss)."""
    loss_fn = make_fullbatch_gcn_loss(plan, train=True)

    def mean_loss(params, graph, step):
        return loss_fn(params, graph, step).mean()

    @jax.jit
    def train_step(params, opt_state, graph, step):
        loss, grads = jax.value_and_grad(mean_loss)(params, graph, step)
        params, opt_state = optimizer.update(params, grads, opt_state)
        return params, opt_state, loss

    return train_step
