"""The plain float32 reference of one mini-batch training step, shared by
every model kind, and the variants that stand in for the program when a
limit is set.

It imports nothing of the program and takes nothing the program made. It
recomputes, in straightforward ``jax.numpy`` at ``"highest"`` matmul
precision, what the program's timed step computes on one chip (grid side
1, one data-parallel group, the per-step stratified schedule). What every
kind shares lives here:

* the sample: ``sort(permutation(key, n)[:B])`` with the key
  ``fold_in(fold_in(PRNGKey(program_seed), step), 0)`` (one vertex range,
  data-parallel index 0);
* the sampled block: every CSR entry ``(r, c)`` with both ends sampled,
  its value times ``(n - 1) / (B - 1)`` (the stratified rescale at one
  range), self-loops unscaled;
* the dropout mask, drawn from ``PRNGKey(program_seed + 1)`` folded with
  the step, the layer and three zero mesh coordinates;
* the mean cross entropy over the batch, its gradient, and AdamW.

A kind's module (``bench/models/<kind>.py``) gives the layers' equations
as a loss ``loss_fn(params, model, graph, step, variant)`` of the raw
graph ``(indptr, indices, data, features, labels)``, built from these
helpers; ``train_step``, ``run`` and ``checked`` take that loss. Its
static sizes ``model`` carry the sample's (``Sizes``) under the same names.

Variants stand in the program's place when a limit is set:

* ``"fp8"``, the control: every matmul (``dot``), forward and backward,
  takes float8 (e4m3) operands, each tensor scaled to the format's range,
  with float32 accumulation. The configuration's float32 matmuls run at
  JAX's default precision, which on a TPU is one bfloat16 pass; fp8 is the
  next precision below it, the step a later change would be tempted by;
* ``"bf16"``: bfloat16 operands, float32 accumulation -- what the default
  precision does, kept as a reading beside the program's;
* ``"half_batch"``: the loss over the first half of the batch only.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = ("f32", "fp8", "bf16", "half_batch")
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


class Sizes(NamedTuple):
    """Static sizes of the sample. A kind's model carries these four under
    the same names, and the helpers below read only them."""

    n: int
    batch: int
    max_row_nnz: int
    program_seed: int


def sample(model: Sizes, step: jax.Array) -> jax.Array:
    """The step's sorted vertex sample, (B,) int32."""
    key = jax.random.PRNGKey(model.program_seed)
    key = jax.random.fold_in(jax.random.fold_in(key, step), 0)
    keys = jax.random.split(key, 1)
    perm = jax.vmap(lambda k: jax.random.permutation(k, model.n))(keys)[0]
    return jnp.sort(perm[:model.batch])


def sampled_entries(model: Sizes, indptr, indices, s):
    """The CSR entries of the sampled rows: (B, max_row_nnz) entry index,
    whether it is a real entry, and its column."""
    start = indptr[s]
    cnt = indptr[s + 1] - start
    lane = jnp.arange(model.max_row_nnz, dtype=jnp.int32)[None, :]
    valid = lane < cnt[:, None]
    e = jnp.where(valid, start[:, None] + lane, 0)
    return e, valid, indices[e]


def block(model: Sizes, indptr, indices, data, s) -> jax.Array:
    """The rescaled dense (B, B) sampled block."""
    b = model.batch
    pos = jnp.full((model.n,), -1, jnp.int32).at[s].set(
        jnp.arange(b, dtype=jnp.int32))
    e, valid, col = sampled_entries(model, indptr, indices, s)
    p = pos[col]
    hit = valid & (p >= 0)
    inv_p = jnp.float32((model.n - 1) / (b - 1))
    scale = jnp.where(col == s[:, None], jnp.float32(1.0), inv_p)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], p.shape)
    return jnp.zeros((b, b), jnp.float32).at[rows, jnp.where(hit, p, 0)].add(
        jnp.where(hit, data[e] * scale, 0.0))


def block_counts(model: Sizes, indptr, indices, s):
    """(sampled-block nonzeros, CSR entries of the sampled rows) of one
    sample -- the work arithmetic reads these."""
    pos = jnp.zeros((model.n,), bool).at[s].set(True)
    _, valid, col = sampled_entries(model, indptr, indices, s)
    return (jnp.sum(valid & pos[col]), jnp.sum(indptr[s + 1] - indptr[s]))


def _fp8(x):
    """``x`` rounded to e4m3 after scaling its largest magnitude to the
    format's largest, then scaled back (values in float32)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(FP8).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8_dot(a, b):
    return jnp.dot(_fp8(a), _fp8(b), preferred_element_type=jnp.float32)


def _fp8_dot_fwd(a, b):
    qa, qb = _fp8(a), _fp8(b)
    return jnp.dot(qa, qb, preferred_element_type=jnp.float32), (qa, qb)


def _fp8_dot_bwd(res, g):
    qa, qb = res
    qg = _fp8(g)
    return qg @ qb.T, qa.T @ qg


_fp8_dot.defvjp(_fp8_dot_fwd, _fp8_dot_bwd)


def dot(a, b, variant: str):
    """``a @ b`` in the variant's precision."""
    if variant == "fp8":
        return _fp8_dot(a, b)
    if variant == "bf16":
        return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return jnp.dot(a, b)


def dropout(x, model: Sizes, rate: float, step, layer: int):
    """Inverted dropout of ``x`` with the program's keep-mask for
    ``layer`` at ``step``."""
    keep_prob = 1.0 - rate
    k = jax.random.PRNGKey(model.program_seed + 1)
    for d in (step, layer, 0, 0, 0):
        k = jax.random.fold_in(k, d)
    keep = jax.random.bernoulli(k, keep_prob, x.shape)
    return jnp.where(keep, x / keep_prob, 0.0)


def cross_entropy(logits, y, variant: str):
    """Mean cross entropy of ``logits`` against labels ``y``; the
    ``half_batch`` variant takes the first half of the batch only."""
    if variant == "half_batch":
        half = logits.shape[0] // 2
        logits, y = logits[:half], y[:half]
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - tgt)


class Adam(NamedTuple):
    lr: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def train_step(params, loss_fn, model, opt: Adam, variant: str, mu, nu, t,
               graph, step):
    """One reference step: ``loss_fn``'s loss and gradient at ``step``,
    then AdamW (no weight decay) as update number ``t`` (1-based)."""
    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(loss_fn)(params, model, graph, step,
                                              variant)
    mu = jax.tree.map(lambda m, x: opt.b1 * m + (1 - opt.b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: opt.b2 * v + (1 - opt.b2) * x * x, nu, g)
    tf = jnp.asarray(t, jnp.float32)
    bc1, bc2 = 1 - opt.b1 ** tf, 1 - opt.b2 ** tf
    params = jax.tree.map(
        lambda p, m, v: p - opt.lr * ((m / bc1) / (jnp.sqrt(v / bc2)
                                                   + opt.eps)),
        params, mu, nu)
    return params, mu, nu, loss, g


class Run(NamedTuple):
    """What a run of the first steps gives the comparison: each step's
    loss, the optimizer's first moment after the last step, the change of
    the parameters over the steps, and (reference only) the first
    gradient."""

    losses: List[float]
    mu: List[np.ndarray]
    delta: List[np.ndarray]
    grad0: List[np.ndarray]


class Checked(NamedTuple):
    """The two chunks a run checks: ``held``, run from the seed's weights
    with the optimizer's second moment started at ``HELD_NU``, so that
    every step's update rounds to nothing and each step's loss and
    gradient are taken at the seed's weights; then ``trained``, the next
    chunk from the seed's fresh state, which trains as the window does."""

    held: Run
    trained: Run


# a second moment this large makes Adam's step lr * m / sqrt(v) about
# 1e-19 of the gradient: below float32's rounding of every weight
HELD_NU = 1e30


def leaves(tree) -> List[np.ndarray]:
    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def run(params, loss_fn, model, opt: Adam, graph, first_step: int,
        steps: int, variant: str = "f32", nu0: float = 0.0) -> Run:
    """``steps`` reference steps from ``params`` at global step
    ``first_step``, the second moment started at ``nu0``."""
    assert variant in VARIANTS, variant
    p = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(lambda x: jnp.full_like(x, nu0), params)
    losses, grad0 = [], None
    for t in range(steps):
        p, mu, nu, loss, g = train_step(p, loss_fn, model, opt, variant,
                                        mu, nu, t + 1, graph,
                                        jnp.int32(first_step + t))
        losses.append(float(loss))
        if grad0 is None:
            grad0 = leaves(g)
    delta = [a - b for a, b in zip(leaves(p), leaves(params))]
    return Run(losses, leaves(mu), delta, grad0)


def checked(params, loss_fn, model, opt: Adam, graph, first_step: int,
            steps: int, variant: str = "f32") -> Checked:
    """The reference's (or a variant's) two checked chunks: held at
    ``[first_step, first_step + steps)``, trained at the next ``steps``."""
    return Checked(
        held=run(params, loss_fn, model, opt, graph, first_step, steps,
                 variant, HELD_NU),
        trained=run(params, loss_fn, model, opt, graph, first_step + steps,
                    steps, variant))
