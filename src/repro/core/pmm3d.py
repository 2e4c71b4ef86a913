"""3D Parallel Matrix Multiplication with layer rotation (ScaleGNN §IV-C).

We adapt Agarwal et al.'s 3D PMM to the mixed sparse-dense computation of
GCN layers, exactly as the paper does. Everything in this module is written
to run *inside* ``shard_map`` over the mesh axes ``(x, y, z)`` (with the DP
axis ``d`` wrapped around it by ``repro/core/fourd.py``).

Layout algebra (DESIGN.md §4). A matrix "lives on plane (a, b)" when its
rows are block-sharded over mesh axis ``a``, its columns over ``b``, and it
is replicated over the remaining axis. One PMM step is::

    C_partial = A_local @ B_local          # pure local compute
    C = psum(C_partial, reduce_axis)       # one all-reduce

Per GCN layer with input state on plane (r, c) replicated over p:

    SpMM: adjacency block on (p, r)  ->  psum over r -> H on (p, c)
    GEMM: weight block on (c, r)     ->  psum over c -> out on (p, r)

so the layer output state is (p, r) replicated over c: the rotation
``(r, c, p) -> (p, r, c)``, period 3 — the paper's "layer rotation"
(§IV-C3), which needs three adjacency shardings and zero feature resharding
between layers. The residual connection *does* need a reshard (paper §IV-C4);
two implementations are provided (all-gather baseline, collective-permute
optimized — a §Perf hillclimb in EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.precision import (WIRE_BITS, dequantize, psum_fp32,
                                  psum_maybe_bf16, quantize)


@dataclasses.dataclass(frozen=True)
class PlaneState:
    """Tracks the (row, col, rep) mesh-axis roles of the activation tensor."""

    row: str
    col: str
    rep: str

    def rotate(self) -> "PlaneState":
        """Layer rotation: (r, c, p) -> (p, r, c)."""
        return PlaneState(row=self.rep, col=self.row, rep=self.col)

    @property
    def adj_plane(self) -> Tuple[str, str]:
        """The plane of the adjacency shard consumed at this state: (p, r)."""
        return (self.rep, self.row)

    @property
    def weight_plane(self) -> Tuple[str, str]:
        """The plane of the GEMM weight consumed at this state: (c, r)."""
        return (self.col, self.row)


def initial_state(axes: Sequence[str] = ("x", "y", "z")) -> PlaneState:
    """State of the projected features F after the input projection
    (Fig. 4 left): rows over x, cols over y, replicated over z."""
    return PlaneState(row=axes[0], col=axes[1], rep=axes[2])


def state_after_layers(num_layers: int,
                       axes: Sequence[str] = ("x", "y", "z")) -> PlaneState:
    st = initial_state(axes)
    for _ in range(num_layers):
        st = st.rotate()
    return st


# ---------------------------------------------------------------------------
# PMM primitives (run inside shard_map)
# ---------------------------------------------------------------------------

def pmm_matmul(lhs: jax.Array, rhs: jax.Array, reduce_axis: str,
               *, bf16: bool = False) -> jax.Array:
    """One 3D-PMM step: local matmul + all-reduce over ``reduce_axis``.

    Used for both the SpMM aggregation (Eq. 27; the adjacency block is dense
    on TPU — DESIGN.md §3) and the GEMM update (Eq. 28)."""
    return psum_maybe_bf16(lhs @ rhs, reduce_axis, bf16)


def csr_spmm_local(rp: jax.Array, ci: jax.Array, val: jax.Array,
                   h: jax.Array, n_rows: int) -> jax.Array:
    """Local sparse A @ H on a padded-CSR shard (used by full-graph eval,
    where densifying an (n_local, n_local) block would be wasteful).

    Padded entries carry ``val == 0`` and sentinel column ``n_local`` —
    the clipped gather contributes nothing.
    """
    e_pad = ci.shape[0]
    # row id of every nnz slot: rows = searchsorted(rp[1:], slot, 'right')
    rows = jnp.searchsorted(rp, jnp.arange(e_pad, dtype=jnp.int32),
                            side="right") - 1
    rows = jnp.clip(rows, 0, n_rows - 1)
    cols = jnp.clip(ci, 0, h.shape[0] - 1)
    contrib = val[:, None] * h[cols]                     # (e_pad, d)
    return jax.ops.segment_sum(contrib, rows, num_segments=n_rows)


def parallel_rmsnorm(x: jax.Array, scale: jax.Array, col_axis: str,
                     d_model: int, eps: float = 1e-6) -> jax.Array:
    """Eq. 29 — RMSNorm with the feature dim sharded over ``col_axis``.
    The sum-of-squares all-reduce stays FP32 (paper §V-B)."""
    sq = jnp.sum(jnp.square(x), axis=-1, keepdims=True)
    ms = psum_fp32(sq, col_axis) / d_model
    return x * jax.lax.rsqrt(ms + eps) * scale


def parallel_cross_entropy(
    logits: jax.Array,           # (b_local, c_local) on plane (row, class)
    labels: jax.Array,           # (b_local,) global class ids, -1 = ignore
    class_axis: str,             # mesh axis sharding the class dim
    row_axis: str,               # mesh axis sharding the batch rows
    n_classes: int,              # true (unpadded) class count
) -> Tuple[jax.Array, jax.Array]:
    """Distributed masked cross-entropy: logsumexp over the class-sharded
    axis (FP32, paper §V-B), target-logit fetch via a masked psum.

    Returns (sum_nll_over_all_rows, count) — both fully reduced and
    replicated within the (x, y, z) group.
    """
    c_local = logits.shape[-1]
    c0 = jax.lax.axis_index(class_axis) * c_local
    # mask padded class columns out of the softmax
    col_ids = c0 + jnp.arange(c_local)
    logits = jnp.where(col_ids[None, :] < n_classes, logits, -1e30)

    # target logit: each row's label lives on exactly one class shard
    rel = labels - c0
    in_range = (rel >= 0) & (rel < c_local) & (labels >= 0)
    safe_rel = jnp.clip(rel, 0, c_local - 1)
    tgt_local = jnp.take_along_axis(logits, safe_rel[:, None], axis=-1)[:, 0]
    tgt = psum_fp32(jnp.where(in_range, tgt_local, 0.0), class_axis)

    # distributed logsumexp (FP32); the max shift is gradient-neutral, so cut
    # the tangent BEFORE pmax (which has no differentiation rule)
    m = jax.lax.pmax(
        jax.lax.stop_gradient(jnp.max(logits, axis=-1)), class_axis)
    z = psum_fp32(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1), class_axis)
    logz = m + jnp.log(z)

    w = (labels >= 0).astype(logits.dtype)
    nll_sum = jnp.sum((logz - tgt) * w)
    cnt = jnp.sum(w)
    return psum_fp32(nll_sum, row_axis), psum_fp32(cnt, row_axis)


def parallel_argmax_correct(
    logits: jax.Array, labels: jax.Array, class_axis: str, row_axis: str,
    n_classes: int,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed accuracy numerator/denominator for evaluation."""
    c_local = logits.shape[-1]
    c0 = jax.lax.axis_index(class_axis) * c_local
    col_ids = c0 + jnp.arange(c_local)
    logits = jnp.where(col_ids[None, :] < n_classes, logits, -jnp.inf)
    local_max = jnp.max(logits, axis=-1)
    local_arg = c0 + jnp.argmax(logits, axis=-1)
    gmax = jax.lax.pmax(local_max, class_axis)
    # smallest class index attaining the max (deterministic tie-break)
    cand = jnp.where(local_max >= gmax, local_arg, n_classes + 1)
    garg = -jax.lax.pmax(-cand, class_axis)          # pmin via pmax
    valid = labels >= 0
    correct = jnp.sum((garg == labels) & valid)
    total = jnp.sum(valid)
    return (psum_fp32(correct.astype(jnp.float32), row_axis),
            psum_fp32(total.astype(jnp.float32), row_axis))


# ---------------------------------------------------------------------------
# Residual resharding (paper §IV-C4)
# ---------------------------------------------------------------------------

def reshard_gather(t: jax.Array, from_state: PlaneState,
                   to_plane: Tuple[str, str]) -> jax.Array:
    """Baseline reshard: all-gather the full matrix over the source plane,
    then slice this device's destination block. Simple and correct; moves
    g^2x more bytes than necessary (see ``reshard_permute``)."""
    full = jax.lax.all_gather(t, from_state.row, axis=0, tiled=True)
    full = jax.lax.all_gather(full, from_state.col, axis=1, tiled=True)
    br, bc = t.shape
    # destination block sizes equal source block sizes (square grid)
    i = jax.lax.axis_index(to_plane[0])
    j = jax.lax.axis_index(to_plane[1])
    return jax.lax.dynamic_slice(full, (i * br, j * bc), (br, bc))


def reshard_permute(t: jax.Array, from_state: PlaneState,
                    to_plane: Tuple[str, str]) -> jax.Array:
    """Optimized reshard for the layer-rotation pattern: the destination
    plane is a *relabeling* of mesh-axis roles, so each block moves exactly
    once — a pure permutation, g^2x less traffic than ``reshard_gather``.

    For the residual case: source (r, c) rep p, destination (p, r) rep c.
    Device (with role-coords r=i, c=j, p=k) holds source block (i, j) and
    needs source block (k, i). We realize the move as two single-axis
    ``ppermute`` steps (TPU ICI is a torus; each step is nearest-neighbor
    friendly):

      step 1 (along p): (i, j, k) <- (i, j, j')  block (i, j) -> every k
              ... not needed: block (k, i) differs from (i, j) in *values*
              of two coords, so we chain axis-wise shifts.

    Implementation: we use ``all_to_all`` over the pair of axes expressed as
    one gather over `p` (size g) followed by a dynamic slice: gather over p
    collects blocks {(i, j) for this (r=i, c=j)} — that's not what we need
    either, so the robust jax-native form is a single ``ppermute`` over the
    *flattened* (r, c, p) axis tuple with the permutation computed on the
    host. jax.lax.ppermute accepts an axis-name tuple for exactly this.
    """
    g = jax.lax.axis_size(from_state.row)
    perm = []
    # device logical coords under axis order (row, col, rep) = (i, j, k);
    # flat index = ((i * g) + j) * g + k.
    # destination device (i, j, k) needs source block (k, i), held by any
    # source device with (row=k, col=i); choose rep coord = j for a bijection
    # (src = (k, i, j)) -> cyclic coordinate rotation.
    for i in range(g):
        for j in range(g):
            for k in range(g):
                src = (k * g + i) * g + j
                dst = (i * g + j) * g + k
                perm.append((src, dst))
    return jax.lax.ppermute(
        t, (from_state.row, from_state.col, from_state.rep), perm)


def reshard(t: jax.Array, from_state: PlaneState, to_plane: Tuple[str, str],
            impl: str = "gather", overlap: str = "none") -> jax.Array:
    if (from_state.row, from_state.col) == to_plane:
        return t
    if impl == "permute":
        return reshard_permute(t, from_state, to_plane)
    if overlap == "ring":
        return reshard_gather_ring(t, from_state, to_plane)
    return reshard_gather(t, from_state, to_plane)


def reshard_gather_ring(t: jax.Array, from_state: PlaneState,
                        to_plane: Tuple[str, str]) -> jax.Array:
    """``reshard_gather`` with both all-gathers decomposed into per-chunk
    ``ppermute`` rings (``ring_all_gather``). Pure data movement — bitwise
    identical to the monolithic form at every grid shape — but each of the
    2(g-1) steps is an independently schedulable op the latency-hiding
    scheduler can hide behind unrelated compute (the SpMM/GEMM chain the
    pipelined ``ForwardEngine`` issues alongside)."""
    full = ring_all_gather(t, from_state.row, axis=0)
    full = ring_all_gather(full, from_state.col, axis=1)
    br, bc = t.shape
    i = jax.lax.axis_index(to_plane[0])
    j = jax.lax.axis_index(to_plane[1])
    return jax.lax.dynamic_slice(full, (i * br, j * bc), (br, bc))


# ---------------------------------------------------------------------------
# Chunked ring collectives (comm–compute overlap, paper §V / ROADMAP item 4)
# ---------------------------------------------------------------------------
#
# The monolithic ``psum`` / ``all_gather`` forms above compile to ONE
# collective op each, which serializes against the matmul consuming its
# result. The ring forms below decompose the same movement into per-chunk
# ``ppermute`` steps (the classic reduce-scatter + all-gather ring), so
#
#   * each step is an independently schedulable HLO op — the XLA
#     latency-hiding scheduler (``launch/xla_flags.py``) can start step
#     s+1's transfer while step s's chunk is being consumed, and
#   * ``ring_psum_chunked`` lets the caller CONSUME each reduced chunk the
#     moment it lands (``on_chunk``), so chunk c's GEMM hides chunk c+1's
#     transfer — the software pipeline ``ForwardEngine`` builds per layer.
#
# Bytes-on-wire do not inflate: an all-reduce ring moves 2(g-1)/g of the
# tensor per device (== the monolithic volume at g=2, strictly less than
# the g*N all-gather accounting convention of ``obs.hlo``).
#
# Numerics: at g <= 2 every chunk reduction is a single IEEE add, so
# ``ring_psum`` is BITWISE equal to ``jax.lax.psum`` (asserted by tier-1
# and the (2,2,2)x2 multidevice tests); at larger g the ring fixes a
# different association order than XLA's all-reduce, so equality is only
# up to float associativity. ``ring_all_gather`` is pure data movement —
# bitwise at every g.


def _chunk_rows(x: jax.Array, g: int) -> Tuple[jax.Array, int]:
    """Pad axis 0 to a multiple of g and view as (g, rows/g, ...) chunks."""
    m = x.shape[0]
    pad = (-m) % g
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x.reshape((g, (m + pad) // g) + x.shape[1:]), pad


def _ring_reduce_scatter(chunks: jax.Array, axis_name: str) -> jax.Array:
    """g-1 ppermute steps; afterwards this device's chunk (idx+1)%g of the
    (g, ...) stack holds the complete sum. Runs inside shard_map."""
    g = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % g) for i in range(g)]
    acc = chunks
    with jax.named_scope("ring_rs"):
        for s in range(g - 1):
            send_ix = (idx - s) % g
            send = jax.lax.dynamic_index_in_dim(acc, send_ix, 0,
                                                keepdims=False)
            recv = jax.lax.ppermute(send, axis_name, fwd)
            recv_ix = (idx - 1 - s) % g
            upd = jax.lax.dynamic_index_in_dim(acc, recv_ix, 0,
                                               keepdims=False) + recv
            acc = jax.lax.dynamic_update_index_in_dim(acc, upd, recv_ix, 0)
    return acc


def ring_psum(x: jax.Array, axis_name: str, *, bf16: bool = False
              ) -> jax.Array:
    """All-reduce over ``axis_name`` decomposed into a reduce-scatter +
    all-gather ring of per-chunk ``ppermute`` steps (chunked along axis 0).

    Matches ``psum_maybe_bf16`` semantics: with ``bf16`` the wire dtype is
    bfloat16 (cast once before the ring, accumulate in bf16, cast back) —
    including the lossy round-trip at g == 1, so the two impls stay
    bit-comparable at every grid shape."""
    return ring_psum_chunked(x, axis_name, lambda c: c, bf16=bf16)


def ring_psum_chunked(x: jax.Array, axis_name: str, on_chunk, *,
                      bf16: bool = False) -> jax.Array:
    """``ring_psum`` that hands each fully-reduced chunk to ``on_chunk`` the
    moment it arrives, concatenating the per-chunk results along axis 0.

    ``on_chunk`` must be row-local and row-preserving (chunk rows in, the
    same number of output rows out — e.g. ``lambda c: c @ w``; a pytree of
    such outputs is fine): then the result equals
    ``on_chunk(psum(x, axis_name))`` while chunk c's compute overlaps chunk
    c+1's ``ppermute`` (the transfers form a serial chain; each ``on_chunk``
    branches OFF the chain, so the scheduler may run it concurrently —
    ``obs.overlap_report`` asserts this structurally on the compiled HLO).
    Row-chunked matmuls are bitwise equal to the full-width form, so the
    pipelined result stays bit-identical to the monolithic path."""
    g = jax.lax.axis_size(axis_name)
    dtype = x.dtype
    wire = x.astype(jnp.bfloat16) if (bf16 and dtype == jnp.float32) else x
    if g == 1:
        return on_chunk(wire.astype(dtype))

    chunks, pad = _chunk_rows(wire, g)
    acc = _ring_reduce_scatter(chunks, axis_name)

    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % g) for i in range(g)]
    per = chunks.shape[1]

    def place(buf, y, ix):
        return jax.tree.map(
            lambda b, a: jax.lax.dynamic_update_index_in_dim(b, a, ix, 0),
            buf, y)

    # all-gather phase: circulate the complete chunks; consume on arrival
    own_ix = (idx + 1) % g
    cur = jax.lax.dynamic_index_in_dim(acc, own_ix, 0, keepdims=False)
    y = on_chunk(cur.astype(dtype))
    assert all(a.shape[0] == per for a in jax.tree.leaves(y)), (
        "on_chunk must preserve the chunk row count")
    out = place(jax.tree.map(
        lambda a: jnp.zeros((g,) + a.shape, a.dtype), y), y, own_ix)
    with jax.named_scope("ring_ag"):
        for s in range(g - 1):
            cur = jax.lax.ppermute(cur, axis_name, fwd)
            out = place(out, on_chunk(cur.astype(dtype)), (idx - s) % g)
    rows = x.shape[0]
    return jax.tree.map(
        lambda a: a.reshape((g * per,) + a.shape[2:])[:rows], out)


def ring_psum_gemm(part: jax.Array, w: jax.Array, row_axis: str, *,
                   bf16: bool = False) -> jax.Array:
    """The pipelined SpMM-reduce + GEMM: ``psum(part, row_axis) @ w`` with
    the all-reduce decomposed into the chunked ring and each reduced chunk
    GEMMed on arrival (``ring_psum_chunked``), so every all-gather-phase
    ``ppermute`` hides behind one chunk's matmul.

    Gradients go through a custom VJP that reassembles the reduced sum in
    the forward (an extra cheap buffer; bitwise equal to the monolithic
    psum result at g <= 2) and uses FULL-WIDTH contractions in the
    backward — the naive transpose would split the weight-gradient
    reduction across chunks (``sum_c chunk_c^T @ dy_c``), reassociating
    floats; with the hand-written backward both loss AND grads stay
    bit-identical to the monolithic path, and the transpose all-reduce is
    itself a ring (the backward pipeline overlaps too)."""

    @jax.custom_vjp
    def f(p_, w_):
        return ring_psum_chunked(p_, row_axis, lambda c: c @ w_,
                                 bf16=bf16)

    def f_fwd(p_, w_):
        agg, conv = ring_psum_chunked(
            p_, row_axis, lambda c: (c, c @ w_), bf16=bf16)
        return conv, (agg, w_)

    def f_bwd(res, dconv):
        agg, w_ = res
        dagg = dconv @ w_.T                      # full-width, matches mono
        dw = agg.T @ dconv                       # full-width, matches mono
        dpart = ring_psum(dagg, row_axis, bf16=bf16)  # psum transpose
        return dpart, dw

    f.defvjp(f_fwd, f_bwd)
    return f(part, w)


def ring_all_gather(x: jax.Array, axis_name: str, *, axis: int = 0
                    ) -> jax.Array:
    """Tiled all-gather over ``axis_name`` decomposed into g-1 ``ppermute``
    steps (bitwise identical to ``jax.lax.all_gather(..., tiled=True)``)."""
    g = jax.lax.axis_size(axis_name)
    if g == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % g) for i in range(g)]
    out = jnp.zeros((g,) + x.shape, x.dtype)
    out = jax.lax.dynamic_update_index_in_dim(out, x, idx, 0)
    cur = x
    with jax.named_scope("ring_ag"):
        for s in range(g - 1):
            cur = jax.lax.ppermute(cur, axis_name, fwd)
            out = jax.lax.dynamic_update_index_in_dim(
                out, cur, (idx - 1 - s) % g, 0)
    return jnp.concatenate([out[k] for k in range(g)], axis=axis)


# ---------------------------------------------------------------------------
# Compressed ring collectives (quantized wire + error feedback, ROADMAP 1)
# ---------------------------------------------------------------------------
#
# The ring forms above still move FP32 (or bf16) chunks. The ``*_q`` forms
# below send each ring chunk QUANTIZED — int8 or nibble-packed int4 with one
# FP32 scale per row (``precision.quantize``) — and dequantize on arrival,
# so the dominant wire operand in the compiled HLO is a true ``s8`` array at
# 1/4 (int8) or 1/8 (int4) of the FP32 bytes. Three properties matter:
#
# * **Replica consistency**: in the all-gather phase every device — the
#   chunk's owner included — reconstructs the chunk from the SAME (q, scale)
#   pair, so col-axis replicas of the activation stay bitwise identical and
#   downstream psums cannot diverge (DESIGN.md §4).
# * **Error feedback** (Karimireddy et al.; the gnn_compress recipe): each
#   call takes this site's EF accumulator, quantizes ``x + ef``, and returns
#   the new residual ``compensated - reconstructed`` alongside the result.
#   The collectives here are *linear*, so a residual re-injected at any
#   contributing device compensates the aggregate on the next step — the
#   quantization error becomes a one-step-delayed correction instead of a
#   bias, and end-of-run loss stays within noise of FP32 (asserted by
#   tests/test_compress.py).
# * **Straight-through gradients with a compressed transpose**: quantization
#   is piecewise-constant, so the compressed wrappers carry a custom VJP
#   whose STRUCTURE is the transpose of the uncompressed linear collective
#   (psum -> psum of the cotangent; the reshard gather -> pad + two
#   reduce-scatters, verified bitwise against ``jax.vjp`` of the FP32 path
#   in tests) — but each backward hop is sent quantized too, at the same
#   bit width as the forward site (``ring_reduce_scatter_q``). Backward
#   quantization is STATELESS (no error feedback): cotangents are fresh
#   every step, so there is no stable accumulator to re-inject into, and
#   absmax-per-row gradient quantization at int8 stays within optimizer
#   noise (asserted end-to-end by tests/test_compress.py). Without this the
#   transpose reduce-scatters dominate the train step and cap the whole-
#   program reduction near 2x; with it the step clears the >= 4x gate.


def ring_psum_q(x: jax.Array, axis_name: str, bits: int,
                ef: jax.Array, on_chunk=None
                ) -> Tuple[jax.Array, jax.Array]:
    """Quantized ring all-reduce: ``psum(x + ef, axis_name)`` with every
    reduce-scatter and all-gather hop sent as (int8-packed q, FP32 row
    scales) instead of full-width floats.

    Returns ``(result_tree, residual)``: ``on_chunk`` (default identity)
    consumes each reconstructed chunk on arrival exactly like
    ``ring_psum_chunked``; ``residual`` is the per-element quantization
    error this device injected (accumulated over its RS sends plus its
    owned-chunk broadcast), to be carried into the next step's ``ef``.

    At g == 1 there is no wire: the result is exact and the residual zero.
    """
    g = jax.lax.axis_size(axis_name)
    consume = on_chunk if on_chunk is not None else (lambda c: c)
    tc = (x + ef).astype(jnp.float32)
    if g == 1:
        return consume(tc), jnp.zeros_like(tc)

    chunks, _pad = _chunk_rows(tc, g)
    per = chunks.shape[1]
    resid = jnp.zeros_like(chunks)
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % g) for i in range(g)]

    def add_resid(buf, ix, err):
        prev = jax.lax.dynamic_index_in_dim(buf, ix, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(buf, prev + err, ix, 0)

    # reduce-scatter phase: each hop moves one quantized chunk; the local
    # quantization error stays here (in ``resid``), the receiver adds the
    # reconstruction to its partial.
    acc = chunks
    with jax.named_scope("ring_rs_q"):
        for s in range(g - 1):
            send_ix = (idx - s) % g
            v = jax.lax.dynamic_index_in_dim(acc, send_ix, 0, keepdims=False)
            q, sc = quantize(v, bits)
            resid = add_resid(resid, send_ix, v - dequantize(q, sc, bits))
            qr = jax.lax.ppermute(q, axis_name, fwd)
            scr = jax.lax.ppermute(sc, axis_name, fwd)
            recv_ix = (idx - 1 - s) % g
            upd = jax.lax.dynamic_index_in_dim(
                acc, recv_ix, 0, keepdims=False) + dequantize(qr, scr, bits)
            acc = jax.lax.dynamic_update_index_in_dim(acc, upd, recv_ix, 0)

    # all-gather phase: the owner quantizes its completed chunk ONCE and the
    # (q, scale) pair circulates verbatim; everyone — owner included —
    # reconstructs from it, so all replicas hold identical values.
    own_ix = (idx + 1) % g
    own = jax.lax.dynamic_index_in_dim(acc, own_ix, 0, keepdims=False)
    cur_q, cur_s = quantize(own, bits)
    own_rec = dequantize(cur_q, cur_s, bits)
    resid = add_resid(resid, own_ix, own - own_rec)

    def place(buf, y, ix):
        return jax.tree.map(
            lambda b, a: jax.lax.dynamic_update_index_in_dim(b, a, ix, 0),
            buf, y)

    y = consume(own_rec)
    assert all(a.shape[0] == per for a in jax.tree.leaves(y)), (
        "on_chunk must preserve the chunk row count")
    out = place(jax.tree.map(
        lambda a: jnp.zeros((g,) + a.shape, a.dtype), y), y, own_ix)
    with jax.named_scope("ring_ag_q"):
        for s in range(g - 1):
            cur_q = jax.lax.ppermute(cur_q, axis_name, fwd)
            cur_s = jax.lax.ppermute(cur_s, axis_name, fwd)
            out = place(out, consume(dequantize(cur_q, cur_s, bits)),
                        (idx - s) % g)
    rows = x.shape[0]
    result = jax.tree.map(
        lambda a: a.reshape((g * per,) + a.shape[2:])[:rows], out)
    residual = resid.reshape((g * per,) + resid.shape[2:])[:rows]
    return result, residual


def _scatter_chunks(v: jax.Array, g: int, dim: int) -> jax.Array:
    """Split ``v`` along ``dim`` (0 or 1; must divide evenly) into g chunks
    stacked on a new leading axis, keeping the feature (last) axis intact so
    per-row quantization scales stay meaningful."""
    if dim == 0:
        return v.reshape((g, v.shape[0] // g) + v.shape[1:])
    assert dim == 1 and v.ndim == 2, (g, dim, v.shape)
    return jnp.moveaxis(v.reshape(v.shape[0], g, v.shape[1] // g), 1, 0)


def ring_reduce_scatter_q(v: jax.Array, axis_name: str, bits: int, *,
                          dim: int = 0) -> jax.Array:
    """Quantized tiled reduce-scatter: ``psum(v)`` over ``axis_name`` with
    device ``idx`` keeping slice ``idx`` along ``dim`` — the transpose of a
    tiled all-gather — sent as g-1 quantized ring hops.

    Stateless (no error feedback): this runs on gradient cotangents, which
    are fresh every step. ``v.shape[dim]`` must divide evenly by g (the
    callers reduce-scatter g-block-tiled cotangents, so it always does)."""
    g = jax.lax.axis_size(axis_name)
    if g == 1:
        return v
    assert v.shape[dim] % g == 0, (v.shape, dim, g)
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % g) for i in range(g)]
    acc = _scatter_chunks(v.astype(jnp.float32), g, dim)
    # the standard RS ring shifted by -1 so device idx ends holding complete
    # chunk idx (matching jax.lax.psum_scatter's tiled convention)
    with jax.named_scope("ring_rs_q"):
        for s in range(g - 1):
            send_ix = (idx - s - 1) % g
            vch = jax.lax.dynamic_index_in_dim(acc, send_ix, 0,
                                               keepdims=False)
            q, sc = quantize(vch, bits)
            qr = jax.lax.ppermute(q, axis_name, fwd)
            scr = jax.lax.ppermute(sc, axis_name, fwd)
            recv_ix = (idx - s - 2) % g
            upd = jax.lax.dynamic_index_in_dim(
                acc, recv_ix, 0, keepdims=False) + dequantize(qr, scr, bits)
            acc = jax.lax.dynamic_update_index_in_dim(acc, upd, recv_ix, 0)
    return jax.lax.dynamic_index_in_dim(acc, idx, 0, keepdims=False)


def compressed_psum(x: jax.Array, axis_name: str, fmt: str, ef: jax.Array,
                    *, bwd_bf16: bool = False
                    ) -> Tuple[jax.Array, jax.Array]:
    """``psum(x + ef)`` over the quantized ring, with a straight-through
    custom VJP: the backward has the transpose STRUCTURE of the uncompressed
    psum (an all-reduce of the cotangent, exactly what ``jax.vjp`` of the
    linear collective emits) but runs it over the same quantized ring, so
    gradient hops ride the int8/int4 wire too (stateless — see the section
    notes). Returns ``(reduced, residual)``; the residual gets a zero
    cotangent (it is carried state, not a differentiated output)."""
    del bwd_bf16    # the quantized bwd wire subsumes the bf16 cast
    bits = WIRE_BITS[fmt]

    @jax.custom_vjp
    def f(x_, ef_):
        return ring_psum_q(x_, axis_name, bits, ef_)

    def f_fwd(x_, ef_):
        return ring_psum_q(x_, axis_name, bits, ef_), None

    def f_bwd(_, cts):
        dy, _dr = cts
        dx, _ = ring_psum_q(dy, axis_name, bits, jnp.zeros_like(dy))
        return dx, jnp.zeros_like(dy)

    f.defvjp(f_fwd, f_bwd)
    return f(x, ef)


def compressed_psum_gemm(part: jax.Array, w: jax.Array, row_axis: str,
                         fmt: str, ef: jax.Array, *, bwd_bf16: bool = False
                         ) -> Tuple[jax.Array, jax.Array]:
    """The quantized counterpart of ``ring_psum_gemm``:
    ``psum_q(part + ef, row_axis) @ w`` with each reconstructed chunk GEMMed
    on arrival, so the int8/int4 transfers hide behind per-chunk matmuls on
    the same pipelined schedule.

    The custom VJP differentiates the actual forward w.r.t. ``w`` (full-
    width ``agg.T @ dconv`` against the reconstructed aggregate — the true
    gradient of the compressed program) and straight-through w.r.t.
    ``part`` (the psum transpose, itself sent over the quantized ring —
    stateless, see the section notes). Returns ``(conv, residual)``."""
    del bwd_bf16    # the quantized bwd wire subsumes the bf16 cast
    bits = WIRE_BITS[fmt]

    @jax.custom_vjp
    def f(p_, w_, e_):
        (_agg, conv), r = ring_psum_q(
            p_, row_axis, bits, e_, on_chunk=lambda c: (c, c @ w_))
        return conv, r

    def f_fwd(p_, w_, e_):
        (agg, conv), r = ring_psum_q(
            p_, row_axis, bits, e_, on_chunk=lambda c: (c, c @ w_))
        return (conv, r), (agg, w_)

    def f_bwd(res, cts):
        dconv, _dr = cts
        agg, w_ = res
        dagg = dconv @ w_.T
        dw = agg.T @ dconv
        dpart, _ = ring_psum_q(dagg, row_axis, bits, jnp.zeros_like(dagg))
        return dpart, dw, jnp.zeros_like(dagg)

    f.defvjp(f_fwd, f_bwd)
    return f(part, w, ef)


def reshard_compressed(t: jax.Array, from_state: PlaneState,
                       to_plane: Tuple[str, str], fmt: str, ef: jax.Array,
                       impl: str = "gather"
                       ) -> Tuple[jax.Array, jax.Array]:
    """The residual reshard (§IV-C4) with a quantized wire: ``t + ef`` is
    quantized ONCE, the (q, scales) pair moves through the ring all-gathers
    (impl "gather") or the single block permutation (impl "permute"), and
    every device dequantizes the blocks it consumes. The residual is the
    local reconstruction error — re-injected next step, it compensates the
    block wherever it landed (the reshard is a permutation of blocks).

    Straight-through custom VJP: the backward has the transpose STRUCTURE
    of the uncompressed reshard — inverse block permutation (impl
    "permute") or pad + two tiled reduce-scatters (impl "gather"; verified
    bitwise against ``jax.vjp`` of the FP32 gather in tests) — with every
    cross-device hop sent quantized at the same bit width (stateless, see
    the section notes). Returns ``(resharded, residual)``."""
    bits = WIRE_BITS[fmt]
    if (from_state.row, from_state.col) == to_plane:
        return t, jnp.zeros_like(t)
    g = jax.lax.axis_size(from_state.row)
    if g == 1:
        # every axis is singleton: the reshard is the identity and there is
        # no wire — quantizing here would manufacture error from nothing
        return t, jnp.zeros_like(t)
    if bits == 4:
        assert t.shape[-1] % 2 == 0, (
            f"int4 reshard needs an even local column count, got {t.shape}")
    br, bc = t.shape

    def _move(t_, e_):
        tc = (t_ + e_).astype(jnp.float32)
        q, sc = quantize(tc, bits)
        resid = tc - dequantize(q, sc, bits)
        if impl == "permute":
            axes = (from_state.row, from_state.col, from_state.rep)
            perm = []
            for i in range(g):
                for j in range(g):
                    for k in range(g):
                        perm.append(((k * g + i) * g + j,
                                     (i * g + j) * g + k))
            qd = jax.lax.ppermute(q, axes, perm)
            sd = jax.lax.ppermute(sc, axes, perm)
            return dequantize(qd, sd, bits), resid
        # gather: circulate the packed q and the scales through the same
        # two ring all-gathers the FP32 path uses, then dequantize each
        # (br, bc) block against its own scale column and slice ours out.
        qf = ring_all_gather(q, from_state.row, axis=0)
        qf = ring_all_gather(qf, from_state.col, axis=1)
        sf = ring_all_gather(sc, from_state.row, axis=0)
        sf = ring_all_gather(sf, from_state.col, axis=1)   # (g*br, g)
        blocks = qf.reshape(g * br, g, -1)                 # (rows, g, pc)
        vals = dequantize(blocks, sf[:, :, None], bits)    # (rows, g, bc)
        full = vals.reshape(g * br, g * bc)
        i = jax.lax.axis_index(to_plane[0])
        j = jax.lax.axis_index(to_plane[1])
        return jax.lax.dynamic_slice(full, (i * br, j * bc), (br, bc)), resid

    @jax.custom_vjp
    def f(t_, e_):
        return _move(t_, e_)

    def f_fwd(t_, e_):
        return _move(t_, e_), None

    def f_bwd(_, cts):
        dout, _dr = cts
        if impl == "permute":
            # transpose of a cross-device block permutation = the inverse
            # permutation; move the (q, scales) pair instead of floats
            axes = (from_state.row, from_state.col, from_state.rep)
            inv = []
            for i in range(g):
                for j in range(g):
                    for k in range(g):
                        inv.append(((i * g + j) * g + k,
                                    (k * g + i) * g + j))
            dq, ds = quantize(dout.astype(jnp.float32), bits)
            dqd = jax.lax.ppermute(dq, axes, inv)
            dsd = jax.lax.ppermute(ds, axes, inv)
            dt = dequantize(dqd, dsd, bits)
        else:
            # transpose of AG(row) -> AG(col) -> slice(i,j): pad the
            # cotangent into its block position, then tiled reduce-scatter
            # back over col then row — each hop quantized
            i = jax.lax.axis_index(to_plane[0])
            j = jax.lax.axis_index(to_plane[1])
            d_full = jnp.zeros((g * br, g * bc), jnp.float32)
            d_full = jax.lax.dynamic_update_slice(
                d_full, dout.astype(jnp.float32), (i * br, j * bc))
            d1 = ring_reduce_scatter_q(d_full, from_state.col, bits, dim=1)
            dt = ring_reduce_scatter_q(d1, from_state.row, bits, dim=0)
        return dt.astype(dout.dtype), jnp.zeros_like(dout)

    f.defvjp(f_fwd, f_bwd)
    return f(t, ef)
