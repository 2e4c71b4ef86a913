"""Block-ELL SpMM Pallas TPU kernel — the paper's SpMM hot-spot (Eq. 5/27),
adapted to the TPU memory hierarchy (DESIGN.md §3).

GPU frameworks run GCN aggregation as CSR SpMM with per-row gathers; the TPU
MXU is a 128x128 systolic array that wants dense tiles resident in VMEM. We
therefore store the mini-batch adjacency A_S in *block-ELL* format:

  rows are grouped into blocks of ``bm``; each row-block holds a fixed
  number ``S`` of column-block slots (ELL padding), each slot being a dense
  (bm, bn) tile plus the column-block index it came from:

    tiles  : (n_rb, S, bm, bn) float32
    colidx : (n_rb, S)         int32      (padding slots point at block 0
                                           with an all-zero tile)

The kernel computes ``out = A @ X`` tile-by-tile: grid over (row-block,
feature-tile); the feature operand X stays resident in VMEM and the inner
``fori_loop`` walks the slots, dynamically slicing the X row-block named by
``colidx`` — offsets are multiples of ``bn`` so every VMEM access stays
tile-aligned for the MXU. The slot table is scalar-prefetched into SMEM,
where the loop reads each column-block id as a scalar. Empty column-blocks
are simply never touched: for a mini-batch adjacency with block-density p,
the kernel does p x the FLOPs and p x the HBM traffic of a dense matmul.

Checked in interpret mode against ``ref.spmm_ell_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend


def _spmm_ell_kernel(colidx_ref, tiles_ref, x_ref, o_ref, *, n_slots: int,
                     bn: int):
    """One (row-block i, feature-tile j) grid cell: accumulate all slots."""
    i = pl.program_id(0)
    bm = o_ref.shape[0]
    dt = o_ref.shape[1]

    def body(s, acc):
        c = colidx_ref[i, s]                            # column-block id (SMEM)
        xblk = x_ref[pl.ds(pl.multiple_of(c * bn, bn), bn), :]  # (bn, dt)
        tile = tiles_ref[0, s]                          # (bm, bn)
        return acc + jnp.dot(tile, xblk,
                             preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(
        0, n_slots, body, jnp.zeros((bm, dt), jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


def spmm_ell_pallas(tiles: jax.Array, colidx: jax.Array, x: jax.Array,
                    *, feat_tile: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """out[i*bm:(i+1)*bm] = sum_s tiles[i, s] @ x[colidx[i, s]*bn : +bn].

    ``interpret`` defaults to the backend's mode (``backend.interpret_mode``).
    """
    n_rb, n_slots, bm, bn = tiles.shape
    n_rows_x, d = x.shape
    assert n_rows_x % bn == 0, "x rows must be a multiple of bn"
    dt = min(feat_tile, d)
    assert d % dt == 0, f"feature dim {d} not a multiple of tile {dt}"

    kernel = functools.partial(_spmm_ell_kernel, n_slots=n_slots, bn=bn)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,          # the (n_rb, S) slot table
            grid=(n_rb, d // dt),
            in_specs=[
                # this row-block's dense tiles: (1, S, bm, bn) in VMEM
                pl.BlockSpec((1, n_slots, bm, bn),
                             lambda i, j, _: (i, 0, 0, 0)),
                # X: all rows resident, one feature tile per grid cell
                pl.BlockSpec((n_rows_x, dt), lambda i, j, _: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, dt), lambda i, j, _: (i, j))),
        out_shape=jax.ShapeDtypeStruct((n_rb * bm, d), x.dtype),
        interpret=backend.interpret_mode(interpret),
    )(colidx.astype(jnp.int32), tiles, x)


def dense_to_block_ell(adj: jax.Array, bm: int, bn: int, n_slots: int):
    """Convert a dense (R, C) matrix to block-ELL (host/trace-time helper).

    ``n_slots`` fixes the slot count (static shape); row-blocks with more
    nonzero column-blocks than ``n_slots`` keep the ``n_slots`` densest ones
    (tests always pass an exact bound so nothing is dropped).
    """
    r, c = adj.shape
    assert r % bm == 0 and c % bn == 0
    n_rb, n_cb = r // bm, c // bn
    blocks = adj.reshape(n_rb, bm, n_cb, bn).transpose(0, 2, 1, 3)
    # score column-blocks by L1 mass; pick top n_slots per row-block
    mass = jnp.abs(blocks).sum(axis=(2, 3))            # (n_rb, n_cb)
    _, top = jax.lax.top_k(mass, n_slots)              # (n_rb, n_slots)
    colidx = jnp.sort(top, axis=1).astype(jnp.int32)
    tiles = jnp.take_along_axis(
        blocks, colidx[:, :, None, None], axis=1)      # (n_rb, S, bm, bn)
    # zero out padding slots (blocks that are actually empty)
    slot_mass = jnp.take_along_axis(mass, colidx, axis=1)
    tiles = tiles * (slot_mass[:, :, None, None] > 0)
    colidx = jnp.where(slot_mass > 0, colidx, 0)
    return tiles, colidx


def dense_to_block_ell_ranked(adj: jax.Array, bm: int, bn: int,
                              n_slots: int):
    """Convert dense -> block-ELL with the SAME slot layout as the direct
    extraction (``sampling.extract_block_ell``): slot s of a row-block holds
    its s-th smallest nonzero column-block; overflow beyond ``n_slots``
    drops the largest column-blocks. This makes the fused-Pallas ELL path
    (dense kernel output + this conversion) bit-identical to the pure-JAX
    direct-to-ELL extraction, which the property tests assert.
    """
    r, c = adj.shape
    assert r % bm == 0 and c % bn == 0
    n_rb, n_cb = r // bm, c // bn
    blocks = adj.reshape(n_rb, bm, n_cb, bn).transpose(0, 2, 1, 3)
    nz = jnp.abs(blocks.astype(jnp.float32)).sum(axis=(2, 3)) > 0
    rank = jnp.cumsum(nz, axis=1) - 1              # ascending-cb rank
    ok = nz & (rank < n_slots)
    slot = jnp.clip(rank, 0, n_slots - 1)
    rb_idx = jnp.broadcast_to(jnp.arange(n_rb)[:, None], (n_rb, n_cb))
    tiles = jnp.zeros((n_rb, n_slots, bm, bn), adj.dtype)
    tiles = tiles.at[rb_idx, slot].add(
        jnp.where(ok[:, :, None, None], blocks, 0), mode="drop")
    colidx = jnp.zeros((n_rb, n_slots), jnp.int32)
    colidx = colidx.at[rb_idx, slot].max(
        jnp.where(ok, jnp.arange(n_cb)[None, :], 0).astype(jnp.int32),
        mode="drop")
    return tiles, colidx


def ell_to_dense(tiles: jax.Array, colidx: jax.Array,
                 n_cols: int) -> jax.Array:
    """Densify a block-ELL matrix (reference/debug helper). Padding slots
    (zero tiles at column-block 0) contribute nothing."""
    n_rb, n_slots, bm, bn = tiles.shape
    assert n_cols % bn == 0
    out = jnp.zeros((n_rb, n_cols // bn, bm, bn), jnp.float32)
    rb = jnp.broadcast_to(jnp.arange(n_rb)[:, None], colidx.shape)
    out = out.at[rb, colidx].add(tiles.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3).reshape(n_rb * bm, n_cols)


def block_density(adj: jax.Array, bm: int, bn: int) -> jax.Array:
    """Fraction of (bm, bn) blocks with any nonzero — the kernel's work
    ratio vs dense."""
    r, c = adj.shape
    blocks = adj.reshape(r // bm, bm, c // bn, bn).transpose(0, 2, 1, 3)
    nz = (jnp.abs(blocks).sum(axis=(2, 3)) > 0)
    return nz.mean()
