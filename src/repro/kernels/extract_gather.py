"""Pallas fused mini-batch extraction — Alg. 2 phases 2-4 in one kernel.

The pure-JAX extraction (``repro.core.sampling``) materializes the sampled
edges as three ``(e_cap,)`` COO streams (row owner, column position, value)
in HBM, then scatter-adds them into the dense block. This kernel fuses the
whole pipeline so the intermediates never leave the core:

  grid cell = ``ROWS`` sampled rows. Per row the kernel
    1. takes the row's CSR extent ``rp[row] .. rp[row+1]`` from SMEM and
       copies the edge window that holds it from HBM by DMA  (phase 2),
    2. walks its edges, matching each column id against the *whole* sorted
       sampled-column vector with one VPU compare — the equality mask is
       simultaneously the membership filter AND the scatter one-hot, so the
       binary search and the scatter of the reference implementation
       collapse into a single vectorized op                   (phase 3),
    3. applies the per-column rescale (with the self-loop exemption of
       Eq. 24) and accumulates into the output row            (phase 4).

Memory placement. The sampled row ids, their CSR extents and the diagonal
flag are scalar-prefetched into SMEM; the CSR column ids and values stay in
HBM (``memory_space=pl.ANY``) — a paper-scale shard does not fit VMEM — and
each row's edges arrive by DMA into SMEM, where the edge loop reads them as
scalars. HBM slices of a 1-D array must start and end on its tile of
``DMA_TILE`` elements, so the DMA copies the aligned window of
``window = DMA_TILE * ceil((DMA_TILE - 1 + max_deg) / DMA_TILE)`` elements
that covers the row; CSR arrays whose length is not a multiple of
``DMA_TILE`` are zero-padded first (a copy — partitioned graphs that want
to skip it size ``e_pad`` to a multiple).

The ``(b_r, b_c)`` block is written exactly once; no COO triples round-trip
through HBM. ``max_deg`` is the static per-row edge bound (the analogue of
``e_cap``): callers pass the partition's ``max_block_row_nnz`` so nothing is
truncated, exactly like sizing ``e_cap = b_r * max_block_row_nnz``.

Rescale semantics match ``sampling.extract_dense_block`` bit-for-bit on
graphs without duplicate edges (one contribution per output cell, so there
is no accumulation-order ambiguity): ``col_scale`` is the per-column
off-diagonal factor, ``diag`` (a traced or static bool) enables the
self-loop exemption where the row id equals the column id.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

ROWS = 8            # sampled rows per grid cell: one f32 sublane tile
DMA_TILE = 1024     # alignment of a DMA slice of a 1-D int32/f32 HBM array


def _extract_kernel(rows_ref, start_ref, cnt_ref, diag_ref,
                    cols_ref, cscale_ref, ci_hbm, val_hbm, o_ref,
                    ci_win, val_win, sem, *, max_deg: int, window: int,
                    e_len: int):
    """``ROWS`` sampled rows per grid cell: DMA -> match -> rescale -> emit."""
    base = pl.program_id(0) * ROWS
    cvec = cols_ref[...]                         # (1, b_c) sorted sampled cols
    # phase 2: start every row's edge-window DMA before the first wait
    copies, offsets = [], []
    for r in range(ROWS):
        start = start_ref[base + r]
        w0 = pl.multiple_of(
            jnp.minimum(start // DMA_TILE * DMA_TILE, e_len - window),
            DMA_TILE)
        offsets.append(start - w0)
        dst = pl.ds(r * window, window)
        cp = (pltpu.make_async_copy(ci_hbm.at[pl.ds(w0, window)],
                                    ci_win.at[dst], sem.at[0, r]),
              pltpu.make_async_copy(val_hbm.at[pl.ds(w0, window)],
                                    val_win.at[dst], sem.at[1, r]))
        for c in cp:
            c.start()
        copies.append(cp)

    for r in range(ROWS):
        for c in copies[r]:
            c.wait()
        row = rows_ref[base + r]                 # this row's local vertex id
        cnt = cnt_ref[base + r]
        first = r * window + offsets[r]
        # self-loops stay unrescaled (Eq. 24): lane is diagonal iff the
        # sampled column equals this row's vertex id and the row/col strata
        # coincide
        is_diag = (diag_ref[0] != 0) & (cvec == row)
        lane_scale = jnp.where(is_diag, 1.0, cscale_ref[...])

        def body(e, acc):
            valid = e < cnt
            idx = jnp.where(valid, first + e, 0)
            col = ci_win[idx]
            v = val_win[idx]
            # membership + compact position + scatter in ONE compare: cols
            # are sorted distinct, so at most one lane matches
            hit = valid & (cvec == col)
            return acc + jnp.where(hit, v, 0.0)

        acc = jax.lax.fori_loop(
            0, max_deg, body, jnp.zeros(cvec.shape, jnp.float32))
        o_ref[pl.ds(r, 1), :] = acc * lane_scale


def extract_dense_fused(
    rp: jax.Array,            # (n_local + 1,) int32 local row pointer
    ci: jax.Array,            # (e_pad,) int32 local col ids
    val: jax.Array,           # (e_pad,) float32 edge values
    rows_local: jax.Array,    # (b_r,) sorted local sampled row ids
    cols_local: jax.Array,    # (b_c,) sorted distinct local sampled col ids
    *,
    col_scale: jax.Array | float,   # scalar or (b_c,) off-diagonal rescale
    diag: jax.Array | bool,         # row/col vertex sets coincide
    max_deg: int,                   # static per-row nnz bound
    dtype=jnp.float32,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused replacement for ``sampling.extract_dense_block``: returns the
    dense rescaled ``(b_r, b_c)`` sampled block straight from padded CSR.
    ``interpret`` defaults to the backend's mode
    (``backend.interpret_mode``)."""
    b_r, b_c = rows_local.shape[0], cols_local.shape[0]
    if ci.shape[0] == 0 or max_deg == 0:         # empty graph shard
        return jnp.zeros((b_r, b_c), dtype=dtype)

    # pad the row list to whole grid cells; padded rows have no edges
    n_rows = -(-b_r // ROWS) * ROWS
    rows = jnp.pad(rows_local.astype(jnp.int32), (0, n_rows - b_r))
    rp = rp.astype(jnp.int32)
    start = rp[rows]
    cnt = jnp.where(jnp.arange(n_rows) < b_r, rp[rows + 1] - start, 0)

    window = DMA_TILE * -(-(DMA_TILE - 1 + max_deg) // DMA_TILE)
    e_len = max(window, -(-ci.shape[0] // DMA_TILE) * DMA_TILE)
    ci = jnp.pad(ci.astype(jnp.int32), (0, e_len - ci.shape[0]))
    val = jnp.pad(val.astype(jnp.float32), (0, e_len - val.shape[0]))

    cscale = jnp.broadcast_to(
        jnp.asarray(col_scale, jnp.float32), (b_c,)).reshape(1, b_c)
    diag1 = jnp.asarray(diag, jnp.int32).reshape(1)
    cols2 = cols_local.astype(jnp.int32).reshape(1, b_c)

    kernel = functools.partial(_extract_kernel, max_deg=max_deg,
                               window=window, e_len=e_len)
    whole = pl.BlockSpec((1, b_c), lambda i, *_: (0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,          # rows, start, cnt, diag -> SMEM
            grid=(n_rows // ROWS,),
            in_specs=[whole, whole, hbm, hbm],   # cols, rescale, ci, val
            out_specs=pl.BlockSpec((ROWS, b_c), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.SMEM((ROWS * window,), jnp.int32),
                            pltpu.SMEM((ROWS * window,), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, ROWS))]),
        out_shape=jax.ShapeDtypeStruct((n_rows, b_c), jnp.float32),
        interpret=backend.interpret_mode(interpret),
    )(rows, start, cnt, diag1, cols2, cscale, ci, val)
    return out[:b_r].astype(dtype)
