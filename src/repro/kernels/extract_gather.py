"""Pallas fused mini-batch extraction — Alg. 2 phases 2-4 in one kernel.

The pure-JAX extraction (``repro.core.sampling``) materializes the sampled
edges as three ``(e_cap,)`` COO streams (row owner, column position, value)
in HBM, then scatter-adds them into the dense block. This kernel fuses the
whole pipeline so the intermediates never leave the core:

  grid cell = ``ROWS`` sampled rows, one per sublane of an f32 vreg. The
  kernel
    1. takes each row's CSR extent ``rp[row] .. rp[row+1]`` from SMEM and
       copies the edge window that holds it from HBM by DMA  (phase 2),
    2. walks the cell's rows together, for as many steps as the cell's
       longest row has edges (a trip count read at run time, not the static
       bound): step e gathers edge e of every row into a ``(ROWS, 1)``
       column-id and value vector and compares the column ids against the
       *whole* sorted sampled-column vector, broadcast to ``(ROWS, b_c)`` —
       the equality mask is simultaneously the membership filter AND the
       scatter one-hot, so the binary search and the scatter of the
       reference implementation collapse into one vectorized op on all 8
       sublanes. A row past its own count reads a stale slot whose value is
       masked to 0.0                                          (phase 3),
    3. applies the per-column rescale (with the self-loop exemption of
       Eq. 24, each row its own diagonal lane) and writes the
       ``(ROWS, b_c)`` block once                             (phase 4).

Memory placement. The sampled row ids, their CSR extents and the diagonal
flag are scalar-prefetched into SMEM; the CSR column ids and values stay in
HBM (``memory_space=pl.ANY``) — a paper-scale shard does not fit VMEM — and
each row's edges arrive by DMA into SMEM, where the walk reads them as
scalars. HBM slices of a 1-D array must start and end on its tile of
``DMA_TILE`` elements, so the DMA copies the aligned window of
``window = DMA_TILE * ceil((DMA_TILE - 1 + max_deg) / DMA_TILE)`` elements
that covers the row; CSR arrays whose length is not a multiple of
``DMA_TILE`` are zero-padded first (a copy — partitioned graphs that want
to skip it size ``e_pad`` to a multiple). Where two cells' windows fit the
SMEM scratch (``max_deg`` up to 2,049), the cells run in order and each
starts the next cell's copies before it walks, so they land meanwhile.

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

ROWS = 8            # sampled rows per grid cell: one per f32 sublane
DMA_TILE = 1024     # alignment of a DMA slice of a 1-D int32/f32 HBM array
# the most words each of the two SMEM scratch arrays may take to hold two
# cells' windows, leaving room in the 1 MiB SMEM for the scalar prefetch;
# past it they hold one cell's, copied after the cell before has walked
PREFETCH_WORDS = 1 << 16
UNROLL = 8          # walk steps per rolled loop iteration


def _sublanes(sub, scalars):
    """``ROWS`` scalars -> a ``(ROWS, 1)`` vector, scalar r on sublane r."""
    out = jnp.zeros((ROWS, 1), jnp.asarray(scalars[0]).dtype)
    for r, x in enumerate(scalars):
        out = jnp.where(sub == r, x, out)
    return out


def _extract_kernel(rows_ref, start_ref, cnt_ref, diag_ref,
                    cols_ref, cscale_ref, ci_hbm, val_hbm, o_ref,
                    ci_win, val_win, sem, *, window: int, e_len: int,
                    slot_len: int, n_slots: int):
    """``ROWS`` sampled rows per grid cell: DMA -> match -> rescale -> emit."""
    cell = pl.program_id(0)

    def windows(c, slot):
        """Cell ``c``'s edge-window copies into scratch ``slot``, and where
        each row's first edge lands there."""
        copies, firsts = [], []
        for r in range(ROWS):
            start = start_ref[c * ROWS + r]
            w0 = pl.multiple_of(
                jnp.minimum(start // DMA_TILE * DMA_TILE, e_len - window),
                DMA_TILE)
            at = pl.multiple_of(slot * slot_len + r * window, DMA_TILE)
            firsts.append(at + start - w0)
            for src, win, k in ((ci_hbm, ci_win, 0), (val_hbm, val_win, 1)):
                copies.append(pltpu.make_async_copy(
                    src.at[pl.ds(w0, window)], win.at[pl.ds(at, window)],
                    sem.at[k, slot, r]))
        return copies, firsts

    def start(copies):
        for cp in copies:
            cp.start()

    # phase 2: with two slots, cell c + 1's windows land while cell c walks
    slot = cell % n_slots
    copies, firsts = windows(cell, slot)
    if n_slots == 1:
        start(copies)
    else:
        pl.when(cell == 0)(lambda: start(copies))
        pl.when(cell + 1 < pl.num_programs(0))(
            lambda: start(windows(cell + 1, 1 - slot)[0]))
    for cp in copies:
        cp.wait()

    sub = jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)
    base = cell * ROWS
    cnts = [cnt_ref[base + r] for r in range(ROWS)]
    cnt = _sublanes(sub, cnts)
    n_edges = functools.reduce(jnp.maximum, cnts)
    cvec = jnp.broadcast_to(cols_ref[...], o_ref.shape)   # (ROWS, b_c)

    def body(k, acc):
        for u in range(UNROLL):
            e = k * UNROLL + u
            # edge e of every row, row r on sublane r; a row past its own
            # count adds 0.0 wherever the stale column id it read matches
            col = _sublanes(sub, [ci_win[f + e] for f in firsts])
            v = _sublanes(sub, [val_win[f + e] for f in firsts])
            v = jnp.where(e < cnt, v, 0.0)
            # membership + compact position + scatter in ONE compare: cols
            # are sorted distinct, so at most one lane per row matches
            acc = acc + jnp.where(cvec == col, v, 0.0)
        return acc

    acc = jax.lax.fori_loop(0, (n_edges + UNROLL - 1) // UNROLL, body,
                            jnp.zeros(o_ref.shape, jnp.float32))
    # self-loops stay unrescaled (Eq. 24): a lane is diagonal iff its
    # sampled column equals the row's vertex id and the strata coincide
    row = _sublanes(sub, [rows_ref[base + r] for r in range(ROWS)])
    is_diag = (diag_ref[0] != 0) & (cvec == row)
    o_ref[...] = acc * jnp.where(is_diag, 1.0, cscale_ref[...])


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

    # a walk reads up to max_deg + UNROLL - 1 slots past a row's window
    # start (the clamped last window, a row past its own count): masked,
    # but inside the scratch. A slot starts on a DMA tile.
    slot_len = DMA_TILE * -(-(ROWS * window + max_deg + UNROLL) // DMA_TILE)
    n_slots = 2 if 2 * slot_len <= PREFETCH_WORDS else 1
    kernel = functools.partial(_extract_kernel, window=window, e_len=e_len,
                               slot_len=slot_len, n_slots=n_slots)
    whole = pl.BlockSpec((1, b_c), lambda i, *_: (0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,          # rows, start, cnt, diag -> SMEM
            grid=(n_rows // ROWS,),
            in_specs=[whole, whole, hbm, hbm],   # cols, rescale, ci, val
            out_specs=pl.BlockSpec((ROWS, b_c), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.SMEM((n_slots * slot_len,), jnp.int32),
                pltpu.SMEM((n_slots * slot_len,), jnp.float32),
                pltpu.SemaphoreType.DMA((2, n_slots, ROWS))]),
        # cells run in order: a cell waits on copies its predecessor started
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=jax.ShapeDtypeStruct((n_rows, b_c), jnp.float32),
        interpret=backend.interpret_mode(interpret),
    )(rows, start, cnt, diag1, cols2, cscale, ci, val)
    return out[:b_r].astype(dtype)
