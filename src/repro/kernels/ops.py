"""Jit'd public wrappers for the Pallas kernels, with custom VJPs.

Each kernel picks its mode when it is traced (``backend.interpret_mode``):
compiled on an accelerator, interpreted on the CPU backend.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _flash_k
from repro.kernels import fused_layer as _fused
from repro.kernels import ref as _ref
from repro.kernels import spmm_ell as _spmm


# ---------------------------------------------------------------------------
# Block-ELL SpMM (custom VJP: transpose SpMM via the same kernel on A^T)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def spmm_ell(tiles: jax.Array, colidx: jax.Array, x: jax.Array) -> jax.Array:
    return _spmm.spmm_ell_pallas(tiles, colidx, x)


def _spmm_fwd(tiles, colidx, x):
    return spmm_ell(tiles, colidx, x), (tiles, colidx, x)


def _spmm_bwd(resid, g):
    tiles, colidx, x = resid
    n_rb, n_slots, bm, bn = tiles.shape
    n_rows_out = n_rb * bm
    # dX = A^T @ g: scatter each slot's tile^T @ g_rowblock into its col block
    gblocks = g.reshape(n_rb, bm, -1)

    def accum(s, dx):
        def per_rb(i, dx):
            c = colidx[i, s]
            contrib = tiles[i, s].T @ gblocks[i]          # (bn, d)
            cur = jax.lax.dynamic_slice(dx, (c * bn, 0), (bn, dx.shape[1]))
            return jax.lax.dynamic_update_slice(dx, cur + contrib,
                                                (c * bn, 0))
        return jax.lax.fori_loop(0, n_rb, per_rb, dx)

    dx = jax.lax.fori_loop(0, n_slots, accum,
                           jnp.zeros_like(x, dtype=jnp.float32))
    # dTiles = g_rowblock @ x_colblock^T per slot
    def dtile(i, s):
        c = colidx[i, s]
        xblk = jax.lax.dynamic_slice(x, (c * bn, 0), (bn, x.shape[1]))
        return gblocks[i] @ xblk.T                        # (bm, bn)
    dtiles = jax.vmap(lambda i: jax.vmap(lambda s: dtile(i, s))(
        jnp.arange(n_slots)))(jnp.arange(n_rb)).astype(tiles.dtype)
    del n_rows_out
    return dtiles, None, dx.astype(x.dtype)


spmm_ell.defvjp(_spmm_fwd, _spmm_bwd)


def dense_to_block_ell(adj, bm: int, bn: int, n_slots: int):
    return _spmm.dense_to_block_ell(adj, bm, bn, n_slots)


def block_density(adj, bm: int, bn: int):
    return _spmm.block_density(adj, bm, bn)


# ---------------------------------------------------------------------------
# Fused element-wise layer tail (custom VJP: jnp backward, XLA-fused)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_core(x, scale, extras, has_mask, has_res, dropout_rate, eps,
                flags):
    mask, res = extras
    use_rmsnorm, use_relu, row_tile = flags
    return _fused.fused_layer_pallas(
        x, scale, mask if has_mask else None, res if has_res else None,
        dropout_rate=dropout_rate, eps=eps, use_rmsnorm=use_rmsnorm,
        use_relu=use_relu, row_tile=row_tile)


def _fused_fwd(x, scale, extras, has_mask, has_res, dropout_rate, eps,
               flags):
    y = _fused_core(x, scale, extras, has_mask, has_res, dropout_rate, eps,
                    flags)
    return y, (x, scale, extras)


def _fused_bwd(has_mask, has_res, dropout_rate, eps, flags, resid, g):
    """Backward of Eq. 7-10 in plain jnp (element-wise; XLA fuses it)."""
    x, scale, (mask, res) = resid
    use_rmsnorm, use_relu, _ = flags
    g = g.astype(jnp.float32)
    x32 = x.astype(jnp.float32)

    d_res = g if has_res else None
    if has_mask:
        g = jnp.where(mask, g / (1.0 - dropout_rate), 0.0)

    # recompute forward up to relu input
    if use_rmsnorm:
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(ms + eps)
        normed = x32 * inv
        pre_relu = normed * scale
    else:
        pre_relu = x32
    if use_relu:
        g = jnp.where(pre_relu > 0, g, 0.0)

    if use_rmsnorm:
        d_scale = jnp.sum(g * normed, axis=0)
        gs = g * scale
        # d/dx of x * rsqrt(mean(x^2) + eps)
        d = x.shape[-1]
        dot = jnp.sum(gs * x32, axis=-1, keepdims=True)
        dx = inv * gs - x32 * (inv ** 3) * dot / d
    else:
        d_scale = jnp.zeros_like(scale)
        dx = g
    dmask = jnp.zeros_like(mask) if mask is not None else None
    dres = (d_res if d_res is not None else
            jnp.zeros_like(res)) if res is not None else None
    return (dx.astype(x.dtype), d_scale.astype(scale.dtype),
            (dmask, dres))


_fused_core.defvjp(_fused_fwd, _fused_bwd)


def fused_layer_tail(
    x: jax.Array,
    residual: Optional[jax.Array],
    scale: jax.Array,
    *,
    dropout_mask: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    eps: float = 1e-6,
    use_rmsnorm: bool = True,
    use_relu: bool = True,
    row_tile: int = 256,
) -> jax.Array:
    """Public fused RMSNorm+ReLU+dropout+residual (paper §V-C)."""
    has_mask = dropout_mask is not None
    has_res = residual is not None
    b, d = x.shape
    mask = dropout_mask if has_mask else jnp.zeros((b, d), jnp.bool_)
    res = residual if has_res else jnp.zeros((b, d), x.dtype)
    return _fused_core(x, scale, (mask, res), has_mask, has_res,
                       float(dropout_rate), float(eps),
                       (use_rmsnorm, use_relu, int(row_tile)))


def fused_layer_ref(*args, **kwargs):
    return _ref.fused_layer_ref(*args, **kwargs)


def spmm_ell_ref(*args, **kwargs):
    return _ref.spmm_ell_ref(*args, **kwargs)


# ---------------------------------------------------------------------------
# Flash attention (Pallas forward; memory-efficient jnp backward shared
# with models/layers.py)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=True, window=None):
    out, _ = _flash_k.flash_attention_pallas(
        q, k, v, causal=causal, window=window)
    return out


def _fa_fwd(q, k, v, causal, window):
    out, lse = _flash_k.flash_attention_pallas(
        q, k, v, causal=causal, window=window)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, resid, dout):
    """Reuse the flash backward from models/layers.py: recompute scores
    per KV block from the saved (out, lse) — O(Sq) residuals."""
    from repro.models import layers as L
    q, k, v, out, lse = resid
    b, sq, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    blk = min(512, t)
    if t % blk != 0:
        pad = blk - t % blk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # layers._flash_bwd wants lse in grouped (b, kv, g, sq) layout
    lse_g = lse.reshape(b, kv, g, sq)
    dq, dk, dv = L._flash_bwd(t, causal, window, 0, blk,
                              (q, k, v, out, lse_g), dout)
    return dq, dk[:, :t], dv[:, :t]


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_ref(*args, **kwargs):
    return _ref.flash_attention_ref(*args, **kwargs)
