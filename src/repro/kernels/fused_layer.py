"""Fused element-wise tail Pallas kernel — the paper's §V-C kernel fusion.

The baseline applies RMSNorm, ReLU, and dropout as separate kernels "with
redundant memory round-trips"; the paper fuses them with torch.compile. On
TPU we hand-write the fusion with an explicit VMEM BlockSpec: one grid cell
loads a (bm, d) row tile once from HBM, applies

    RMSNorm (Eq. 7) -> ReLU (Eq. 8) -> dropout via precomputed keep-mask
    (Eq. 9) -> residual add (Eq. 10)

entirely in VMEM/VREGs, and writes the tile back once — a single HBM
round-trip instead of four. The full feature dim stays in one block so the
row-wise mean-of-squares needs no cross-block reduction (d_h is at most a
few thousand floats -> a few hundred KB per tile, comfortably inside the
~16 MB of v5e VMEM for bm up to ~1024).

The kernel is forward-only; gradients flow through a ``jax.custom_vjp``
whose backward is expressed in plain jnp (XLA fuses the element-wise
backward well; the paper's fusion win is likewise reported for the forward
kernels). Checked in interpret mode against ``ref.fused_layer_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend


def _fused_kernel(x_ref, scale_ref, mask_ref, res_ref, o_ref, *,
                  eps: float, keep_prob: float, use_rmsnorm: bool,
                  use_relu: bool, has_mask: bool, has_res: bool):
    x = x_ref[...].astype(jnp.float32)
    if use_rmsnorm:
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(ms + eps) * scale_ref[...].astype(jnp.float32)
    if use_relu:
        x = jnp.maximum(x, 0.0)
    if has_mask:
        x = jnp.where(mask_ref[...], x / keep_prob, 0.0)
    if has_res:
        x = x + res_ref[...].astype(jnp.float32)
    o_ref[...] = x.astype(o_ref.dtype)


def fused_layer_pallas(
    x: jax.Array,                      # (B, d)
    scale: jax.Array,                  # (d,)
    dropout_mask: Optional[jax.Array],  # (B, d) bool or None
    residual: Optional[jax.Array],     # (B, d) or None
    *,
    dropout_rate: float = 0.0,
    eps: float = 1e-6,
    use_rmsnorm: bool = True,
    use_relu: bool = True,
    row_tile: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """One-HBM-round-trip RMSNorm+ReLU+dropout+residual (see module doc)."""
    b, d = x.shape
    bm = min(row_tile, b)
    assert b % bm == 0, f"rows {b} not a multiple of row tile {bm}"
    has_mask = dropout_mask is not None
    has_res = residual is not None
    keep_prob = 1.0 - dropout_rate

    # Pallas wants every operand present; feed zero-size dummies when absent
    mask_in = dropout_mask if has_mask else jnp.zeros((b, d), jnp.bool_)
    res_in = residual if has_res else jnp.zeros((b, d), x.dtype)

    kernel = functools.partial(
        _fused_kernel, eps=eps, keep_prob=keep_prob,
        use_rmsnorm=use_rmsnorm, use_relu=use_relu,
        has_mask=has_mask, has_res=has_res)
    row_spec = pl.BlockSpec((bm, d), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(b // bm,),
        in_specs=[
            row_spec,                                   # x
            pl.BlockSpec((d,), lambda i: (0,)),         # scale
            row_spec,                                   # mask
            row_spec,                                   # residual
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, d), x.dtype),
        interpret=backend.interpret_mode(interpret),
    )(x, scale, mask_in, res_in)
