"""Flash-attention Pallas TPU kernel — the fusion identified by §Perf H1.2.

The pure-JAX blockwise attention (models/layers.py) is memory-correct but
materializes the per-block probabilities and the f32 accumulator in HBM on
every scan step; the roofline analysis (EXPERIMENTS.md §Perf, pair 1)
shows this stream dominating the 32k-prefill memory term. This kernel
keeps the whole running-softmax loop in VMEM:

  grid = (batch, q_heads, q_tiles); each cell holds one (tq, hd) query
  tile plus its (m, l, acc) statistics in VMEM/VREGs and streams the
  (T, hd) K/V panels of its KV head through ``pl.dslice`` loads. Causality
  is exploited structurally: the kv loop runs only to the tile's last
  visible block (the q-chunking insight, here at tile granularity).

HBM traffic per cell: q tile once, K/V prefix once, out tile once — the
p/ds/acc streams never leave VMEM. GQA maps q-head -> kv-head inside the
index maps (no KV repetition).

Validated in interpret mode against ``ref.flash_attention_ref``; the
public wrapper (`ops.flash_attention`) pairs this forward with the
memory-efficient jnp backward shared with models/layers.py.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import backend


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, kv_block: int,
                  causal: bool, window: Optional[int], t_true: int,
                  q_tile: int):
    qt = pl.program_id(2)
    q = q_ref[0, :, 0, :].astype(jnp.float32)           # (tq, hd)
    tq, hd = q.shape
    t_pad = k_ref.shape[1]
    scale = hd ** -0.5
    q_pos = qt * q_tile + jax.lax.iota(jnp.int32, tq)

    # causal: only blocks up to this tile's last row are visible
    if causal:
        last = qt * q_tile + tq - 1
        nb = jax.lax.div(last, kv_block) + 1
    else:
        nb = t_pad // kv_block

    def body(i, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.dslice(i * kv_block, kv_block), 0, :]
        vb = v_ref[0, pl.dslice(i * kv_block, kv_block), 0, :]
        s = jnp.dot(q, kb.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32) * scale
        k_pos = i * kv_block + jax.lax.iota(jnp.int32, kv_block)
        allow = k_pos[None, :] < t_true
        if causal:
            allow &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            allow &= k_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(allow, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(allow, jnp.exp(s - m_safe[:, None]), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[:, None] + jnp.dot(
            p.astype(vb.dtype), vb.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((tq,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((tq,), jnp.float32)
    acc0 = jnp.zeros((tq, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-20)[:, None]
    o_ref[0, :, 0, :] = out.astype(o_ref.dtype)
    lse_ref[0, 0, :] = (jnp.where(jnp.isfinite(m), m, 0.0)
                        + jnp.log(jnp.maximum(l, 1e-20)))


def flash_attention_pallas(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, T, KV, hd)
    v: jax.Array,            # (B, T, KV, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_tile: int = 128,
    kv_block: int = 128,
    interpret: bool | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out (B, Sq, H, hd), lse (B, H, Sq))."""
    b, sq, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    tq = min(q_tile, sq)
    assert sq % tq == 0, f"Sq {sq} not a multiple of q_tile {tq}"
    blk = min(kv_block, t)
    if t % blk != 0:
        pad = blk - t % blk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    t_pad = k.shape[1]

    kernel = functools.partial(
        _flash_kernel, kv_block=blk, causal=causal, window=window,
        t_true=t, q_tile=tq)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, sq // tq),
        in_specs=[
            # one q tile per cell
            pl.BlockSpec((1, tq, 1, hd), lambda bi, hi, qi: (bi, qi, hi, 0)),
            # the full K/V panel of this q-head's KV head stays resident;
            # the kernel streams kv_block slices out of it
            pl.BlockSpec((1, t_pad, 1, hd),
                         lambda bi, hi, qi: (bi, 0, hi // g, 0)),
            pl.BlockSpec((1, t_pad, 1, hd),
                         lambda bi, hi, qi: (bi, 0, hi // g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, 1, hd), lambda bi, hi, qi: (bi, qi, hi, 0)),
            pl.BlockSpec((1, 1, tq), lambda bi, hi, qi: (bi, hi, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, h, hd), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq), jnp.float32),
        ],
        interpret=backend.interpret_mode(interpret),
    )(q, k, v)
    return out, lse
