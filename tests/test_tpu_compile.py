"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at the widths of the paper-scale training cell.

The TPU compiler is installed with JAX, so ``jax.experimental.topologies``
can describe a ``v5e:2x2`` slice and ``jit(...).lower(...).compile()``
raises whatever the chip's compiler would raise: misaligned block shapes,
more VMEM or SMEM than a kernel may use. Nothing runs; each test asserts
that the kernel reached the compiled program as a ``tpu_custom_call``.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import backend, ops
from repro.kernels.extract_gather import extract_dense_fused


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The default backend here is the CPU, where the kernels would pick
    interpret mode; the described chip must get the compiled kernels."""
    monkeypatch.setattr(backend, "interpret_mode",
                        lambda interpret=None: bool(interpret))


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt


def test_fused_layer_tail_compiles(one_chip, compiled_kernels):
    """The fused RMSNorm/ReLU/dropout/residual tail at (1024, 256), with
    the bool dropout mask, forward and custom-VJP backward."""
    b, d = 1024, 256

    def loss(x, res, scale, mask):
        y = ops.fused_layer_tail(x, res, scale, dropout_mask=mask,
                                 dropout_rate=0.2, row_tile=256)
        return jnp.sum(y * y)

    s = lambda shp, dt=jnp.float32: _sds(one_chip, shp, dt)
    _assert_kernel(jax.value_and_grad(loss, argnums=(0, 2)),
                   s((b, d)), s((b, d)), s((d,)), s((b, d), jnp.bool_))


def test_spmm_ell_compiles(one_chip, compiled_kernels):
    """Block-ELL SpMM at d=256 with 128x128 tiles: the (1024, 1024) block
    of a 1024-vertex batch as 8 row-blocks x 16 slots."""
    n_rb, slots, t, d = 8, 16, 128, 256

    def loss(tiles, colidx, x):
        return jnp.sum(ops.spmm_ell(tiles, colidx, x) ** 2)

    s = lambda shp, dt=jnp.float32: _sds(one_chip, shp, dt)
    _assert_kernel(jax.value_and_grad(loss, argnums=(0, 2)),
                   s((n_rb, slots, t, t)), s((n_rb, slots), jnp.int32),
                   s((n_rb * t, d)))


def _assert_extract_compiles(one_chip, n, e, max_deg):
    def extract(rp, ci, val, rows, cols):
        return extract_dense_fused(rp, ci, val, rows, cols, col_scale=2.5,
                                   diag=True, max_deg=max_deg,
                                   interpret=False)

    s = lambda shp, dt=jnp.int32: _sds(one_chip, shp, dt)
    _assert_kernel(extract, s((n + 1,)), s((e,)), s((e,), jnp.float32),
                   s((1024,)), s((1024,)))


def test_extract_dense_fused_compiles(one_chip):
    """Fused extraction from a 262,144-row CSR of average degree 25 (the
    ogbn-products-shaped shard): CSR arrays stay in HBM, row extents in
    SMEM, 1024 x 1024 block out."""
    _assert_extract_compiles(one_chip, 262_144, 262_144 * 25, 64)


def test_extract_dense_fused_compiles_reddit_shape(one_chip):
    """The same at the Reddit-shaped cell's graph: 32,768 rows, 16.2M
    entries, up to 593 per row (a two-tile DMA window per row)."""
    _assert_extract_compiles(one_chip, 32_768, 16_186_418, 593)


def test_extract_dense_fused_compiles_long_rows(one_chip):
    """Rows of up to 12,000 entries: a 13-tile window per row, so SMEM
    holds one cell's windows at a time and none is prefetched."""
    _assert_extract_compiles(one_chip, 32_768, 16_186_418, 12_000)
