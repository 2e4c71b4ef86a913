"""The benchmark's graph generator: a stochastic block model (SBM) with
noisy label prototypes as features, made from a seed with numpy.

It follows the model of the program's own stand-in generator
(``repro.graphs.synthetic.make_synthetic_dataset(kind="sbm")``):
communities are the classes; ``p_in / p_out`` is fixed; the expected
degree that is asked for sets ``p_out``; vertex pairs are drawn with
replacement per pair of communities, symmetrised, de-duplicated and given
one self-loop; values are the GCN normalisation
``1 / sqrt(deg(r) * deg(c))`` with the self-loop counted. It draws every
pair of communities in one vectorised pass and sorts once, so a graph of
15M entries takes seconds, not tens of seconds.

The realised mean degree lands above the one asked for: a pair inside a
community is drawn from the ordered pairs and then symmetrised, which
doubles the intra-community share (about +15% at 8:1 and 40-50
communities), less what de-duplication removes.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class CSR:
    indptr: np.ndarray   # (n + 1,) int32
    indices: np.ndarray  # (nnz,) int32, sorted within each row
    data: np.ndarray     # (nnz,) float32

    @property
    def n_rows(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])


@dataclasses.dataclass
class Graph:
    """What ``repro.graphs.build_partitioned_graph`` reads from a dataset."""

    adj_norm: CSR
    features: np.ndarray     # (n, d_in) float32
    labels: np.ndarray       # (n,) int32
    train_mask: np.ndarray   # (n,) bool
    num_classes: int

    @property
    def mean_degree(self) -> float:
        """Mean off-diagonal entries per row (the self-loop not counted)."""
        n = self.adj_norm.n_rows
        return (self.adj_norm.nnz - n) / n

    @property
    def max_row_nnz(self) -> int:
        return int(np.diff(self.adj_norm.indptr).max())


def generate(n: int, num_classes: int, d_in: int, avg_degree: float, *,
             seed: int, p_in_out_ratio: float = 8.0,
             feature_noise: float = 2.0) -> Graph:
    """An SBM graph of ``n`` vertices with ``num_classes`` communities.
    ``avg_degree`` is the degree asked for; see the module docstring for
    what is realised."""
    rng = np.random.default_rng([seed, 0])
    k = num_classes
    block = rng.integers(0, k, n)
    order = np.argsort(block, kind="stable")
    counts = np.bincount(block, minlength=k).astype(np.int64)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])

    p_out = avg_degree / (p_in_out_ratio * (n / k) + (n - n / k))
    p_in = p_in_out_ratio * p_out
    bi, bj = np.triu_indices(k)
    total = counts[bi] * counts[bj]
    m = rng.binomial(total, np.where(bi == bj, p_in, p_out))
    pair = np.repeat(np.arange(bi.shape[0]), m)
    flat = (rng.random(pair.shape[0]) * total[pair]).astype(np.int64)
    width = counts[bj[pair]]
    src = order[starts[bi[pair]] + flat // width]
    dst = order[starts[bj[pair]] + flat % width]
    del pair, flat, width
    keep = src != dst
    src, dst = src[keep], dst[keep]
    loop = np.arange(n, dtype=np.int64)
    key = np.unique(np.concatenate([src * n + dst, dst * n + src,
                                    loop * n + loop]))
    del src, dst
    rows = key // n
    cols = (key - rows * n).astype(np.int32)
    deg = np.bincount(rows, minlength=n)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    dinv = 1.0 / np.sqrt(deg.astype(np.float64))
    data = (dinv[rows] * dinv[cols]).astype(np.float32)
    del key, rows

    frng = np.random.default_rng([seed, 1])
    prototypes = frng.standard_normal((k, d_in), dtype=np.float32)
    noise = frng.standard_normal((n, d_in), dtype=np.float32)
    noise *= np.float32(feature_noise)
    noise += prototypes[block]
    return Graph(adj_norm=CSR(indptr.astype(np.int32), cols, data),
                 features=noise, labels=block.astype(np.int32),
                 train_mask=np.ones(n, bool), num_classes=k)
