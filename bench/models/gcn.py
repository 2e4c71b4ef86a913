"""The paper's GCN as a model kind of the benchmark (``model.kind`` =
``"gcn"``): the program's model configuration, the weights in its layout,
the reference's layer equations and the work a step requires.

The layers (``loss_fn``), on the shared sample and block of
``bench/reference.py``: ``h = x W_in``; per layer ``A h W``, RMSNorm,
ReLU, dropout, then the residual ``+ h``; logits ``h W_out``; the mean
cross entropy over the batch.

A kind's module gives ``Model`` (the reference's static sizes, which carry
``reference.Sizes``' four under the same names), ``build``,
``program_config``, ``train_options``, ``init_params``, ``loss_fn`` and
``work``; the harness finds it by the configuration's ``model.kind``.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from bench import reference
from bench.work import F32, NONZERO, extract_bytes


class Model(NamedTuple):
    """Static sizes of the step."""

    n: int
    batch: int
    max_row_nnz: int
    program_seed: int
    d_in: int
    d_hidden: int
    num_layers: int
    num_classes: int
    dropout: float
    rms_eps: float


def build(spec: Dict[str, Any], sizes: reference.Sizes, d_in: int,
          num_classes: int) -> Model:
    """The sizes of the configuration's ``model`` group ``spec``, whose
    RMSNorm, ReLU and residual the reference always computes."""
    off = [k for k in ("rmsnorm", "relu", "residual") if not spec[k]]
    if off:
        raise ValueError(f"the GCN reference computes {off}; the "
                         "configuration turns them off")
    return Model(*sizes, d_in=d_in, d_hidden=int(spec["d_hidden"]),
                 num_layers=int(spec["num_layers"]), num_classes=num_classes,
                 dropout=float(spec["dropout"]),
                 rms_eps=float(spec["rms_eps"]))


def program_config(model: Model):
    """The program's ``GCNConfig`` of these sizes."""
    from repro.core import gcn_model
    return gcn_model.GCNConfig(
        d_in=model.d_in, d_hidden=model.d_hidden,
        num_layers=model.num_layers, num_classes=model.num_classes,
        dropout=model.dropout, rms_eps=model.rms_eps)


def train_options(model: Model) -> Dict[str, Any]:
    """The ``TrainOptions`` fields the model sets."""
    return {"dropout": model.dropout}


def init_params(key: jax.Array, model: Model) -> Dict[str, Any]:
    """Glorot-normal weights and unit RMSNorm scales, in the program's
    parameter layout (``w_in``, ``w_out``, ``layers[i].w``,
    ``layers[i].rms_scale``)."""
    k_in, k_out, *k_layers = jax.random.split(key, model.num_layers + 2)

    def glorot(k, fan_in, fan_out):
        scale = jnp.sqrt(2.0 / (fan_in + fan_out))
        return scale * jax.random.normal(k, (fan_in, fan_out), jnp.float32)

    d_hidden = model.d_hidden
    return {
        "w_in": glorot(k_in, model.d_in, d_hidden),
        "w_out": glorot(k_out, d_hidden, model.num_classes),
        "layers": [{"w": glorot(k, d_hidden, d_hidden),
                    "rms_scale": jnp.ones((d_hidden,), jnp.float32)}
                   for k in k_layers],
    }


def loss_fn(params, model: Model, graph, step, variant: str = "f32"):
    indptr, indices, data, feats, labels = graph
    s = reference.sample(model, step)
    adj = reference.block(model, indptr, indices, data, s)
    h = reference.dot(feats[s], params["w_in"], variant)
    for li, layer in enumerate(params["layers"]):
        conv = reference.dot(reference.dot(adj, h, variant), layer["w"],
                             variant)
        ms = jnp.mean(jnp.square(conv), axis=-1, keepdims=True)
        x = conv * jax.lax.rsqrt(ms + model.rms_eps) * layer["rms_scale"]
        x = jnp.maximum(x, 0.0)
        if model.dropout > 0:
            x = reference.dropout(x, model, model.dropout, step, li)
        h = x + h
    logits = reference.dot(h, params["w_out"], variant)
    return reference.cross_entropy(logits, labels[s], variant)


def model_flops(batch: int, d_in: int, d_hidden: int, num_layers: int,
                num_classes: int, nnz: float) -> float:
    """Forward plus backward operations of one step. A matmul against a
    weight needs the weight's gradient and, unless its input is data, the
    input's; aggregation by the (data) adjacency needs only the input's.
    The input projection's input is the features, so it has no input
    gradient."""
    gemm = 2.0 * batch * d_hidden * d_hidden
    agg = 2.0 * nnz * d_hidden
    w_in = 2.0 * batch * d_in * d_hidden
    w_out = 2.0 * batch * d_hidden * num_classes
    forward = w_in + num_layers * (agg + gemm) + w_out
    backward = w_in + num_layers * (agg + 2 * gemm) + 2 * w_out
    return forward + backward


def spmm_work(batch: int, d_hidden: int, num_layers: int,
              nnz: float) -> Dict[str, float]:
    """Operations and bytes of the aggregations of one step, forward and
    the input-gradient transpose: each reads its dense operand and the
    nonzeros and writes its result."""
    passes = 2 * num_layers
    return {"flops": passes * 2.0 * nnz * d_hidden,
            "bytes": passes * (2.0 * batch * d_hidden * F32
                               + nnz * NONZERO)}


def work(model: Model, nnz: float, row_entries: float) -> Dict[str, float]:
    """The work one step requires, for the per-layer metrics, given the
    sampled block's mean nonzeros and the sampled rows' CSR entries."""
    sp = spmm_work(model.batch, model.d_hidden, model.num_layers, nnz)
    return {
        "model_flops": model_flops(model.batch, model.d_in, model.d_hidden,
                                   model.num_layers, model.num_classes,
                                   nnz),
        "extract_bytes": extract_bytes(min(3, model.num_layers),
                                       row_entries, nnz),
        "spmm_flops": sp["flops"], "spmm_bytes": sp["bytes"],
    }
