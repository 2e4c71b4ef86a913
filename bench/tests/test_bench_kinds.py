"""A model of another kind enters the benchmark by new files alone: a toy
kind with its configuration, its limits and a metric that reads a scope
no other metric reads, copied beside the benchmark into a fresh root, is
found by the manifest and the harness; the shared reference trains it; an
unknown kind fails; and the trace reader, given the union of the cells'
scopes, reads the chip's fixture as it did with the old six."""
import json
import os
import shutil

import pytest

from benchkit import ROOT, load_json

FIXTURE = os.path.join(ROOT, "bench", "tests", "fixtures",
                       "products_trace.json.gz")
OLD_SIX = ("extract", "spmm", "gemm", "tail", "reshard", "rotate")

TOY_KIND = '''"""A toy kind: one aggregation and a linear head."""
from typing import NamedTuple

import jax

from bench import reference


class Model(NamedTuple):
    n: int
    batch: int
    max_row_nnz: int
    program_seed: int
    d_in: int
    num_classes: int


def build(spec, sizes, d_in, num_classes):
    return Model(*sizes, d_in=d_in, num_classes=num_classes)


def program_config(model):
    raise NotImplementedError("the toy kind has no program")


def train_options(model):
    return {}


def init_params(key, model):
    return {"w": 0.1 * jax.random.normal(key, (model.d_in,
                                               model.num_classes))}


def loss_fn(params, model, graph, step, variant="f32"):
    indptr, indices, data, feats, labels = graph
    s = reference.sample(model, step)
    adj = reference.block(model, indptr, indices, data, s)
    h = reference.dot(adj, feats[s], variant)
    return reference.cross_entropy(reference.dot(h, params["w"], variant),
                                   labels[s], variant)


def work(model, nnz, row_entries):
    return {"model_flops": 6.0 * model.batch * model.d_in
            * model.num_classes}
'''

TOY_METRIC = '''"""Device time per step under the toy's ``attn`` scope."""

SCOPES = ("attn",)


def compute(ctx):
    s = ctx["trace"]["scope_s"].get("attn")
    if not s or ctx["steps"] <= 0:
        return None
    return 1e3 * s / ctx["steps"]
'''


def _files(root):
    return {os.path.relpath(os.path.join(dp, f), root):
            open(os.path.join(dp, f), "rb").read()
            for dp, _, fs in os.walk(root) for f in fs}


@pytest.fixture
def toy_root(tmp_path):
    """A checkout holding ``BENCHMARK.json`` and ``bench/`` as committed,
    plus the toy kind's files and entries; returns (root, the files as
    committed)."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _files(root)
    bench = root / "bench"
    (bench / "models" / "toy.py").write_text(TOY_KIND)
    (bench / "metrics" / "attn_ms.py").write_text(TOY_METRIC)
    cfg = load_json(bench / "configs" / "gcn-products.json")
    cfg.update(name="toy-products", model={"kind": "toy"})
    (bench / "configs" / "toy-products.json").write_text(json.dumps(cfg))
    (bench / "limits" / "toy-b1024.json").write_text(
        json.dumps({"limits": {"loss": 1e-3, "grad": 1e-2, "update": 1e-2}}))
    m = load_json(root / "BENCHMARK.json")
    m["configs"].append(dict(m["configs"][0], name="toy-products",
                             file="bench/configs/toy-products.json"))
    m["workloads"].append({"name": "toy-b1024", "config": "toy-products",
                           "traffic": "stratified-b1024", "chips": 1,
                           "why": "a test cell"})
    m["per_layer"].append({"name": "attn_ms", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "model (core/forward.py ForwardEngine)",
                           "moves": "step_ms", "workloads": ["toy-b1024"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root, before


def test_a_new_kind_and_its_scope_load_from_new_files_alone(toy_root):
    import jax
    from bench import harness, manifest
    root, before = toy_root
    toy = manifest.load_cell("toy-b1024", str(root))
    assert toy.kind.__file__ == str(root / "bench" / "models" / "toy.py")
    assert manifest.load_model("toy", str(root / "bench")) is toy.kind
    assert harness.TrainCell(toy, jax.devices("cpu")).kind is toy.kind
    assert toy.limits == {"loss": 1e-3, "grad": 1e-2, "update": 1e-2}
    assert "attn_ms" in [m.name for m in toy.per_layer]
    assert "attn" in toy.scopes and set(OLD_SIX) < set(toy.scopes)
    gcn = manifest.load_cell("products-b1024", str(root))
    assert os.path.basename(gcn.kind.__file__) == "gcn.py"
    assert "attn" not in gcn.scopes
    assert manifest.kinds(str(root / "bench")) == ["gcn", "toy"]
    after = _files(root)
    assert {p: after[p] for p in before if p != "BENCHMARK.json"} == {
        p: b for p, b in before.items() if p != "BENCHMARK.json"}


def test_the_shared_reference_trains_a_new_kind(toy_root):
    """``reference.checked`` takes the toy's loss as it takes the GCN's:
    the held chunk keeps the weights, the trained chunk moves them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import graphgen, manifest, reference
    root, _ = toy_root
    toy = manifest.load_cell("toy-b1024", str(root)).kind
    g = graphgen.generate(256, 4, 8, 6.0, seed=3)
    a = g.adj_norm
    graph = tuple(jnp.asarray(x) for x in (a.indptr, a.indices, a.data,
                                            g.features, g.labels))
    model = toy.build({}, reference.Sizes(256, 32, g.max_row_nnz, 0), 8, 4)
    params = toy.init_params(jax.random.PRNGKey(1), model)
    both = reference.checked(params, toy.loss_fn, model,
                             reference.Adam(lr=5e-3), graph, 5, 2)
    assert all(np.isfinite(both.held.losses + both.trained.losses))
    assert all(np.all(d == 0) for d in both.held.delta)
    assert any(np.any(d != 0) for d in both.trained.delta)


def test_an_unknown_kind_fails_and_names_the_known_kinds(toy_root):
    from bench import manifest
    root, _ = toy_root
    path = root / "bench" / "configs" / "toy-products.json"
    cfg = load_json(path)
    cfg["model"]["kind"] = "gat"
    path.write_text(json.dumps(cfg))
    with pytest.raises(SystemExit, match=r"'gat'.*known kinds: "
                                         r"\['gcn', 'toy'\]"):
        manifest.load_cell("toy-b1024", str(root))


def test_the_union_of_scopes_reads_the_recorded_trace_as_the_old_six(
        toy_root):
    """The chip's fixture holds no ``attn`` op: given the union of both
    cells' scopes the reader gives the six old scopes and the busy time
    exactly what it gives with the six alone."""
    from bench import manifest, trace
    root, _ = toy_root
    union = tuple(dict.fromkeys(
        manifest.load_cell("products-b1024", str(root)).scopes
        + manifest.load_cell("toy-b1024", str(root)).scopes))
    ev = trace.load_plain(FIXTURE)
    window = trace.window_of(ev["spans"], "bench.window")
    old = trace.reduce(ev, window, OLD_SIX)
    new = trace.reduce(ev, window, union)
    assert set(old["scope_s"]) <= set(OLD_SIX)
    assert new["scope_s"] == old["scope_s"]
    assert new["busy_s"] == old["busy_s"]
    assert new["device_ops"] == old["device_ops"]
