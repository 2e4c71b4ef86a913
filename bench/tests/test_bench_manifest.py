"""The benchmark's manifest: every cell finds its files by name, names and
units keep to the allowed characters, metrics move an end-to-end metric
their cells report, a new cell or metric needs only new files and entries,
and without a chip the command prints no result."""
import json
import os
import re
import shutil
import subprocess
import sys

from benchkit import ROOT, load_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _reporting(metric, cells):
    return metric.get("workloads", [w["name"] for w in cells])


def test_cells_name_files_that_exist():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    for c in MANIFEST["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in
                                          MANIFEST["paths"]))
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs
        for sub, name in (("traffic", w["traffic"]),
                          ("limits", w["name"])):
            assert os.path.isfile(os.path.join(ROOT, "bench", sub,
                                               f"{name}.json")), (sub, name)
        assert w["chips"] in (1, 4)
    for m in MANIFEST["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in MANIFEST["configs"]]
    names += [w["name"] for w in MANIFEST["workloads"]]
    names += [w[k] for w in MANIFEST["workloads"] for k in ("config",
                                                           "traffic")]
    names += [k for c in MANIFEST["configs"] for k in c["reduced"]]
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names += [m["name"] for m in metrics]
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        seen = [x["name"] for x in MANIFEST[group]]
        assert len(seen) == len(set(seen))
    seen = [m["name"] for m in metrics]
    assert len(seen) == len(set(seen))


def test_every_metric_moves_an_end_to_end_metric_its_cells_report():
    cells = MANIFEST["workloads"]
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e, m
        target = e2e[m["moves"]]
        for cell in _reporting(m, cells):
            assert cell in _reporting(target, cells), (m["name"], cell)
    for w in cells:
        assert any(w["name"] in _reporting(m, cells)
                   for m in MANIFEST["per_layer"])
        assert sum(w["name"] in _reporting(m, cells)
                   for m in MANIFEST["end_to_end"]) >= 2


def test_a_new_cell_and_metric_load_from_new_files_alone(tmp_path):
    from bench import manifest
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(root / "bench") for p in fs}
    bench = root / "bench"
    cfg = load_json(bench / "configs" / "gcn-products.json")
    cfg["name"] = "gcn-extra"
    (bench / "configs" / "gcn-extra.json").write_text(json.dumps(cfg))
    traffic = load_json(bench / "traffic" / "stratified-b1024.json")
    traffic["batch"] = 2048
    (bench / "traffic" / "extra-mix.json").write_text(json.dumps(traffic))
    (bench / "limits" / "extra-cell.json").write_text(
        json.dumps({"limits": {"loss": 1, "grad": 1, "update": 1}}))
    (bench / "metrics" / "extra_ms.py").write_text(
        "def compute(ctx):\n    return 1e3 * ctx['steps']\n")
    m = json.loads(json.dumps(MANIFEST))
    m["configs"].append(dict(m["configs"][0], name="gcn-extra",
                             file="bench/configs/gcn-extra.json"))
    m["workloads"].append({"name": "extra-cell", "config": "gcn-extra",
                           "traffic": "extra-mix", "chips": 1,
                           "why": "a test cell"})
    m["per_layer"].append({"name": "extra_ms", "unit": "ms",
                           "better": "lower", "source": "device_trace",
                           "layer": "device", "moves": "step_ms",
                           "workloads": ["extra-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    cell = manifest.load_cell("extra-cell", str(root))
    assert cell.traffic["batch"] == 2048
    assert cell.config["name"] == "gcn-extra"
    extra = [x for x in cell.per_layer if x.name == "extra_ms"]
    assert extra and extra[0].compute({"steps": 2}) == 2e3
    old = manifest.load_cell("products-b1024", str(root))
    assert "extra_ms" not in [x.name for x in old.per_layer]
    for dp, _, fs in os.walk(root / "bench"):
        for p in fs:
            if p in before:
                assert open(os.path.join(dp, p), "rb").read() == before[p]


def _run_command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "products-b1024",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not [line for line in proc.stdout.splitlines()
                if line.strip().startswith("{")]


def test_command_without_a_chip_prints_no_result():
    _no_result(_run_command(ROOT))


def test_command_without_the_program_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run_command(str(tmp_path)))
