"""Helpers of the benchmark's tests: the checkout on the import path, and
a cell small enough for the CPU, where the Pallas kernels run in interpret
mode, built from the same files as the chip's cells."""
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(limits=None):
    """products-b1024's configuration and traffic at a size the CPU holds:
    2,048 vertices, batch 256, 2 steps per chunk."""
    from bench import manifest
    cell = manifest.load_cell("products-b1024", ROOT)
    config = copy.deepcopy(cell.config)
    config["graph"].update(vertices=2048, feature_dim=16, num_classes=4,
                           asked_mean_degree=8.0)
    config["model"].update(d_hidden=32)
    traffic = dict(cell.traffic, batch=256, steps_per_chunk=2)
    return manifest.Cell(
        name="tiny", chips=1, config=config, traffic=traffic,
        limits=limits or cell.limits, end_to_end=cell.end_to_end,
        per_layer=cell.per_layer, kind=cell.kind)


def load_json(path):
    with open(path) as f:
        return json.load(f)
