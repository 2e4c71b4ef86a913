"""Production meshes.

``make_production_mesh`` follows the harness contract exactly: a 16 x 16
("data", "model") single pod of 256 chips, or 2 x 16 x 16
("pod", "data", "model") across two pods = 512 chips. Defined as FUNCTIONS
so importing this module never touches jax device state.

``make_production_mesh_4d`` is the paper-faithful GNN mesh
(G_d, x, y, z) with a cube 3D-PMM grid — (4, 4, 4, 4) = 256 single-pod,
(8, 4, 4, 4) = 512 multi-pod.
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """A device mesh whose axes are all ``Auto`` (GSPMD-propagated)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_production_mesh_4d(*, multi_pod: bool = False):
    """ScaleGNN's 4D grid at production scale (cube 3D-PMM, §VII-C)."""
    shape = (8, 4, 4, 4) if multi_pod else (4, 4, 4, 4)
    return make_mesh(shape, ("d", "x", "y", "z"))


def make_production_serve_mesh(*, multi_pod: bool = False):
    """Serving mesh at production scale (serve/distributed.py): a small
    (2, 2, 2) PMM cube per replica group — one serving micro-batch is tiny
    next to a training batch, so latency favors a shallow grid — with the
    remaining chips as stacked-micro-batch data groups (`d`): 32 groups
    single-pod (256 chips), 64 across two pods."""
    shape = (64, 2, 2, 2) if multi_pod else (32, 2, 2, 2)
    return make_mesh(shape, ("d", "x", "y", "z"))
