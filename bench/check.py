"""The comparison that decides ``correct`` for a training cell.

A run checks two chunks of the window's own compiled step
(``reference.Checked``): ``held``, from the seed's weights with Adam's
second moment started so high that no step moves a weight, so every step
is a first step -- its loss and gradient taken at the seed's weights, with
no drift between the program's and the reference's trajectories; then
``trained``, the next chunk from the seed's fresh state, which trains as
the window does. Three numbers, each compared with its own limit
(``bench/limits``) where the cell's limits file gives one:

* ``loss``   -- the held chunk's losses, by the worst step:
  ``max_t |L_t - L_ref,t| / |L_ref,t|``;
* ``grad``   -- the optimizer's first moment after the held chunk (the
  chunk's gradients at the seed's weights as the optimizer gets them), by
  the worst leaf: ``| |m| - |m_ref| | / max(|m_ref|, median_leaf |m_ref|)``;
* ``update`` -- the parameters' change over the trained chunk, by the same
  worst-leaf rule, over the leaves whose first reference gradient is at
  least a thousandth of the median leaf's (a leaf below that moves under
  Adam by round-off alone).

The trained chunk's losses are kept as readings (``loss_steps``): each
later step starts from parameters that have already drifted apart, so
their gaps are the noise of the drift. ``held_moved``, the largest change
of a weight over the held chunk, reads 0 where the weights were held. A
reading that is not finite is returned as ``inf``, which no limit passes.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

NUMBERS = ("loss", "grad", "update")
MOVING_LEAF = 1e-3


def _norms(xs: Sequence[np.ndarray]) -> np.ndarray:
    return np.array([float(np.linalg.norm(x)) for x in xs])


def worst_leaf(got: Sequence[np.ndarray], ref: Sequence[np.ndarray],
               keep: Optional[np.ndarray] = None) -> float:
    """Worst gap of the leaves' norms, each against the larger of the
    reference leaf's norm and the median leaf's."""
    a, b = _norms(got), _norms(ref)
    floor = float(np.median(b))
    gap = np.abs(a - b) / np.maximum(np.maximum(b, floor), 1e-30)
    if keep is not None:
        gap = gap[keep]
    worst = float(gap.max()) if gap.size else 0.0
    return worst if math.isfinite(worst) else math.inf


def moving_leaves(grad0: Sequence[np.ndarray]) -> np.ndarray:
    n = _norms(grad0)
    return n >= MOVING_LEAF * float(np.median(n))


def _loss_gaps(got, ref) -> List[float]:
    gl = np.asarray(got.losses, np.float64)
    rl = np.asarray(ref.losses, np.float64)
    return [float(x) if math.isfinite(x) else math.inf
            for x in np.abs(gl - rl) / np.abs(rl)]


def readings(got, ref) -> Dict[str, object]:
    """``got`` and ``ref`` are ``reference.Checked``-shaped (``held`` and
    ``trained`` runs); ``ref.trained.grad0`` chooses the leaves the update
    is read on."""
    held = _loss_gaps(got.held, ref.held)
    return {
        "loss": max(held),
        "grad": worst_leaf(got.held.mu, ref.held.mu),
        "update": worst_leaf(got.trained.delta, ref.trained.delta,
                             moving_leaves(ref.trained.grad0)),
        "loss_held": held,
        "loss_steps": _loss_gaps(got.trained, ref.trained),
        "held_moved": max(float(np.max(np.abs(d)))
                          for d in got.held.delta),
    }


def compared(limits: Dict[str, float]) -> List[str]:
    """The numbers a cell compares: those its limits file gives a limit.
    A number with no reading that fails it has no limit and is only
    reported."""
    return [k for k in NUMBERS if k in limits]


def verdict(values: Dict[str, object], limits: Dict[str, float]) -> bool:
    """Every compared number within its limit, and every step's loss
    finite."""
    return (all(values[k] <= limits[k] for k in compared(limits))
            and all(math.isfinite(x) for x in values["loss_held"]
                    + values["loss_steps"]))


def report(values: Dict[str, object],
           limits: Dict[str, float]) -> List[str]:
    """One line per compared number, with its limit."""
    return [f"check {k}: {values[k]!r} limit {limits[k]!r}"
            for k in compared(limits)]
