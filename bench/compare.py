"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes for the same inputs.

Training compares each checked step's loss, the first gradient as the
optimizer received it (after clipping), and the parameters' change over the
checked steps. Norms are compared leaf by leaf: the gap between the
program's norm and the reference's, over the larger of the reference's norm
of that leaf and the median leaf's, since some gradients are all but zero.
Leaves whose reference gradient is under a thousandth of the median leaf's
move under Adam by round-off alone and are left out of the change.
Inference compares every sampled prediction with the reference's forward
pass: the widest gap over the largest magnitude of the reference's field.
"""
from __future__ import annotations

import numpy as np
import jax

#: a leaf whose reference gradient norm is under this share of the median
#: leaf's is left out of the parameter change
STILL_LEAF = 1e-3


def _norms(tree) -> np.ndarray:
    return np.array([np.linalg.norm(np.asarray(x, np.float64))
                     for x in jax.tree.leaves(tree)])


def _worst_leaf(prog: np.ndarray, ref: np.ndarray) -> float:
    floor = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref) / floor))


def train_gaps(prog, ref) -> dict:
    """Gaps between the program's and the reference's ``(losses, first
    clipped gradient, parameters' change)``: ``loss_gap``, the worst relative
    gap of the checked steps' losses; ``grad_gap``, the worst leaf of the
    gradient; ``change_gap``, the worst moving leaf of the change."""
    (pl, pg, pc), (rl, rg, rc) = prog, ref
    pl, rl = np.asarray(pl, np.float64), np.asarray(rl, np.float64)
    g_ref = _norms(rg)
    moving = g_ref >= STILL_LEAF * np.median(g_ref)
    return {
        "loss_gap": float(np.max(np.abs(pl - rl) / np.abs(rl))),
        "grad_gap": _worst_leaf(_norms(pg), g_ref),
        "change_gap": _worst_leaf(_norms(pc)[moving], _norms(rc)[moving]),
    }


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
                        a, b)


def prediction_gap(pred: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap of a predicted field over the reference field's largest
    magnitude."""
    pred, ref = np.asarray(pred, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(pred - ref)) / np.max(np.abs(ref)))
