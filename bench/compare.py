"""The numbers that decide ``correct``, from the program's readings and the
plain reference's.

A training cell compares, over its first steps:

* ``loss``: the largest relative gap of a step's loss;
* ``grad1``: the gradient as the optimizer got it in step 1 (its momentum
  after that step), by the worst leaf;
* ``change``: the change of the parameters over the checked steps, by the
  worst leaf, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).

"By the worst leaf" is the largest gap between the program's norm of a
leaf and the reference's, over the reference's norm of that leaf or of the
median leaf, whichever is larger.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from bench.harness import Check

NOUGHT = 1e-3          # a leaf whose gradient is under this share of the
                       # median leaf's moves by round-off alone


def worst_leaf(prog: Sequence[float], ref: Sequence[float],
               keep: Optional[np.ndarray] = None) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        raise ValueError(f"{prog.shape[0]} leaves against {ref.shape[0]}")
    gaps = np.abs(prog - ref) / np.maximum(ref, np.median(ref))
    if keep is not None:
        gaps = gaps[keep]
    return float(np.max(gaps))


def moving(ref_grad: Sequence[float]) -> np.ndarray:
    g = np.asarray(ref_grad, np.float64)
    return g >= NOUGHT * np.median(g)


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    if len(prog["loss"]) != len(ref["loss"]) or not np.all(
            np.isfinite(prog["loss"])):
        loss = float("inf")
    return {"loss": float(loss),
            "grad1": worst_leaf(prog["mom1"], ref["mom1"]),
            "change": worst_leaf(prog["change"], ref["change"],
                                 moving(ref["grad1"]))}


def train_checks(prog: Dict, ref: Dict, limits: Dict[str, float]
                 ) -> List[Check]:
    numbers = train_numbers(prog, ref)
    return [Check(name, numbers[name], limits[name]) for name in limits]
