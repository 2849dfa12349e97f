"""How far a served prediction may sit from its reference.

Votes are counted exactly on the device (float32 holds integers exactly
below 2**24), so a classification prediction must EQUAL the reference.
A regression prediction is the mean of a forest's T leaf fits, which the
device sums in float32: recursive summation of T terms with unit
roundoff 2**-24 (each fit rounded to float32 on the way in) errs by at
most T * 2**-24 * sum|fit| <= T**2 * 2**-24 * max|fit| on the sum, so by
at most T * 2**-24 * max|fit| on the mean.
"""
from __future__ import annotations

import numpy as np


def served_tolerance(comp) -> float:
    """Largest admissible |served - reference| for one forest
    (``CompressedForest`` or ``Forest``): 0 for classification, the
    float32 summation bound above for regression."""
    if comp.meta.task == "classification":
        return 0.0
    max_fit = float(np.abs(np.asarray(comp.fit_values)).max(initial=0.0))
    return comp.n_trees * 2.0 ** -24 * max_fit


def count_mismatches(pred, ref, tol: float = 0.0) -> int:
    """Rows of ``pred`` farther than ``tol`` from ``ref`` (a NaN or a
    shape disagreement counts against every row)."""
    pred = np.asarray(pred, np.float64)
    ref = np.asarray(ref, np.float64)
    if pred.shape != ref.shape:
        return max(pred.size, ref.size, 1)
    return int(np.sum(~(np.abs(pred - ref) <= tol)))
