"""The plain reference: a numpy walk of the uncompressed trees.

It imports nothing of the program and reads nothing the program made: the
trees come from ``forests.npz``, written at build time from the forests as
generated, before the codec ever saw them.  Each user's trees are walked
together, one level per step, from the root to a leaf; a classification
forest answers with its majority vote (ties to the lowest class id, as an
argmax over vote counts gives), a regression forest with the float64 mean
of its leaf fits.

The walk also counts what the batch needs at the least (``Work``): the
distinct nodes its rows visit and the node visits themselves.  ``work.py``
turns that into the lower-bound time behind every roofline share.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

#: rows walked at once; bounds the (trees, rows) index arrays
ROW_BLOCK = 4096


@dataclass
class Work:
    """What one walk needs at the least: the distinct internal nodes and
    leaves its rows visit, and the node visits (one per node on every
    (tree, row) path, leaf included)."""

    internal: int = 0
    leaves: int = 0
    visits: int = 0

    def __iadd__(self, other: "Work") -> "Work":
        self.internal += other.internal
        self.leaves += other.leaves
        self.visits += other.visits
        return self


class Forests:
    """The uncompressed forests of one deployment, as plain arrays."""

    def __init__(self, arrays: dict) -> None:
        self.task = str(arrays["task"])
        self.n_classes = int(arrays["n_classes"])
        self.users = [str(u) for u in arrays["users"]]
        self._index = {u: i for i, u in enumerate(self.users)}
        self.tree_off = np.asarray(arrays["tree_off"], np.int64)
        self.node_off = np.asarray(arrays["node_off"], np.int64)
        self.feature = np.asarray(arrays["feature"], np.int32)
        self.threshold = np.asarray(arrays["threshold"], np.int32)
        self.node_fit = np.asarray(arrays["node_fit"], np.int64)
        self.fit_values = np.asarray(arrays["fit_values"], np.float64)
        self.fit_off = np.asarray(arrays["fit_off"], np.int64)
        # children as global node ids (-1 stays -1 at leaves)
        owner = np.repeat(
            np.arange(len(self.node_off) - 1), np.diff(self.node_off)
        )
        base = self.node_off[owner]
        left = np.asarray(arrays["left"], np.int64)
        right = np.asarray(arrays["right"], np.int64)
        self.left = np.where(left >= 0, left + base, -1)
        self.right = np.where(right >= 0, right + base, -1)

    @classmethod
    def load(cls, path) -> "Forests":
        with np.load(path) as z:
            return cls({k: z[k] for k in z.files})

    def n_trees(self, user: str) -> int:
        i = self._index[user]
        return int(self.tree_off[i + 1] - self.tree_off[i])

    def fits(self, user: str) -> np.ndarray:
        i = self._index[user]
        return self.fit_values[self.fit_off[i]:self.fit_off[i + 1]]

    def tolerance(self, user: str) -> float:
        """How far a served answer may sit from the reference's: 0 for a
        vote; for a mean of T float32 leaf fits, T * 2**-24 * max|fit| (each
        fit rounded to float32 and T - 1 float32 additions, divided by T)."""
        if self.task == "classification":
            return 0.0
        fits = self.fits(user)
        return self.n_trees(user) * 2.0 ** -24 * float(
            np.abs(fits).max(initial=0.0)
        )

    def walk(self, user: str, x: np.ndarray) -> tuple[np.ndarray, Work]:
        """(n,) answers of ``user``'s forest on binned rows ``x`` (n, d),
        and the work the walk saw."""
        i = self._index[user]
        roots = self.node_off[self.tree_off[i]:self.tree_off[i + 1]]
        n = len(x)
        out = np.zeros(n, np.float64)
        seen = np.zeros(len(self.feature), bool)
        work = Work()
        for r0 in range(0, n, ROW_BLOCK):
            xs = x[r0:r0 + ROW_BLOCK]
            out[r0:r0 + len(xs)] = self._walk_block(user, roots, xs, seen,
                                                     work)
        inner = self.feature[seen] >= 0
        work.internal = int(inner.sum())
        work.leaves = int((~inner).sum())
        return out, work

    def _walk_block(self, user, roots, xs, seen, work) -> np.ndarray:
        n = len(xs)
        cols = np.arange(n)[None, :]
        idx = np.broadcast_to(roots[:, None], (len(roots), n)).copy()
        seen[roots] = True
        work.visits += idx.size
        while True:
            feat = self.feature[idx]
            active = feat >= 0
            if not active.any():
                break
            go_left = xs[cols, np.maximum(feat, 0)] <= self.threshold[idx]
            nxt = np.where(go_left, self.left[idx], self.right[idx])
            idx = np.where(active, nxt, idx)
            seen[idx[active]] = True
            work.visits += int(active.sum())
        leaf = self.node_fit[idx]  # (T, n)
        if self.task == "classification":
            votes = np.stack([
                (leaf == c).sum(0) for c in range(max(self.n_classes, 1))
            ])
            return votes.argmax(0).astype(np.float64)
        return self.fits(user)[leaf].sum(0) / max(len(roots), 1)


#: printed in place of a gap where an answer never came (JSON has no inf)
MISSING_GAP = 1e300


def compare(forests: Forests, user: str, served, ref: np.ndarray) -> tuple[int, float]:
    """``(wrong rows, widest gap over the tolerance)`` of one request.

    A request with no answer, or an answer of the wrong shape, counts
    every row wrong and its gap as ``MISSING_GAP``.  For votes the gap is
    0 or ``MISSING_GAP``; for means it is |served - reference| over the
    float32 bound (``Forests.tolerance``)."""
    if served is None or np.shape(served) != ref.shape:
        return len(ref), MISSING_GAP
    served = np.asarray(served, np.float64)
    diff = np.abs(served - ref)
    tol = forests.tolerance(user)
    wrong = int(np.sum(~(diff <= tol)))
    if forests.task == "classification":
        return wrong, (MISSING_GAP if wrong else 0.0)
    if not np.all(np.isfinite(served)):
        return wrong, MISSING_GAP
    gap = float(diff.max(initial=0.0)) / tol if tol > 0 else (
        MISSING_GAP if wrong else 0.0
    )
    return wrong, gap
