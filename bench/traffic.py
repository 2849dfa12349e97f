"""The one traffic generator.  Every mix under ``bench/traffic/`` is a data
file that this module reads; a new mix is a new file, never new code.

A mix names its loop (``"loop": "closed"``, the one kind so far): one
client, sending its next call only when the last one returned.  Call ``k``
holds ``users_per_call`` requests of ``rows_per_user`` rows, for users taken
in a fixed rotation over the deployment's user list.

Rows are fresh binned rows drawn from the seed: ``RowSource`` makes one pool
of random bins in set-up and hands out consecutive slices of it.
"""
from __future__ import annotations

import math

import numpy as np

#: bytes of random bins one run draws in set-up; calls consume the pool in
#: order and, once through it, start again one bin further on, so no two
#: calls of a run get the same rows
POOL_BYTES = 1 << 24


class RowSource:
    """Fresh (n, d) int32 rows of bins in ``[0, n_bins)``, drawn from the
    seed.  ``take`` returns the rows and the pool offset they came from, so
    the check can draw the same rows again after the window."""

    def __init__(self, seed: int, n_features: int, n_bins: int,
                 pool_bytes: int = POOL_BYTES) -> None:
        if not 0 < n_bins <= 256:
            raise ValueError(f"n_bins={n_bins} does not fit one byte")
        rng = np.random.default_rng([seed, 1])
        self.d = n_features
        self.pool = rng.integers(0, n_bins, pool_bytes, dtype=np.uint8)
        self._pos = 0
        self._lap = 0

    def take(self, n: int) -> tuple[np.ndarray, int]:
        size = n * self.d
        if size > len(self.pool):
            raise ValueError(f"{n} rows exceed the row pool")
        if self._pos + size > len(self.pool):
            self._lap += 1
            self._pos = self._lap % self.d
        start = self._pos
        self._pos += size
        return self.rows_at(start, n), start

    def rows_at(self, start: int, n: int) -> np.ndarray:
        block = self.pool[start:start + n * self.d]
        return block.reshape(n, self.d).astype(np.int32)


def closed_call(traffic: dict, users: list[str], k: int) -> list[tuple[str, int]]:
    """``(user, n_rows)`` pairs of closed-loop call ``k``."""
    upc = int(traffic["users_per_call"])
    first = k * upc
    return [
        (users[(first + i) % len(users)], int(traffic["rows_per_user"]))
        for i in range(upc)
    ]


def closed_period(traffic: dict, users: list[str]) -> int:
    """Number of calls after which the rotation repeats its compositions."""
    upc = int(traffic["users_per_call"])
    return len(users) // math.gcd(len(users), upc)
