"""The work behind every roofline share: a lower bound on what one served
batch must move and compute, whatever the implementation.

* Bytes: each distinct node the batch's rows visit, read once at the
  narrowest width that holds its fields (an internal node: its leaf flag,
  feature id and threshold bin; a leaf: its flag and its class id, or its
  index into the user's table of fit values, plus that table once at
  float32); each row once at one byte per binned feature; each answer
  once (one byte for a class, four for a float32 mean).
* Operations: node visits, one per node on every (tree, row) path.
* Time: the larger of bytes over the chip's memory bandwidth and
  operations over its highest operation rate, naming which bound binds.

A one-hot, a heap pad, a re-read chunk or a padded tree never counts: the
count is of the work, not of one way to do it, so later kernels are
judged against the same number.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def bits(n_values: int) -> int:
    """Bits that hold ``n_values`` distinct values (at least 1)."""
    return max(1, math.ceil(math.log2(max(n_values, 2))))


def node_bytes(n_features: int, n_bins: int, n_leaf_values: int) -> tuple[int, int]:
    """``(internal, leaf)`` bytes of one node at its narrowest width."""
    internal = math.ceil((1 + bits(n_features) + bits(n_bins)) / 8)
    leaf = math.ceil((1 + bits(n_leaf_values)) / 8)
    return internal, leaf


@dataclass
class Bound:
    bytes: float
    ops: float
    seconds: float
    binds: str  # "bytes" or "ops"


def lower_bound(
    *, internal: int, leaves: int, visits: int, n_rows: int,
    n_features: int, n_bins: int, n_leaf_values: int, table_values: int,
    answer_bytes: int, peaks: dict,
) -> Bound:
    """The least time one batch can take on a chip with ``peaks``.

    ``internal``/``leaves``: distinct nodes visited; ``visits``: node
    visits; ``table_values``: float32 fit values the batch's users keep
    (0 for classification)."""
    w_int, w_leaf = node_bytes(n_features, n_bins, n_leaf_values)
    moved = (
        internal * w_int + leaves * w_leaf + 4 * table_values
        + n_rows * n_features + n_rows * answer_bytes
    )
    t_bytes = moved / peaks["hbm_bytes_per_s"]
    t_ops = visits / peaks["ops_per_s"]
    if t_bytes >= t_ops:
        return Bound(float(moved), float(visits), t_bytes, "bytes")
    return Bound(float(moved), float(visits), t_ops, "ops")


def load_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS_FILE}"
        ) from None
