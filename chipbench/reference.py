"""Plain NumPy reference for ``GROUP BY`` with count, sum and avg, and
the comparison that decides ``correct``.

Shares no code with ``repro``.  Key columns are uint32 and combine
major-first into one uint64; sums are taken in float64 over the same
float32 values the engine is given.  :func:`control` is the reference
in the nearest precision below the engine's float32 value planes:
values and results held in bfloat16 (summed exactly in between, the
most favourable way to compute in bfloat16).
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np


@dataclasses.dataclass
class Relation:
    """Key columns (major first), ``count`` (G,), ``sum`` and ``avg``
    (G, V); ``sum``/``avg`` may be None when not requested."""

    keys: list[np.ndarray]
    count: np.ndarray
    sum: np.ndarray | None = None
    avg: np.ndarray | None = None


def pack(key_cols) -> np.ndarray:
    k = np.zeros(len(key_cols[0]), np.uint64)
    for c in key_cols:
        k = (k << np.uint64(32)) | np.asarray(c).astype(np.uint64)
    return k


def reference(key_cols, values) -> Relation:
    """Sorted distinct key tuples with count, float64 sum and avg."""
    k = pack(key_cols)
    uk, inv = np.unique(k, return_inverse=True)
    keys = [((uk >> np.uint64(32 * (len(key_cols) - 1 - i)))
             & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            for i in range(len(key_cols))]
    count = np.bincount(inv, minlength=len(uk))
    if values is None:
        return Relation(keys, count)
    values = np.asarray(values, np.float64).reshape(len(k), -1)
    sums = np.stack([np.bincount(inv, weights=values[:, j], minlength=len(uk))
                     for j in range(values.shape[1])], axis=1)
    return Relation(keys, count, sums, sums / count[:, None])


def _bf16(x):
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)


def control(key_cols, values) -> Relation:
    """The reference with bfloat16 value planes."""
    ref = reference(key_cols, None if values is None else _bf16(values))
    if ref.sum is not None:
        ref.sum = _bf16(ref.sum)
        ref.avg = _bf16(ref.avg)
    return ref


def compare(got: Relation, want: Relation) -> dict[str, float]:
    """The numbers compared, each 0 for an exact answer:

    * ``order``: adjacent output rows not strictly increasing by key;
    * ``keys``: output rows whose key differs from the reference's, plus
      the difference in row count;
    * ``counts``: rows whose count differs;
    * ``sum_rel``: the largest relative error of a sum or avg, against
      the reference (where the keys line up)."""
    k = pack(got.keys) if len(got.count) else np.zeros(0, np.uint64)
    order = int(np.sum(k[1:] <= k[:-1]))
    wk = pack(want.keys)
    m = min(len(k), len(wk))
    keys = int(np.sum(k[:m] != wk[:m])) + abs(len(k) - len(wk))
    same = k[:m] == wk[:m]
    counts = int(np.sum(np.asarray(got.count)[:m][same]
                        != want.count[:m][same])) + (len(k) != len(wk))
    rel = 0.0
    for g, w in ((got.sum, want.sum), (got.avg, want.avg)):
        if w is None:
            continue
        if g is None:
            return dict(order=order, keys=keys, counts=counts,
                        sum_rel=float("inf"))
        g = np.asarray(g, np.float64).reshape(len(k), w.shape[1])[:m][same]
        w = w[:m][same]
        if g.size:
            err = np.abs(g - w) / np.maximum(np.abs(w), np.finfo(np.float32).tiny)
            rel = max(rel, float(err.max()))
    return dict(order=order, keys=keys, counts=counts, sum_rel=rel)
