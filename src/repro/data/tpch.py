"""TPC-H ``lineitem`` key and quantity columns, generated from a seed.

Follows the TPC-H specification v3.0.1, §4.2.3, for the columns the
grouping queries here read:

* ``orders`` holds ``1_500_000 × SF`` rows; ``o_orderkey`` is sparse —
  only the first 8 key values of every 32 are populated;
* each order has 1–7 line items, uniformly;
* ``l_quantity`` is uniform in 1..50;
* ``l_partkey`` is uniform in 1..``200_000 × SF``, and ``l_suppkey`` is
  one of that part's four suppliers, by the spec's formula over
  ``S = 10_000 × SF`` suppliers:
  ``(partkey + i · (S/4 + (partkey − 1) // S)) mod S + 1``, ``i`` in 0..3.

``dbgen`` emits line items clustered by order key.  ``shuffle=True``
permutes the rows with the same seed, so the group key arrives in no
order — the general input of a sort-based aggregation (clustered input is
the in-stream case).  Nothing is downloaded; every column is drawn in
bulk with NumPy.
"""
from __future__ import annotations

import numpy as np

ORDERS_PER_SF = 1_500_000
PARTS_PER_SF = 200_000
SUPPLIERS_PER_SF = 10_000


def sparse_orderkey(index: np.ndarray) -> np.ndarray:
    """The ``index``-th populated order key (0-based): the first 8 of
    every 32 key values, starting at 1."""
    index = np.asarray(index, np.int64)
    return (index // 8) * 32 + index % 8 + 1


def lineitem(sf: float, seed: int, *, shuffle: bool = True) -> dict[str, np.ndarray]:
    """``l_orderkey``, ``l_suppkey`` (uint32) and ``l_quantity`` (float32,
    integral) columns of ``lineitem`` at scale factor ``sf``."""
    n_orders = int(round(ORDERS_PER_SF * sf))
    if n_orders <= 0:
        raise ValueError(f"scale factor {sf} gives no orders")
    n_supp = max(1, int(round(SUPPLIERS_PER_SF * sf)))
    n_part = max(1, int(round(PARTS_PER_SF * sf)))
    rng = np.random.default_rng(seed)
    lines = rng.integers(1, 8, size=n_orders)
    orderkey = np.repeat(sparse_orderkey(np.arange(n_orders)), lines)
    n = orderkey.shape[0]
    partkey = rng.integers(1, n_part + 1, size=n, dtype=np.int64)
    i = rng.integers(0, 4, size=n, dtype=np.int64)
    suppkey = (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1
    quantity = rng.integers(1, 51, size=n).astype(np.float32)
    cols = {
        "l_orderkey": orderkey.astype(np.uint32),
        "l_suppkey": suppkey.astype(np.uint32),
        "l_quantity": quantity,
    }
    if shuffle:
        perm = rng.permutation(n)
        cols = {k: v[perm] for k, v in cols.items()}
    return cols


def orders_count(sf: float) -> int:
    """Rows of ``orders`` (= groups of ``lineitem`` by ``l_orderkey``)."""
    return int(round(ORDERS_PER_SF * sf))
