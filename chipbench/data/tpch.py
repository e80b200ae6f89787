"""TPC-H ``lineitem`` columns for Q1 and Q18, generated from a seed.

Follows the TPC-H specification v3.0.1, §4.2.3 (a copy of
``repro/data/tpch.py``, kept here so that the benchmark's data cannot
change with the program, and extended with Q1's columns):

* ``orders`` holds ``1_500_000 × SF`` rows; ``o_orderkey`` is sparse:
  only the first 8 key values of every 32 are populated;
* ``o_orderdate`` is uniform in [STARTDATE, ENDDATE − 151 days];
* each order has 1–7 line items, uniformly;
* ``l_quantity`` is uniform in 1..50;
* ``l_partkey`` is uniform in 1..``200_000 × SF``; ``l_suppkey`` is one of
  that part's four suppliers over ``S = 10_000 × SF`` suppliers:
  ``(partkey + i · (S/4 + (partkey − 1) // S)) mod S + 1``, ``i`` in 0..3;
* ``p_retailprice = (90000 + (partkey / 10) mod 20001
  + 100 · (partkey mod 1000)) / 100`` and
  ``l_extendedprice = l_quantity · p_retailprice``;
* ``l_discount`` is uniform in {0.00, …, 0.10}, ``l_tax`` in
  {0.00, …, 0.08};
* ``l_shipdate = o_orderdate + [1, 121]`` days,
  ``l_receiptdate = l_shipdate + [1, 30]`` days;
* ``l_returnflag`` is R or A (even odds) where
  ``l_receiptdate <= CURRENTDATE``, else N; ``l_linestatus`` is O where
  ``l_shipdate > CURRENTDATE``, else F.

Dates are days since 1970-01-01 (int32).  Money is generated in cents,
exactly, and handed out as float32 (the engine's value planes).  Flags
are their ASCII codes (uint32).  Every column is drawn in bulk with NumPy.

Row order.  ``dbgen`` emits line items clustered by order key; here the
group key arrives in no order, the general input of a sort-based
aggregation.  Which line items there are (the line counts) and how the
rows are dealt to blocks of ``block_rows`` are one fixed draw
(``FIXED_SEED``); the seed orders the rows inside each block and draws
every value.  The engine sorts each input batch before it merges it, so
with blocks equal to its batches every seed hands it the same batches,
and run generation makes the same runs, spills and pages, in another
arrival order.  Drawn per seed instead, the block contents changed the
wide merge's page count, and with it the query time, from seed to seed.
With ``shards`` the blocks start afresh at each shard's first row
(``ceil(rows / shards)`` rows a shard), as the engine's per-shard batches do.
"""
from __future__ import annotations

import numpy as np

ORDERS_PER_SF = 1_500_000
PARTS_PER_SF = 200_000
SUPPLIERS_PER_SF = 10_000
FIXED_SEED = 0


def day(iso: str) -> int:
    """Days since 1970-01-01 of an ISO date."""
    return int(np.datetime64(iso, "D").astype(np.int64))


STARTDATE = day("1992-01-01")
CURRENTDATE = day("1995-06-17")
ENDDATE = day("1998-12-31")


def sparse_orderkey(index: np.ndarray) -> np.ndarray:
    """The ``index``-th populated order key (0-based): the first 8 of
    every 32 key values, starting at 1."""
    index = np.asarray(index, np.int64)
    return (index // 8) * 32 + index % 8 + 1


def retailprice_cents(partkey: np.ndarray) -> np.ndarray:
    """``p_retailprice`` in cents, by the spec's formula."""
    partkey = np.asarray(partkey, np.int64)
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def orders_count(sf: float) -> int:
    """Rows of ``orders`` (= groups of ``lineitem`` by ``l_orderkey``)."""
    return int(round(ORDERS_PER_SF * sf))


def block_ids(n: int, block_rows: int | None, shards: int = 1) -> np.ndarray:
    """The block of each row position (ascending)."""
    if block_rows is None:
        return np.zeros(n, np.int64)
    n_loc = -(-n // shards)
    pos = np.arange(n)
    shard, local = pos // n_loc, pos % n_loc
    return shard * (n_loc // block_rows + 1) + local // block_rows


def lineitem(sf: float, seed: int, *, block_rows: int | None = None,
             shards: int = 1) -> dict[str, np.ndarray]:
    """The ``lineitem`` columns Q1 and Q18 read, at scale factor ``sf``.

    Keys and flags are uint32, dates int32 days, ``l_quantity`` float32
    (integral), ``l_extendedprice`` float32 dollars and ``l_discount`` /
    ``l_tax`` float32 fractions; ``extendedprice_cents``, ``discount_pct``
    and ``tax_pct`` are the same values as exact int64.  ``block_rows``
    None makes the whole table one block."""
    n_orders = orders_count(sf)
    if n_orders <= 0:
        raise ValueError(f"scale factor {sf} gives no orders")
    n_supp = max(1, int(round(SUPPLIERS_PER_SF * sf)))
    n_part = max(1, int(round(PARTS_PER_SF * sf)))
    fixed = np.random.default_rng(FIXED_SEED)
    lines = fixed.integers(1, 8, size=n_orders)
    rng = np.random.default_rng(seed)
    orderkey = np.repeat(sparse_orderkey(np.arange(n_orders)), lines)
    n = orderkey.shape[0]
    partkey = rng.integers(1, n_part + 1, size=n, dtype=np.int64)
    i = rng.integers(0, 4, size=n, dtype=np.int64)
    suppkey = (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1
    quantity = rng.integers(1, 51, size=n)
    orderdate = np.repeat(
        rng.integers(STARTDATE, ENDDATE - 151 + 1, size=n_orders), lines)
    shipdate = orderdate + rng.integers(1, 122, size=n)
    receiptdate = shipdate + rng.integers(1, 31, size=n)
    returned = np.where(rng.integers(0, 2, size=n) == 0, ord("R"), ord("A"))
    returnflag = np.where(receiptdate <= CURRENTDATE, returned, ord("N"))
    linestatus = np.where(shipdate > CURRENTDATE, ord("O"), ord("F"))
    discount = rng.integers(0, 11, size=n)
    tax = rng.integers(0, 9, size=n)
    extended = quantity * retailprice_cents(partkey)
    cols = {
        "l_orderkey": orderkey.astype(np.uint32),
        "l_partkey": partkey.astype(np.uint32),
        "l_suppkey": suppkey.astype(np.uint32),
        "l_quantity": quantity.astype(np.float32),
        "l_extendedprice": (extended / 100).astype(np.float32),
        "l_discount": (discount / 100).astype(np.float32),
        "l_tax": (tax / 100).astype(np.float32),
        "l_shipdate": shipdate.astype(np.int32),
        "l_receiptdate": receiptdate.astype(np.int32),
        "l_returnflag": returnflag.astype(np.uint32),
        "l_linestatus": linestatus.astype(np.uint32),
        "extendedprice_cents": extended,
        "discount_pct": discount,
        "tax_pct": tax,
    }
    dealt = fixed.permutation(n)
    inside = np.lexsort((rng.random(n), block_ids(n, block_rows, shards)))
    rows = dealt[inside]
    return {k: v[rows] for k, v in cols.items()}
