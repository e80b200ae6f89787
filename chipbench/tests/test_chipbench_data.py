"""The TPC-H generator copy and the NumPy reference, on the CPU."""
import collections
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import reference  # noqa: E402
from chipbench.data import tpch  # noqa: E402


@pytest.fixture(scope="module")
def table():
    return tpch.lineitem(0.01, 2**31 + 17)


def test_q1_groups_follow_from_the_spec_dates(table):
    keep = table["l_shipdate"] <= tpch.day("1998-09-02")
    flags = collections.Counter(
        (chr(r), chr(s)) for r, s in zip(table["l_returnflag"][keep],
                                          table["l_linestatus"][keep]))
    assert set(flags) == {("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")}
    share = flags[("N", "O")] / keep.sum()
    assert 0.45 < share < 0.55
    # R and A split the returned rows evenly; N/F is the narrow band of
    # rows shipped before CURRENTDATE and received after it
    assert flags[("R", "F")] == pytest.approx(flags[("A", "F")], rel=0.05)
    assert flags[("N", "F")] < 0.05 * keep.sum()


def test_q1_column_rules(table):
    ship, rec = table["l_shipdate"], table["l_receiptdate"]
    assert np.all((rec - ship >= 1) & (rec - ship <= 30))
    returned = rec <= tpch.CURRENTDATE
    rf = table["l_returnflag"]
    assert np.all(np.isin(rf[returned], [ord("R"), ord("A")]))
    assert np.all(rf[~returned] == ord("N"))
    assert np.all((table["l_linestatus"] == ord("O")) == (ship > tpch.CURRENTDATE))
    assert table["discount_pct"].min() == 0 and table["discount_pct"].max() == 10
    assert table["tax_pct"].min() == 0 and table["tax_pct"].max() == 8
    assert ship.min() >= tpch.STARTDATE + 1
    assert ship.max() <= tpch.ENDDATE - 151 + 121


def test_retailprice_follows_the_spec_formula(table):
    # (90000 + ((partkey / 10) mod 20001) + 100 * (partkey mod 1000)) / 100
    assert tpch.retailprice_cents(1) == 90100
    assert tpch.retailprice_cents(12345) == 90000 + 1234 + 34500
    assert tpch.retailprice_cents(200000) == 90000 + 20000 % 20001
    pk = table["l_partkey"]
    want = table["l_quantity"].astype(np.int64) * tpch.retailprice_cents(pk)
    assert np.array_equal(table["extendedprice_cents"], want)
    np.testing.assert_allclose(table["l_extendedprice"], want / 100, rtol=1e-7)


def test_the_same_seed_gives_the_same_table():
    a, b = tpch.lineitem(0.002, 7), tpch.lineitem(0.002, 7)
    c = tpch.lineitem(0.002, 8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["l_orderkey"], c["l_orderkey"])
    assert not np.array_equal(a["l_quantity"], c["l_quantity"])
    # every seed has the same line items: same rows, same group sizes
    assert len(a["l_orderkey"]) == len(c["l_orderkey"])
    sizes = lambda t: np.unique(t["l_orderkey"], return_counts=True)[1]
    assert np.array_equal(sizes(a), sizes(c))


@pytest.mark.parametrize("shards", [1, 4])
def test_every_seed_fills_each_block_with_the_same_keys(shards):
    block = 1000
    tables = [tpch.lineitem(0.005, s, block_rows=block, shards=shards)
              for s in (11, 12)]
    n_rows = len(tables[0]["l_orderkey"])
    ids = tpch.block_ids(n_rows, block, shards)
    assert np.all(np.diff(ids) >= 0)
    n_loc = -(-n_rows // shards)
    # a block never crosses a shard's first row
    assert len(set(ids[::n_loc])) == len(ids[::n_loc])
    a, b = (t["l_orderkey"] for t in tables)
    assert not np.array_equal(a, b)
    for blk in np.unique(ids):
        rows = ids == blk
        assert len(rows.nonzero()[0]) <= block
        assert np.array_equal(np.sort(a[rows]), np.sort(b[rows]))


def test_orderkeys_are_sparse_and_line_counts_uniform(table):
    keys, counts = np.unique(table["l_orderkey"], return_counts=True)
    assert len(keys) == tpch.orders_count(0.01)
    assert np.all((keys - 1) % 32 < 8)
    assert counts.min() == 1 and counts.max() == 7


def _brute(key_cols, values):
    acc = {}
    for i in range(len(key_cols[0])):
        k = tuple(int(c[i]) for c in key_cols)
        c, s = acc.get(k, (0, np.zeros(values.shape[1])))
        acc[k] = (c + 1, s + values[i].astype(np.float64))
    return sorted(acc.items())


def test_reference_matches_a_brute_force_loop():
    rng = np.random.default_rng(3)
    cols = [rng.integers(0, 3, 500).astype(np.uint32),
            rng.integers(0, 5, 500).astype(np.uint32)]
    vals = rng.random((500, 2)).astype(np.float32)
    ref = reference.reference(cols, vals)
    brute = _brute(cols, vals)
    assert [tuple(int(c[i]) for c in ref.keys) for i in range(len(ref.count))] \
        == [k for k, _ in brute]
    assert list(ref.count) == [c for _, (c, _) in brute]
    np.testing.assert_allclose(ref.sum, [s for _, (_, s) in brute], rtol=1e-12)
    np.testing.assert_allclose(ref.avg, ref.sum / ref.count[:, None])
    assert reference.compare(ref, ref) == dict(order=0, keys=0, counts=0,
                                               sum_rel=0.0)


def test_compare_counts_each_kind_of_fault():
    cols = [np.array([3, 1, 2, 1], np.uint32)]
    vals = np.array([[1.0], [2.0], [3.0], [4.0]], np.float32)
    want = reference.reference(cols, vals)
    got = reference.reference(cols, vals)
    got.sum = got.sum.copy()
    got.sum[0, 0] *= 1.5
    assert reference.compare(got, want)["sum_rel"] == pytest.approx(0.5)
    short = reference.reference([c[:3] for c in cols], vals[:3])
    r = reference.compare(short, want)
    assert r["counts"] > 0 and r["sum_rel"] > 0
    dup = reference.Relation(keys=[np.array([1, 1, 2, 3], np.uint32)],
                             count=np.array([1, 1, 1, 1]))
    r = reference.compare(dup, reference.reference(cols, None))
    assert r["order"] == 1 and r["keys"] > 0
