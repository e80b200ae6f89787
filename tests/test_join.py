"""Order-consuming merge join (PR 9): oracle parity for inner/semi/anti
over u32 AND u64 keys, structural no-sort/no-scatter jaxpr invariants,
Pallas probe parity, KeySpec-packed ``join_aggregate``, and exact parity
of the composed ``aggregate → merge_join → rollup`` pipeline against the
same operators run independently (stats included).

Capacities are kept small (≤ 512) on purpose: segmented-combine /
merge-join compiles scale badly on the CPU backend and tier-1 must stay
fast."""
import functools
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from _jaxpr_checks import assert_no_sort_no_scatter

import repro
from repro.core import merge_join as mj
from repro.core.join import join_aggregate, resolve_join_keys
from repro.core.schema import KeySpec, _check_join_compat
from repro.core.types import AggState, empty_key, key_dtype_context

RNG = np.random.default_rng(29)

CAP = 64  # one shared capacity ⇒ one jit cache entry per (how, dtype)


def make_state(uniq, counts=None, sums=None, capacity=CAP, dtype=np.uint32):
    """A sorted, duplicate-free, EMPTY-tailed AggState from unique keys."""
    uniq = np.asarray(uniq, dtype)
    assert len(np.unique(uniq)) == len(uniq)
    n = len(uniq)
    kd = np.dtype(dtype)
    keys = np.full(capacity, empty_key(kd), kd)
    keys[:n] = np.sort(uniq)
    order = np.argsort(uniq, kind="stable")
    count = np.zeros(capacity, np.int32)
    count[:n] = 1 if counts is None else np.asarray(counts, np.int32)[order]
    s = np.zeros((capacity, 1), np.float32)
    s[:n, 0] = (
        (keys[:n] % 97).astype(np.float32) if sums is None
        else np.asarray(sums, np.float32)[order]
    )
    inf = np.float32(np.inf)
    mn = np.full((capacity, 1), inf, np.float32)
    mx = np.full((capacity, 1), -inf, np.float32)
    mn[:n] = s[:n]
    mx[:n] = s[:n]
    return AggState(keys=jnp.asarray(keys), count=jnp.asarray(count),
                    sum=jnp.asarray(s), min=jnp.asarray(mn),
                    max=jnp.asarray(mx))


def _u64ify(keys):
    """Push u32-range keys above 2**32 so the hi lane actually varies."""
    return (np.asarray(keys, np.uint64) << np.uint64(33)) | np.uint64(5)


# ---------------------------------------------------------------------------
# merge_join oracle parity: how × dtype × edge scenarios
# ---------------------------------------------------------------------------

SCENARIOS = {
    "overlap": (np.array([1, 4, 7, 9, 12, 30]), np.array([2, 4, 9, 13, 30])),
    "disjoint": (np.array([1, 3, 5]), np.array([2, 4, 6])),
    "empty_left": (np.array([], np.int64), np.array([2, 4, 6])),
    "empty_right": (np.array([1, 3, 5]), np.array([], np.int64)),
    "both_empty": (np.array([], np.int64), np.array([], np.int64)),
    "all_equal": (np.array([17]), np.array([17])),
    "identical": (np.arange(40), np.arange(40)),
}


def _expected_keys(ka, kb, how):
    sa, sb = set(ka.tolist()), set(kb.tolist())
    keep = sorted(sa & sb) if how in ("inner", "semi") else sorted(sa - sb)
    return np.asarray(keep, np.uint64)


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64], ids=["u32", "u64"])
@pytest.mark.parametrize("how", mj.JOIN_HOWS)
def test_merge_join_matches_oracle(how, dtype):
    for name, (ka, kb) in SCENARIOS.items():
        ka = _u64ify(ka) if dtype is np.uint64 else np.asarray(ka, np.uint32)
        kb = _u64ify(kb) if dtype is np.uint64 else np.asarray(kb, np.uint32)
        with key_dtype_context(dtype):
            a, b = make_state(ka, dtype=dtype), make_state(kb, dtype=dtype)
            left, right = mj.merge_join(a, b, how=how, backend="xla")
        got = np.asarray(left.keys)
        got = got[got != empty_key(got.dtype)]
        exp = _expected_keys(ka, kb, how)
        np.testing.assert_array_equal(
            got.astype(np.uint64), exp, err_msg=f"{how}/{name}")
        # matched tail stays EMPTY-padded (OrderedIndex invariant)
        tail = np.asarray(left.keys)[len(exp):]
        assert (tail == empty_key(tail.dtype)).all(), f"{how}/{name}"
        if how == "inner":
            # right rows aligned on the SAME key vector, carrying b's planes
            np.testing.assert_array_equal(
                np.asarray(right.keys)[: len(exp)].astype(np.uint64), exp,
                err_msg=f"{how}/{name}")
            exp32 = exp.astype(dtype)
            np.testing.assert_allclose(
                np.asarray(right.sum)[: len(exp), 0],
                (exp32 % 97).astype(np.float32), err_msg=f"{how}/{name}")
        else:
            assert right is None


def test_merge_join_hot_key_products_fp32():
    """Hot groups: per-side counts up to 10^6 — |L|·|R| = 10^12 overflows
    int32, so the group-join product plane must be float."""
    ka = np.array([3, 8, 11], np.uint32)
    kb = np.array([8, 11, 20], np.uint32)
    a = make_state(ka, counts=[1_000_000, 1_000_000, 2])
    b = make_state(kb, counts=[1_000_000, 5, 9])
    left, right = mj.merge_join(a, b, how="inner")
    prods = mj.group_join_products(left, right)
    jc = np.asarray(prods["join_count"])[:2]
    np.testing.assert_allclose(jc, [1e12, 10.0])
    assert prods["join_count"].dtype == jnp.float32


def test_merge_join_zero_capacity():
    empty = make_state(np.array([], np.int64), capacity=0)
    some = make_state(np.array([1, 2], np.int64), capacity=4)
    for how in mj.JOIN_HOWS:
        left, right = mj.merge_join(empty, some, how=how)
        assert left.capacity == 0
        left, right = mj.merge_join(some, empty, how=how)
        got = np.asarray(left.keys)
        n_live = int((got != empty_key(got.dtype)).sum())
        assert n_live == (2 if how == "anti" else 0)


# ---------------------------------------------------------------------------
# structural invariant: the jaxpr has NO sort and NO scatter (u32 AND u64)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64], ids=["u32", "u64"])
@pytest.mark.parametrize("how", mj.JOIN_HOWS)
def test_merge_join_jaxpr_sort_and_scatter_free(how, dtype):
    ka = np.array([1, 4, 9], np.uint64)
    kb = np.array([4, 9, 13], np.uint64)
    if dtype is np.uint64:
        ka, kb = _u64ify(ka), _u64ify(kb)
    with key_dtype_context(dtype):
        a, b = make_state(ka, dtype=dtype), make_state(kb, dtype=dtype)
        fn = functools.partial(mj.merge_join, how=how, backend="xla")
        assert_no_sort_no_scatter(
            fn, a, b, context=f"in merge_join[{how}] over {np.dtype(dtype)}")


def test_compact_state_jaxpr_sort_and_scatter_free():
    st = make_state(np.array([2, 5, 9], np.int64))
    # punch interior EMPTY gaps like a mesh shard boundary would
    keys = np.asarray(st.keys).copy()
    keys[1] = empty_key(keys.dtype)
    st = AggState(keys=jnp.asarray(keys), count=st.count, sum=st.sum,
                  min=st.min, max=st.max)
    assert_no_sort_no_scatter(mj.compact_state, st, context="in compact_state")
    out = mj.compact_state(st)
    got = np.asarray(out.keys)
    np.testing.assert_array_equal(got[:2], [2, 9])
    assert (got[2:] == empty_key(got.dtype)).all()


# ---------------------------------------------------------------------------
# Pallas probe kernel parity (interpret mode off-TPU)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64], ids=["u32", "u64"])
def test_pallas_probe_matches_xla(dtype):
    from repro.kernels import ops as kops

    base_a = np.sort(RNG.choice(4000, 120, replace=False))
    base_b = np.sort(RNG.choice(4000, 90, replace=False))
    ka = _u64ify(base_a) if dtype is np.uint64 else base_a.astype(np.uint32)
    kb = _u64ify(base_b) if dtype is np.uint64 else base_b.astype(np.uint32)
    with key_dtype_context(dtype):
        # EMPTY tails as merge_join would pass them
        a = np.asarray(make_state(ka, capacity=128, dtype=dtype).keys)
        b = np.asarray(make_state(kb, capacity=128, dtype=dtype).keys)
        pos_p, hit_p = kops.join_probe(jnp.asarray(a), jnp.asarray(b))
        pos_x, hit_x = mj.join_probe_xla(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(hit_p), np.asarray(hit_x))
    hp, px, pp = np.asarray(hit_p), np.asarray(pos_x), np.asarray(pos_p)
    np.testing.assert_array_equal(pp[hp], px[hp])
    exp_hit = np.isin(a, b) & (a != empty_key(np.dtype(dtype)))
    np.testing.assert_array_equal(hp, exp_hit)


# ---------------------------------------------------------------------------
# join.py: KeySpec packing, dtype-mismatch guards (satellite #1)
# ---------------------------------------------------------------------------


def test_resolve_join_keys_dtype_mismatch_raises():
    with pytest.raises(TypeError, match="dtype mismatch"):
        resolve_join_keys(np.array([1], np.uint32), np.array([1], np.uint64))
    with pytest.raises(TypeError, match="integers"):
        resolve_join_keys(np.array([1.0]), np.array([1.0]))
    with pytest.raises(ValueError, match="non-negative"):
        resolve_join_keys(np.array([-1]), np.array([1]))


def test_resolve_join_keys_widens_not_truncates():
    """Seed regression: >32-bit keys must infer uint64, never truncate."""
    big = np.array([2**40, 2**40 + 1], np.uint64)
    lk, rk, kd = resolve_join_keys(big, big)
    assert kd == np.dtype(np.uint64)
    np.testing.assert_array_equal(lk, big)
    lk, rk, kd = resolve_join_keys(
        np.array([3, 7], np.uint32), np.array([7], np.uint32))
    assert kd == np.dtype(np.uint32)


def test_join_aggregate_u64_keyspec_matches_oracle():
    spec = KeySpec.of(store=30, sku=20)  # 50 bits → uint64 packing
    assert spec.key_dtype == np.uint64
    r = np.random.default_rng(11)
    n = 300
    left = {"store": r.integers(0, 6, n) + 2**28, "sku": r.integers(0, 5, n)}
    right = {"store": r.integers(0, 6, n) + 2**28, "sku": r.integers(0, 5, n)}
    lpay = r.normal(size=n).astype(np.float32)
    res, stats = join_aggregate(
        left, right, left_payload=lpay, by=spec, output_estimate=128)
    keys = np.asarray(res["keys"])
    live = keys != empty_key(keys.dtype)
    lk, rk = spec.pack(left), spec.pack(right)
    # oracle: per shared key, |L|·|R| and Σ_L payload·|R|
    exp = {}
    for k in np.unique(np.concatenate([lk, rk])):
        nl, nr = int((lk == k).sum()), int((rk == k).sum())
        exp[int(k)] = (nl * nr, lpay[lk == k].sum() * nr)
    got_k = keys[live]
    np.testing.assert_array_equal(np.sort(got_k), np.unique(np.concatenate([lk, rk])))
    for k, jc, sl in zip(got_k, np.asarray(res["join_count"])[live],
                         np.asarray(res["sum_left_pay"])[live, 0]):
        e_jc, e_sl = exp[int(k)]
        assert jc == e_jc, int(k)
        np.testing.assert_allclose(sl, e_sl, rtol=1e-5)
    spilled = stats.rows_spilled_run_generation + stats.rows_spilled_merge
    assert spilled <= 2 * n  # each mixed-stream row spills at most once


# ---------------------------------------------------------------------------
# schema composition: AggResult.merge_join / rollup / pipeline
# ---------------------------------------------------------------------------

SPEC = KeySpec.of(region=6, store=8)
N = 600


def _rel(seed, lo=0, hi=12):
    r = np.random.default_rng(seed)
    cols = {"region": r.integers(0, 4, N), "store": r.integers(lo, hi, N)}
    vals = r.normal(size=N).astype(np.float32)
    return cols, vals


def _aggregate(cols, vals):
    return repro.aggregate(cols, by=SPEC, values=vals, aggs=("count", "sum"),
                           output_estimate=256)


@pytest.fixture(scope="module")
def two_relations():
    (lc, lv), (rc, rv) = _rel(1), _rel(2, lo=6, hi=18)
    return _aggregate(lc, lv), _aggregate(rc, rv), (lc, lv), (rc, rv)


def _np_groupby(cols, vals):
    k = SPEC.pack(cols)
    out = {}
    for kk in np.unique(k):
        m = k == kk
        out[int(kk)] = (int(m.sum()), float(vals[m].sum()))
    return out


def test_schema_merge_join_matches_oracle(two_relations):
    L, R, (lc, lv), (rc, rv) = two_relations
    gl, gr = _np_groupby(lc, lv), _np_groupby(rc, rv)
    shared = sorted(set(gl) & set(gr))
    J = L.merge_join(R)
    rel = J.relation()
    packed = SPEC.pack({"region": rel["region"], "store": rel["store"]})
    np.testing.assert_array_equal(packed.astype(np.int64), shared)
    for i, k in enumerate(shared):
        assert rel["count_left"][i] == gl[k][0]
        assert rel["count_right"][i] == gr[k][0]
        np.testing.assert_allclose(rel["sum_left"][i], gl[k][1], rtol=1e-4)
        np.testing.assert_allclose(
            rel["join_count"][i], gl[k][0] * gr[k][0], rtol=1e-6)
        np.testing.assert_allclose(
            rel["sum_left_x_count_right"][i, 0], gl[k][1] * gr[k][0],
            rtol=1e-4)
    # cost model: consuming the established order means a ZERO sort term
    cm = J.plan["cost_model"]
    assert cm["inputs_sorted"] and cm["sort_rows"] == 0.0
    base = J.plan["cost_model_resort_baseline"]
    assert base["sort_rows"] > 0 and base["merge_join_ns"] > cm["merge_join_ns"]
    # stats combine BOTH sides' accounting
    assert J.stats.runs_generated == L.stats.runs_generated + R.stats.runs_generated
    assert J.stats.rows_emitted == L.stats.rows_emitted + R.stats.rows_emitted


def test_schema_semi_anti_partition(two_relations):
    L, R, (lc, lv), (rc, rv) = two_relations
    gl, gr = _np_groupby(lc, lv), _np_groupby(rc, rv)
    semi = L.merge_join(R, how="semi")
    anti = L.merge_join(R, how="anti")
    ks = SPEC.pack({k: v for k, v in semi.relation().items()
                    if k in ("region", "store")})
    ka = SPEC.pack({k: v for k, v in anti.relation().items()
                    if k in ("region", "store")})
    assert set(ks.tolist()) == set(gl) & set(gr)
    assert set(ka.tolist()) == set(gl) - set(gr)
    # semi + anti partition the left key set exactly
    assert semi.occupancy() + anti.occupancy() == L.occupancy()
    assert semi.right is None and semi.products is None


def test_join_key_layout_mismatch_raises(two_relations):
    L, R, _, _ = two_relations
    other_spec = KeySpec.of(region=6, store=30)  # 36 bits → uint64
    with pytest.raises(TypeError, match="dtype mismatch"):
        _check_join_compat(SPEC, other_spec)
    with pytest.raises(TypeError, match="layout mismatch"):
        _check_join_compat(KeySpec.of(a=6, b=8), KeySpec.of(a=8, b=6))
    with pytest.raises(ValueError, match="unknown join how"):
        L.merge_join(R, how="outer")


def test_join_rollup_exact(two_relations):
    """Rollup OF the join = the fine join's aggregates grouped by prefix
    (the products are sums over join pairs, hence additive)."""
    L, R, (lc, lv), (rc, rv) = two_relations
    gl, gr = _np_groupby(lc, lv), _np_groupby(rc, rv)
    shared = sorted(set(gl) & set(gr))
    J = L.merge_join(R)
    tiers = J.rollup()
    assert set(tiers) == {("region", "store"), ("region",), ()}
    # per-region: Σ over fine matched keys of |L|·|R|
    shift = SPEC.shift_of("region")
    exp_by_region = {}
    for k in shared:
        r = k >> shift
        exp_by_region[r] = exp_by_region.get(r, 0.0) + gl[k][0] * gr[k][0]
    rel = tiers[("region",)].relation()
    got = dict(zip(rel["region"].tolist(), rel["join_count"].tolist()))
    assert got == pytest.approx(exp_by_region)
    # grand total joins the full cardinality
    total = tiers[()].relation()
    np.testing.assert_allclose(
        total["join_count"], [sum(exp_by_region.values())])
    # left/right packets roll up alongside
    np.testing.assert_allclose(
        total["count_left"], [sum(gl[k][0] for k in shared)])
    for t in tiers.values():
        assert t.plan["rollup"]["sorts"] == 0


def test_pipeline_composes_without_resort(two_relations):
    L, R, (lc, lv), _ = two_relations
    out = repro.pipeline([
        ("aggregate", dict(columns=lc, by=SPEC, values=lv,
                           aggs=("count", "sum"), output_estimate=256)),
        ("merge_join", {"right": R}),
        ("rollup", {}),
    ])
    assert isinstance(out, dict)
    manual = L.merge_join(R).rollup()
    for names, tier in out.items():
        pipe_block = tier.plan["pipeline"]
        assert pipe_block == {
            "stages": ["aggregate", "merge_join[inner]", "rollup"],
            "source_sorts": 2,
            "re_sorts": 0,
        }
        # exact parity with the independently composed operators
        got, exp = tier.relation(), manual[names].relation()
        assert set(got) == set(exp)
        for col in got:
            np.testing.assert_allclose(got[col], exp[col], rtol=1e-6,
                                       err_msg=f"{names}/{col}")
        assert tier.stats == manual[names].stats


def test_sorted_by_threads_through(two_relations):
    L, R, _, _ = two_relations
    assert L.sorted_by == {"columns": ("region", "store"), "prefix_len": 2,
                           "key_dtype": "uint32"}
    J = L.merge_join(R)
    assert J.sorted_by == L.sorted_by
    assert J.plan["sorted_by"] == [L.sorted_by, R.sorted_by]


# ---------------------------------------------------------------------------
# mesh-sharded merge join (8 fake devices, subprocess per dry-run contract)
# ---------------------------------------------------------------------------

ENV = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH="src")


def run_py(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_mesh_merge_join_matches_local():
    run_py("""
        import jax, numpy as np
        import repro

        mesh = jax.make_mesh((8,), ("data",))
        spec = repro.KeySpec.of(region=6, store=8)
        n = 600

        def rel(seed, lo, hi):
            r = np.random.default_rng(seed)
            cols = {"region": r.integers(0, 4, n),
                    "store": r.integers(lo, hi, n)}
            return repro.aggregate(cols, by=spec,
                                   values=r.normal(size=n).astype(np.float32),
                                   aggs=("count", "sum"), output_estimate=256)

        L, R = rel(1, 0, 12), rel(2, 6, 18)
        ref = L.merge_join(R).relation()
        with mesh:
            J = L.merge_join(R, mesh=mesh, mesh_axis="data")
        mp = J.plan["mesh"]
        assert (mp["axis"], mp["world"]) == ("data", 8)
        assert mp["exchange"]["rows_exchanged"] == J.stats.rows_exchanged
        assert J.stats.rows_exchanged > 0
        got = J.relation()
        o = np.lexsort((got["store"], got["region"]))
        assert set(got) == set(ref)
        for col in ref:
            np.testing.assert_allclose(
                np.asarray(got[col])[o], ref[col], rtol=1e-5, err_msg=col)
        # rollup off the mesh-sharded join still matches the local one
        tier = J.rollup(levels=[0])[()].relation()
        ref_tier = L.merge_join(R).rollup(levels=[0])[()].relation()
        np.testing.assert_allclose(tier["join_count"], ref_tier["join_count"])
        # anti join: mesh and local agree on the surviving key set
        with mesh:
            A = L.merge_join(R, how="anti", mesh=mesh, mesh_axis="data")
        ra = L.merge_join(R, how="anti").relation()
        ga = A.relation()
        oa = np.lexsort((ga["store"], ga["region"]))
        np.testing.assert_array_equal(np.asarray(ga["region"])[oa], ra["region"])
        np.testing.assert_array_equal(np.asarray(ga["store"])[oa], ra["store"])
        print("mesh merge join OK", len(got["region"]))
    """)
