"""Mesh-sharded pipeline tests (8 fake CPU devices via subprocess — the
main test process must keep seeing 1 device, per the dry-run contract).

Parity contract: at every world size (now through 32), for every
run-generation policy and both key dtypes, the sharded program's relation
(keys, counts, sums) is EXACTLY the single-device pipeline's, and its
reduced SpillStats equal the shard-wise reduction of per-shard
single-device references (``SpillStats.reduce_shards``) — the exchange
itself adds only its own accounting (``rows_exchanged`` plus the
capacity-bounded quota fields ``exchange_quota`` / ``exchange_max_fill``
/ ``exchange_retries``).  Plus: Zipf-skewed key draws at world 32, edge
inputs (empty / one hot key / skewed key band), exchange edge geometry
(quota=1 with an empty shard; every row aimed at one peer), the
retry-once ladder firing exactly once under a deliberately small quota,
and a transfer-guard proof that the whole mesh program still performs
exactly one stats readback.
"""
import os
import subprocess
import sys
import textwrap

import pytest


def run_py(code: str, devices: int = 8):
    env = dict(os.environ, PYTHONPATH="src",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


_PARITY = """
    import jax, numpy as np
    from repro.core import pipeline
    from repro.core.types import ExecConfig, SpillStats, empty_key
    from repro.core.operators import validate_against_oracle

    WORLD = {world}
    CFG = ExecConfig(memory_rows=256, page_rows=32, fanin=4, batch_rows=64)
    N = 4096  # divisible by every world size
    rng = np.random.default_rng(7)
    mesh = jax.make_mesh((WORLD,), ("data",))

    def strip(st):
        k = np.asarray(st.keys)
        v = k != empty_key(k.dtype)
        return k[v], np.asarray(st.count)[v], np.asarray(st.sum)[v]

    for kd in (np.uint32, np.uint64):
        for policy in ("traditional", "inrun_dedup", "early_agg", "rs"):
            keys = rng.integers(0, 1200, N).astype(kd)
            if kd == np.uint64:
                keys = keys << np.uint64(30)  # spread past 32 bits
            pay = rng.normal(size=(N, 1)).astype(np.float32)
            st, stats = pipeline.insort_aggregate_device(
                keys, pay, CFG, policy=policy, mesh=mesh)
            validate_against_oracle(st, keys, pay)
            gk, gc, gs = strip(st)
            assert np.all(gk[:-1] < gk[1:])  # globally sorted, unique
            # exact relation parity with the single-device program
            st1, _ = pipeline.insort_aggregate_device(
                keys, pay, CFG, policy=policy)
            rk, rc, rs_ = strip(st1)
            np.testing.assert_array_equal(gk, rk)
            np.testing.assert_array_equal(gc, rc)
            np.testing.assert_allclose(gs, rs_, rtol=2e-4, atol=2e-3)
            # exact stats parity: the sharded accounting is the reduction
            # of per-shard single-device references; the exchange adds
            # only rows_exchanged
            n_loc = N // WORLD
            refs = [pipeline.insort_aggregate_device(
                        keys[i*n_loc:(i+1)*n_loc], pay[i*n_loc:(i+1)*n_loc],
                        CFG, policy=policy)[1] for i in range(WORLD)]
            want = SpillStats.reduce_shards(refs).as_dict()
            got = stats.as_dict()
            assert got.pop("rows_exchanged") > 0
            want.pop("rows_exchanged")
            # exchange accounting exists only on the sharded side: the
            # quota is capacity-bounded and the sampled cuts never
            # overfilled it (no retry)
            assert got.pop("exchange_retries") == 0 == want.pop("exchange_retries")
            quota, fill = got.pop("exchange_quota"), got.pop("exchange_max_fill")
            assert 0 < fill <= quota
            want.pop("exchange_quota"); want.pop("exchange_max_fill")
            assert got == want, (policy, np.dtype(kd).name, got, want)
            print("OK", np.dtype(kd).name, policy)
    print("sharded parity OK at world", WORLD)
"""


@pytest.mark.parametrize("world,devices", ((1, 8), (2, 8), (8, 8), (32, 32)))
def test_sharded_pipeline_matches_single_device(world, devices):
    run_py(_PARITY.format(world=world), devices=devices)


_ZIPF = """
    import jax, numpy as np
    from repro.core import pipeline
    from repro.core.types import ExecConfig, SpillStats, empty_key
    from repro.core.operators import validate_against_oracle

    WORLD = 32
    CFG = ExecConfig(memory_rows=256, page_rows=32, fanin=4, batch_rows=64)
    N = 8192
    kd = np.{dtype}
    mesh = jax.make_mesh((WORLD,), ("data",))
    rng = np.random.default_rng(23)

    def zipf(n, domain, s):
        ranks = np.arange(1, domain + 1, dtype=np.float64)
        p = ranks ** -float(s)
        return rng.choice(domain, size=n, p=p / p.sum())

    def strip(st):
        k = np.asarray(st.keys)
        v = k != empty_key(k.dtype)
        return k[v], np.asarray(st.count)[v], np.asarray(st.sum)[v]

    for s in (0.0, 1.2):
        for policy in ("traditional", "inrun_dedup", "early_agg", "rs"):
            keys = zipf(N, 2048, s).astype(kd)
            if kd == np.uint64:
                keys = keys << np.uint64(30)
            pay = rng.normal(size=(N, 1)).astype(np.float32)
            st, stats = pipeline.insort_aggregate_device(
                keys, pay, CFG, policy=policy, mesh=mesh)
            validate_against_oracle(st, keys, pay)
            gk, gc, gs = strip(st)
            # the single-device reference needs a wider merge index at
            # s=1.2: merging duplicate-laden traditional runs keeps every
            # copy of the frontier key resident, and the hottest key has
            # ~0.2*N rows.  (The sharded program doesn't: per-shard
            # hot-key copies are ~N/world * 0.2, and the exchange merges
            # per-shard DEDUPED fragments.)
            st1, _ = pipeline.insort_aggregate_device(
                keys, pay, CFG, policy=policy, index_rows=2048)
            rk, rc, rs_ = strip(st1)
            np.testing.assert_array_equal(gk, rk)
            np.testing.assert_array_equal(gc, rc)
            np.testing.assert_allclose(gs, rs_, rtol=2e-4, atol=2e-3)
            # shuffle-volume oracle: every shard fully dedups its slice
            # locally, then puts each surviving row on the wire exactly
            # once — so rows_exchanged is the sum of per-slice distinct
            # key counts
            n_loc = N // WORLD
            want_sent = sum(
                len(np.unique(keys[i * n_loc:(i + 1) * n_loc]))
                for i in range(WORLD))
            assert stats.rows_exchanged == want_sent
            # even at s=1.2 the sampled+strictified cuts keep every send
            # segment inside the capacity-derived quota: no retry fired
            assert stats.exchange_retries == 0
            assert 0 < stats.exchange_max_fill <= stats.exchange_quota
            print("OK", np.dtype(kd).name, policy, "s=", s)
    print("zipf parity OK")
"""


@pytest.mark.parametrize("dtype", ("uint32", "uint64"))
def test_sharded_zipf_skew_world32(dtype):
    run_py(_ZIPF.format(dtype=dtype), devices=32)


def test_exchange_footprint_capacity_bounded():
    """The §4 discipline for the exchange: per-shard footprint is
    O(world·quota + world·page) with quota ≈ 2·capacity/world, so at a
    FIXED per-shard capacity the footprint is ~flat in world — growing
    the world 8 → 32 must cost ≤ 1.3×.  (Under the old quota=capacity
    scheme the same ratio was exactly 4×.)  Pure accounting: the numbers
    come from the same helpers the mesh pipeline derives its buffer
    shapes from, so this is the shipped geometry, not a model of it."""
    from repro.distributed import groupby as gb

    n_loc = 2048  # rows per shard, fixed as the world grows
    cap = n_loc  # worst case: every local row a distinct key
    foot = {}
    for world in (8, 32):
        quota = gb.default_exchange_quota(cap, world)
        page = gb.exchange_page_rows(quota, 32)
        assert quota * world >= cap  # lossless when cuts are balanced
        assert quota % page == 0
        foot[world] = gb.exchange_footprint_rows(world, quota, 32)
    ratio = foot[32] / foot[8]
    assert ratio <= 1.3, (foot, ratio)
    # and the old scheme really was the world-proportional one
    old = {w: 2 * w * cap + (w + 2) * 32 for w in (8, 32)}
    assert old[32] / old[8] > 3.5


def test_exchange_edge_geometry():
    """quota=1 at world=2 with an empty shard, and an all-rows-to-one-
    peer split: the exchange must pad honestly, flag overfill instead of
    corrupting, and keep parity."""
    run_py("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import groupby as gb
        from repro.core.types import EMPTY, empty_state

        mesh = jax.make_mesh((2,), ("data",))

        def mk(keys_np, capacity):
            # a sorted, duplicate-free, EMPTY-padded local state
            keys = np.full(capacity, EMPTY, np.uint32)
            keys[:len(keys_np)] = np.sort(np.asarray(keys_np, np.uint32))
            cnt = (keys != EMPTY).astype(np.int32)
            return dataclasses.replace(empty_state(capacity, 1),
                                       keys=jnp.asarray(keys),
                                       count=jnp.asarray(cnt))

        def run_exchange(local_a, local_b, quota, inner=None):
            cap = local_a.capacity

            def f(st):
                recv, sent, dropped, fill = gb.exchange_sorted_fragments(
                    st, "data", 2, quota=quota, inner_cuts=inner)
                return (recv,
                        jax.lax.psum(sent, "data"),
                        jax.lax.pmax(dropped, "data"),
                        jax.lax.pmax(fill, "data"))

            spec = P("data")
            stacked = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b]), local_a, local_b)
            fn = jax.jit(jax.shard_map(f, mesh=mesh,
                                       in_specs=(spec,),
                                       out_specs=(spec, P(), P(), P())))
            return fn(stacked)

        # --- quota=1, world=2, shard B empty: one row per peer range ---
        a = mk([3, 900000], 4)     # one key below the cut, one above
        b = mk([], 4)
        inner = jnp.asarray([1000], jnp.uint32)  # cut: [0,1000) | [1000,top]
        recv, sent, dropped, fill = run_exchange(a, b, 1, inner)
        assert not bool(dropped) and int(sent) == 2 and int(fill) == 1
        rk = np.asarray(recv.keys).reshape(2, 2)  # (shard, world*quota=2)
        # owner 0 got key 3 from shard A and EMPTY padding from B;
        # owner 1 got 900000 from A and EMPTY from B
        assert rk[0, 0] == 3 and rk[0, 1] == EMPTY
        assert rk[1, 0] == 900000 and rk[1, 1] == EMPTY

        # --- every row aimed at one peer: fill == occupancy, and a
        # quota below it trips send_dropped (the retryable signal) ---
        a = mk([10, 11, 12], 4)
        b = mk([13, 14, 15], 4)
        inner = jnp.asarray([1 << 20], jnp.uint32)  # everything -> owner 0
        recv, sent, dropped, fill = run_exchange(a, b, 2, inner)
        assert bool(dropped) and int(fill) == 3
        recv, sent, dropped, fill = run_exchange(a, b, 4, inner)
        assert not bool(dropped) and int(sent) == 6 and int(fill) == 3
        rk = np.asarray(recv.keys).reshape(2, 2, 4)  # (shard, peer, quota)
        np.testing.assert_array_equal(rk[0, 0, :3], [10, 11, 12])
        np.testing.assert_array_equal(rk[0, 1, :3], [13, 14, 15])
        assert np.all(rk[1] == EMPTY)  # owner 1's range is empty
        print("edge geometry OK")
    """)


def test_exchange_retry_fires_exactly_once():
    """A deliberately undersized explicit quota makes the first dispatch
    overflow; the host entry point must retry ONCE at the next pow2 and
    land exact parity with exchange_retries == 1."""
    run_py("""
        import jax, numpy as np
        from repro.core import pipeline
        from repro.core.types import ExecConfig, empty_key
        from repro.core.operators import validate_against_oracle

        CFG = ExecConfig(memory_rows=512, page_rows=32, fanin=4,
                         batch_rows=64)
        mesh = jax.make_mesh((2,), ("data",))
        # shard 0 holds keys 0..255, shard 1 holds 256..511: the sampled
        # cut sends each shard's 256 distinct keys to one owner apiece,
        # so quota=128 overflows (fill=256) and the pow2 retry at 256
        # succeeds
        keys = np.arange(512, dtype=np.uint32)
        pay = np.ones((512, 1), np.float32)
        st, stats = pipeline.insort_aggregate_device(
            keys, pay, CFG, policy="rs", mesh=mesh, exchange_quota=128)
        assert stats.exchange_retries == 1, stats
        assert stats.exchange_quota == 256
        assert stats.exchange_max_fill == 256
        validate_against_oracle(st, keys, pay)
        k = np.asarray(st.keys)
        assert (k != empty_key(k.dtype)).sum() == 512
        print("retry-once OK")
    """)


def test_strictify_cuts_dedupes_and_clamps():
    """Duplicate sampled cut values (heavy skew) must come out strictly
    increasing wherever the key domain allows, saturating at the top of
    the domain — on both key widths."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.types import key_dtype_context, max_key
    from repro.distributed.groupby import strictify_cuts

    for kd in (np.uint32, np.uint64):
        ctx = key_dtype_context(kd)
        top = max_key(kd)
        with ctx:  # uint64 keys need the scoped x64 context (as in-engine)
            cuts = jnp.asarray(np.array([7, 7, 7, 9, 9, 3], dtype=kd))
            out = np.asarray(strictify_cuts(cuts))
            np.testing.assert_array_equal(out, np.array(
                [7, 8, 9, 10, 11, 12], dtype=kd))
            # already-strict cuts pass through untouched
            cuts = jnp.asarray(np.array([5, 100, 2000], dtype=kd))
            np.testing.assert_array_equal(np.asarray(strictify_cuts(cuts)),
                                          np.array([5, 100, 2000], dtype=kd))
            # saturation at the domain top (EMPTY stays reserved)
            cuts = jnp.asarray(np.array([top, top, top], dtype=kd))
            out = np.asarray(strictify_cuts(cuts))
            np.testing.assert_array_equal(out, np.array([top] * 3, dtype=kd))
            assert out.max() == top  # never into the EMPTY sentinel


def test_hot_key_majority_regression():
    """Satellite regression: >50% of all rows carry ONE key.  Raw sample
    quantiles then repeat that key across most cut positions; without
    dedup/clamp several owners' ranges collapse and the exchange piles
    everything on one peer.  Parity + no retry proves the strictified
    cuts keep the quota bound honest under majority skew."""
    run_py("""
        import jax, numpy as np
        from repro.core import pipeline
        from repro.core.types import ExecConfig, empty_key
        from repro.core.operators import validate_against_oracle

        CFG = ExecConfig(memory_rows=256, page_rows=32, fanin=4,
                         batch_rows=64)
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(17)
        N = 4096
        keys = rng.integers(0, 700, N).astype(np.uint32)
        keys[rng.permutation(N)[: int(N * 0.6)]] = 350  # >=60% one key
        hot_rows = int((keys == 350).sum())
        assert hot_rows >= N * 0.6
        pay = rng.normal(size=(N, 1)).astype(np.float32)
        for policy in ("rs", "early_agg"):
            st, stats = pipeline.insort_aggregate_device(
                keys, pay, CFG, policy=policy, mesh=mesh)
            validate_against_oracle(st, keys, pay)
            assert stats.exchange_retries == 0
            assert stats.exchange_max_fill <= stats.exchange_quota
            k = np.asarray(st.keys)
            v = k != empty_key(k.dtype)
            assert int(np.asarray(st.count)[v][k[v] == 350][0]) == hot_rows
        print("hot key OK")
    """)


def test_non_shardable_backend_refused_at_front_door():
    """The mesh path guards on Backend.shardable before building any
    program (in-process: a world-1 mesh needs no fake devices)."""
    import jax
    import numpy as np

    from repro.core import dispatch, pipeline
    from repro.core.types import ExecConfig

    be = dispatch.get_backend("xla")
    dispatch.register_backend(
        "nosharding",
        lambda: dispatch.Backend(
            name="nosharding", argsort=be.argsort,
            segmented_combine=be.segmented_combine,
            merge_sorted=be.merge_sorted, shardable=False,
        ),
    )
    try:
        mesh = jax.make_mesh((1,), ("data",))
        keys = np.arange(64, dtype=np.uint32)
        with pytest.raises(dispatch.BackendUnavailable, match="shard_map"):
            pipeline.aggregate_device(keys, None, ExecConfig(),
                                      backend="nosharding", mesh=mesh)
        # single-device plans are untouched by the capability flag
        st, _ = pipeline.insort_aggregate_device(keys, None, ExecConfig(),
                                                 backend="nosharding")
        assert int(st.occupancy()) == 64
    finally:
        dispatch._loaders.pop("nosharding", None)
        dispatch._cache.pop("nosharding", None)


def test_sharded_pipeline_edges():
    run_py("""
        import jax, numpy as np
        from repro.core import pipeline
        from repro.core.types import ExecConfig, EMPTY
        from repro.core.operators import validate_against_oracle

        CFG = ExecConfig(memory_rows=256, page_rows=32, fanin=4, batch_rows=64)
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(3)

        # empty input
        st, stats = pipeline.insort_aggregate_device(
            np.zeros((0,), np.uint32), None, CFG, mesh=mesh)
        assert int(st.occupancy()) == 0 and stats.total_spill_rows == 0

        # input not divisible by world (EMPTY padding path)
        keys = rng.integers(0, 900, 4001).astype(np.uint32)
        st, _ = pipeline.insort_aggregate_device(
            keys, None, CFG, policy="early_agg", mesh=mesh)
        validate_against_oracle(st, keys)

        # one hot key: a single group, every shard sends one row to the
        # same range owner
        hot = np.full(12000, 7, np.uint32)
        st, stats = pipeline.insort_aggregate_device(
            hot, None, CFG, policy="rs", mesh=mesh)
        k = np.asarray(st.keys)
        assert int(st.occupancy()) == 1
        assert int(np.asarray(st.count)[k == 7][0]) == 12000
        assert stats.rows_exchanged == 8  # one surviving row per shard

        # skewed key range: every key inside a narrow band high in the
        # key space — fixed uniform ranges would send everything to one
        # owner; the sampled cuts adapt
        keys = (rng.integers(0, 500, 4096) + (1 << 31)).astype(np.uint32)
        pay = rng.normal(size=(4096, 2)).astype(np.float32)
        st, stats = pipeline.insort_aggregate_device(
            keys, pay, CFG, policy="rs", mesh=mesh)
        validate_against_oracle(st, keys, pay)
        # rows landed on several owners, not one
        kk = np.asarray(st.keys).reshape(8, -1)
        owners = (kk != EMPTY).any(axis=1).sum()
        assert owners >= 4, owners

        # plane-width restriction travels through the exchange
        st, _ = pipeline.insort_aggregate_device(
            keys, pay, CFG, policy="rs", widths=(2, 0, 0), mesh=mesh)
        assert st.widths == (2, 0, 0)
        validate_against_oracle(st, keys, pay)
        print("sharded edges OK")
    """)


def test_sharded_pipeline_single_readback_under_transfer_guard():
    run_py("""
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import pipeline
        from repro.core.types import DeviceSpillStats, ExecConfig
        from repro.core.operators import validate_against_oracle

        CFG = ExecConfig(memory_rows=256, page_rows=32, fanin=4, batch_rows=64)
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(5)
        keys = rng.integers(0, 1200, 4096).astype(np.uint32)
        pay = rng.normal(size=(4096, 1)).astype(np.float32)
        dk = jax.device_put(keys, NamedSharding(mesh, P("data")))
        dp = jax.device_put(pay, NamedSharding(mesh, P("data", None)))
        # compile outside the guard; the guard then proves steady state
        state, _ = pipeline.aggregate_device(dk, dp, CFG, policy="rs",
                                             mesh=mesh)
        jax.block_until_ready(state)
        with jax.transfer_guard("disallow"):
            state, dstats = pipeline.aggregate_device(dk, dp, CFG,
                                                      policy="rs", mesh=mesh)
            jax.block_until_ready((state, dstats))
        assert isinstance(dstats, DeviceSpillStats)
        stats = dstats.finalize()  # the single readback, outside the guard
        assert stats.total_spill_rows > 0
        assert 0 < stats.rows_exchanged < len(keys)
        validate_against_oracle(state, keys, pay)
        print("sharded transfer guard OK")
    """)


def test_sharded_schema_front_door_and_pallas_smoke():
    run_py("""
        import jax, numpy as np
        import repro
        from repro.core import pipeline
        from repro.core.schema import KeySpec
        from repro.core.types import ExecConfig
        from repro.core.operators import validate_against_oracle, group_by

        CFG = ExecConfig(memory_rows=256, page_rows=32, fanin=4, batch_rows=64)
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(11)
        keys = rng.integers(0, 1200, 4096).astype(np.uint32)
        pay = rng.normal(size=(4096, 1)).astype(np.float32)
        res = repro.aggregate({"k": keys}, by=KeySpec.of(k=12), values=pay,
                              aggs=("count", "sum"), cfg=CFG, order_by=True,
                              mesh=mesh)
        assert res.plan["mesh"] == {"axis": "data", "world": 8}
        assert res.plan["pipeline"] == "device"
        validate_against_oracle(res.state, keys, pay)
        rel = res.relation()
        assert np.all(np.diff(rel["k"].astype(np.int64)) > 0)

        st, _ = group_by(keys, pay, CFG, mesh=mesh)
        validate_against_oracle(st, keys, pay)

        # mesh + non-device plans must refuse, not silently single-device
        try:
            repro.aggregate({"k": keys}, by=KeySpec.of(k=12), cfg=CFG,
                            algorithm="hash", mesh=mesh)
            raise SystemExit("hash+mesh did not raise")
        except ValueError:
            pass
        try:
            group_by(keys, pay, CFG, pipeline="host", mesh=mesh)
            raise SystemExit("host+mesh did not raise")
        except ValueError:
            pass

        # the fused sharded program also compiles with the Pallas kernel
        # backend (interpret mode off-TPU) — tiny size, one program
        cfg = ExecConfig(memory_rows=64, page_rows=16, fanin=4, batch_rows=16)
        mesh2 = jax.make_mesh((2,), ("data",))
        k2 = rng.integers(0, 120, 400).astype(np.uint32)
        p2 = rng.normal(size=(400, 1)).astype(np.float32)
        st, _ = pipeline.insort_aggregate_device(
            k2, p2, cfg, policy="early_agg", backend="pallas", mesh=mesh2)
        validate_against_oracle(st, k2, p2)
        print("sharded front door + pallas smoke OK")
    """)
