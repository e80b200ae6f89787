"""OrderedIndex engine + backend registry tests.

The acceptance bar of the merge-path refactor: absorbing one sorted state
into another is a *linear merge* — no full argsort on either backend —
and the rank computation that realizes it is exactly the stable-merge
permutation.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import dispatch, sorted_ops
from repro.core.ordered_index import (
    OrderedIndex,
    merge_absorb_xla,
    merge_gather_indices,
    merge_ranks,
    pair_combine_xla,
)
from repro.core.operators import validate_against_oracle
from repro.core.types import (
    EMPTY,
    AggState,
    empty_state,
    key_dtype_context,
    rows_to_state,
)

RNG = np.random.default_rng(99)

BACKENDS = ("xla", "pallas")
KEY_DTYPES = (np.uint32, np.uint64)


def _sorted_state(n, domain, width, rng=RNG, key_dtype=np.uint32):
    keys = rng.integers(0, domain, n).astype(key_dtype)
    if key_dtype == np.uint64:
        keys = keys << np.uint64(30)  # spread past 32 bits
    pay = None if width == 0 else rng.normal(size=(n, width)).astype(np.float32)
    st = rows_to_state(keys, None if pay is None else jnp.asarray(pay))
    return sorted_ops.absorb(st), keys, pay


# ---------------------------------------------------------------------------
# rank computation (the heart of the linear merge)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("na,nb,domain", [(100, 100, 50), (257, 33, 10),
                                          (64, 512, 1 << 30), (1, 1, 2)])
def test_merge_ranks_is_stable_merge_permutation(na, nb, domain):
    a = np.sort(RNG.integers(0, domain, na).astype(np.uint32))
    b = np.sort(RNG.integers(0, domain, nb).astype(np.uint32))
    pos_a, pos_b = merge_ranks(jnp.asarray(a), jnp.asarray(b))
    pos_a, pos_b = np.asarray(pos_a), np.asarray(pos_b)
    # a permutation of range(na+nb) …
    assert sorted(pos_a.tolist() + pos_b.tolist()) == list(range(na + nb))
    # … that realizes the sorted merge …
    out = np.empty(na + nb, np.uint32)
    out[pos_a] = a
    out[pos_b] = b
    np.testing.assert_array_equal(out, np.sort(np.concatenate([a, b])))
    # … stably: on ties, every a-row precedes every b-row
    for k in np.intersect1d(a, b):
        assert pos_a[a == k].max() < pos_b[b == k].min()


def test_merge_gather_indices_inverts_ranks():
    a = np.sort(RNG.integers(0, 40, 300).astype(np.uint32))
    b = np.sort(RNG.integers(0, 40, 200).astype(np.uint32))
    src = np.asarray(merge_gather_indices(jnp.asarray(a), jnp.asarray(b)))
    cat = np.concatenate([a, b])
    np.testing.assert_array_equal(cat[src], np.sort(cat))
    assert sorted(src.tolist()) == list(range(500))  # a permutation


from _jaxpr_checks import assert_no_scatter, assert_no_sort, collect_primitives


@pytest.mark.parametrize("key_dtype", KEY_DTYPES)
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("assume_unique", [False, True])
def test_merge_absorb_performs_no_sort(backend, assume_unique, key_dtype):
    """merge_absorb of two sorted states must not contain a sort primitive
    anywhere in its jaxpr (including inside the Pallas kernel body) — at
    32 AND 64-bit key width (64-bit keys run as (hi, lo) uint32 lanes on
    Pallas and native uint64 under x64 on XLA)."""
    with key_dtype_context(key_dtype):
        a, _, _ = _sorted_state(256, 100, 2, key_dtype=key_dtype)
        b, _, _ = _sorted_state(128, 100, 2, key_dtype=key_dtype)
        jx = jax.make_jaxpr(
            lambda x, y: sorted_ops.merge_absorb(
                x, y, backend=backend, assume_unique=assume_unique
            )
        )(a, b)
    prims = collect_primitives(jx.jaxpr)
    assert_no_sort(prims, context=f"via backend={backend}")
    if backend == "xla":
        # the XLA engine is also scatter-free end to end: rank-gather
        # interleave + segmented-scan combine + compaction gather
        assert_no_scatter(prims, context="on xla path")


@pytest.mark.parametrize("key_dtype", KEY_DTYPES)
def test_segmented_combine_xla_scatter_free_and_correct(key_dtype):
    """The general segmented combine (≥3 duplicates per group) on XLA is a
    segmented associative scan + compaction gather: its jaxpr must contain
    neither a sort nor any scatter primitive, and it must match the oracle
    on groups with ≥3 duplicates."""
    rng = np.random.default_rng(5)
    keys = np.sort(
        np.repeat(rng.choice(200, 60, replace=False), rng.integers(3, 7, 60))
    ).astype(key_dtype)
    if key_dtype == np.uint64:
        keys = keys << np.uint64(34)
    pay = rng.normal(size=(len(keys), 2)).astype(np.float32)
    with key_dtype_context(key_dtype):
        st = rows_to_state(jnp.asarray(keys), jnp.asarray(pay))
        jx = jax.make_jaxpr(
            lambda s: sorted_ops.segmented_combine(s, backend="xla")
        )(st)
        out = sorted_ops.segmented_combine(st, backend="xla")
    prims = collect_primitives(jx.jaxpr)
    assert_no_scatter(prims, context="in segmented_combine_xla")
    assert_no_sort(prims)
    validate_against_oracle(out, keys, pay)
    # per-group min/max survive the scan rewrite
    got_valid = np.asarray(out.valid())
    got_keys = np.asarray(out.keys)[got_valid]
    for name, red in (("min", np.minimum.reduceat), ("max", np.maximum.reduceat)):
        col = np.asarray(getattr(out, name))[got_valid]
        uk, starts = np.unique(keys, return_index=True)
        want = red(pay, starts, axis=0)
        np.testing.assert_array_equal(got_keys, uk)
        np.testing.assert_allclose(col, want, rtol=1e-6)


def test_absorb_of_unsorted_does_sort():
    """Sanity check on the detector: the full-argsort path IS a sort."""
    st = rows_to_state(jnp.asarray(RNG.integers(0, 9, 64).astype(np.uint32)), None)
    jx = jax.make_jaxpr(lambda x: sorted_ops.absorb(x))(st)
    assert "sort" in collect_primitives(jx.jaxpr)


# ---------------------------------------------------------------------------
# merge_absorb correctness across backends / shapes / uniqueness promises
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("na,nb,domain,width", [
    (700, 500, 300, 2), (128, 128, 10, 0), (64, 1, 5, 1), (300, 900, 1 << 30, 2),
])
def test_merge_absorb_matches_oracle(backend, na, nb, domain, width):
    a, ka, pa = _sorted_state(na, domain, width)
    b, kb, pb = _sorted_state(nb, domain, width)
    for uniq in (False, True):
        got = sorted_ops.merge_absorb(a, b, backend=backend, assume_unique=uniq)
        assert got.capacity == na + nb
        validate_against_oracle(
            got, np.concatenate([ka, kb]),
            None if width == 0 else np.concatenate([pa, pb]),
        )
        k = np.asarray(got.keys)
        k = k[k != EMPTY]
        assert np.all(np.diff(k.astype(np.int64)) > 0)  # sorted, duplicate-free


@pytest.mark.parametrize("backend", BACKENDS)
def test_merge_absorb_duplicates_within_inputs(backend):
    """Sorted-but-not-deduped inputs (e.g. run pages) combine correctly on
    the general path."""
    ka = np.sort(RNG.integers(0, 50, 200).astype(np.uint32))
    kb = np.sort(RNG.integers(0, 50, 100).astype(np.uint32))
    a = rows_to_state(jnp.asarray(ka), None)
    b = rows_to_state(jnp.asarray(kb), None)
    got = sorted_ops.merge_absorb(a, b, backend=backend)
    validate_against_oracle(got, np.concatenate([ka, kb]))


def test_merge_absorb_empty_capacity_side():
    a, ka, pa = _sorted_state(100, 30, 2)
    b = empty_state(0, 2)
    for uniq in (False, True):
        got = sorted_ops.merge_absorb(a, b, assume_unique=uniq)
        validate_against_oracle(got, ka, pa)


def test_pair_combine_matches_segmented_combine():
    """On ≤2-rows-per-key sorted input the pair-combine must agree with
    the general segmented combine bit for bit (modulo float assoc)."""
    keys = np.repeat(RNG.choice(1000, 300, replace=False).astype(np.uint32),
                     RNG.integers(1, 3, 300))
    keys = np.sort(keys)
    pay = RNG.normal(size=(len(keys), 2)).astype(np.float32)
    st = rows_to_state(jnp.asarray(keys), jnp.asarray(pay))
    got = pair_combine_xla(st)
    want = sorted_ops.segmented_combine(st)
    np.testing.assert_array_equal(np.asarray(got.keys), np.asarray(want.keys))
    np.testing.assert_array_equal(np.asarray(got.count), np.asarray(want.count))
    np.testing.assert_allclose(np.asarray(got.sum), np.asarray(want.sum),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.min), np.asarray(want.min))
    np.testing.assert_allclose(np.asarray(got.max), np.asarray(want.max))


# ---------------------------------------------------------------------------
# OrderedIndex type
# ---------------------------------------------------------------------------


def test_ordered_index_roundtrip_and_trim():
    keys = RNG.integers(0, 64, 500).astype(np.uint32)
    pay = RNG.normal(size=(500, 1)).astype(np.float32)
    oi = OrderedIndex.from_unsorted(rows_to_state(jnp.asarray(keys), jnp.asarray(pay)))
    validate_against_oracle(oi.state, keys, pay)
    occ = int(oi.occupancy())
    trimmed = oi.trim(occ)
    assert trimmed.capacity == occ
    validate_against_oracle(trimmed.state, keys, pay)


def test_ordered_index_merge_absorb():
    a = OrderedIndex.from_unsorted(
        rows_to_state(jnp.asarray(RNG.integers(0, 99, 400).astype(np.uint32)), None)
    )
    b = OrderedIndex.from_unsorted(
        rows_to_state(jnp.asarray(RNG.integers(50, 150, 300).astype(np.uint32)), None)
    )
    m = a.merge_absorb(b)
    assert isinstance(m, OrderedIndex)
    assert m.capacity == 700
    k = np.asarray(m.keys)
    k = k[k != EMPTY]
    assert np.all(np.diff(k.astype(np.int64)) > 0)


def test_ordered_index_is_pytree():
    oi = OrderedIndex.empty(16, 2)
    out = jax.jit(lambda x: x.merge_absorb(OrderedIndex.empty(16, 2)))(oi)
    assert out.capacity == 32


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------


def test_registry_builtin_backends():
    assert set(dispatch.registered_backends()) >= {"xla", "pallas"}
    assert dispatch.backend_available("xla")
    be = dispatch.get_backend("xla")
    assert be.name == "xla"
    assert dispatch.get_backend("xla") is be  # cached


def test_registry_auto_resolution():
    # auto is the XLA engine on every platform, TPU included
    assert dispatch.resolve_backend_name("auto") == "xla"
    assert dispatch.get_backend("auto") is dispatch.get_backend("xla")


def test_registry_unknown_backend_raises():
    with pytest.raises(KeyError):
        dispatch.get_backend("cuda-classic")


def test_registry_custom_backend_and_probe():
    calls = []

    def loader():
        calls.append(1)
        xla = dispatch.get_backend("xla")
        return dispatch.Backend(
            name="custom", argsort=xla.argsort,
            segmented_combine=xla.segmented_combine, merge_sorted=xla.merge_sorted,
        )

    dispatch.register_backend("custom-test", loader)
    try:
        assert dispatch.backend_available("custom-test")
        be = dispatch.get_backend("custom-test")
        assert be.name == "custom" and calls == [1]
        dispatch.get_backend("custom-test")
        assert calls == [1]  # loader ran once
        with pytest.raises(ValueError):
            dispatch.register_backend("custom-test", loader)
    finally:
        dispatch._loaders.pop("custom-test", None)
        dispatch._cache.pop("custom-test", None)


def test_registry_unavailable_backend_probes_false():
    def loader():
        raise dispatch.BackendUnavailable("no such accelerator")

    dispatch.register_backend("broken-test", loader)
    try:
        assert not dispatch.backend_available("broken-test")
        with pytest.raises(dispatch.BackendUnavailable):
            dispatch.get_backend("broken-test")
    finally:
        dispatch._loaders.pop("broken-test", None)


# ---------------------------------------------------------------------------
# the full operator on the pallas engine (acceptance: every policy + wide
# merge, both backends) — sizes kept small: interpret mode is slow
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["traditional", "inrun_dedup", "early_agg", "rs"])
def test_policies_oracle_pallas_backend(policy):
    from repro.core import insort_aggregate
    from repro.core.types import ExecConfig

    cfg = ExecConfig(memory_rows=128, page_rows=32, fanin=4, batch_rows=32)
    keys = RNG.integers(0, 300, 1500).astype(np.uint32)
    pay = RNG.normal(size=(1500, 1)).astype(np.float32)
    if policy == "rs":
        st, _ = insort_aggregate(keys, pay, cfg, output_estimate=300,
                                 run_policy="rs", backend="pallas")
    elif policy == "traditional":
        from repro.core.insort import sort_then_stream_aggregate

        st, _ = sort_then_stream_aggregate(keys, pay, cfg, backend="pallas")
    else:
        st, _ = insort_aggregate(
            keys, pay, cfg, output_estimate=300,
            early_aggregation=(policy == "early_agg"), run_policy="batch",
            backend="pallas",
        )
    validate_against_oracle(st, keys, pay)


@pytest.mark.parametrize("backend", BACKENDS)
def test_wide_merge_oracle_both_backends(backend):
    from repro.core import insort_aggregate
    from repro.core.types import ExecConfig

    cfg = ExecConfig(memory_rows=128, page_rows=32, fanin=4, batch_rows=32)
    keys = RNG.integers(0, 400, 2000).astype(np.uint32)
    st, stats = insort_aggregate(keys, None, cfg, output_estimate=400,
                                 backend=backend)
    validate_against_oracle(st, keys)
    assert stats.rows_spilled_merge == 0  # the wide merge never spills
    assert stats.rows_emitted == len(np.unique(keys))
