"""Behaviour tests for the paper's operator and its baselines.

Every algorithm must produce the identical multiset of (key, count, sum)
groups as the NumPy oracle, for any input — the paper's correctness bar.
Spill accounting must obey the paper's structural claims.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    EMPTY,
    AggState,
    ExecConfig,
    distinct,
    f1_hash_aggregate,
    finalize,
    group_by,
    hash_aggregate,
    insort_aggregate,
    instream_aggregate,
    sort_then_stream_aggregate,
    sorted_groupby,
)
from repro.core.operators import validate_against_oracle

RNG = np.random.default_rng(42)


def mkinput(n, o, width=2, skew=False):
    if skew:
        # zipf-ish skew: a few very hot keys
        z = RNG.zipf(1.5, size=n).astype(np.uint64)
        keys = (z % o).astype(np.uint32)
    else:
        keys = RNG.integers(0, o, n).astype(np.uint32)
    pay = RNG.normal(size=(n, width)).astype(np.float32) if width else None
    return keys, pay


CFG = ExecConfig(memory_rows=512, page_rows=64, fanin=4, batch_rows=128)

ALGOS = ["insort", "hash", "f1_hash", "sort_then_stream", "inmemory"]


@pytest.mark.parametrize("algorithm", ALGOS)
@pytest.mark.parametrize("o", [1, 37, 700, 5000])
def test_groupby_matches_oracle(algorithm, o):
    keys, pay = mkinput(12_000, o)
    st, stats = group_by(keys, pay, CFG, algorithm=algorithm, output_estimate=o)
    validate_against_oracle(st, keys, pay)
    assert stats.total_spill_rows >= 0


@pytest.mark.parametrize("algorithm", ["insort", "hash"])
def test_groupby_skewed_keys(algorithm):
    keys, pay = mkinput(20_000, 3_000, skew=True)
    st, _ = group_by(keys, pay, CFG, algorithm=algorithm, output_estimate=3_000)
    validate_against_oracle(st, keys, pay)


def test_inmemory_case_never_spills():
    """Paper Fig 6 / Example 1 (TPC-H Q1): O ≤ M ⇒ zero spill."""
    keys, pay = mkinput(50_000, 100)
    st, stats = insort_aggregate(keys, pay, CFG, output_estimate=100)
    assert stats.total_spill_rows == 0
    assert stats.runs_generated == 0
    validate_against_oracle(st, keys, pay)


def test_insort_output_is_sorted():
    """Interesting orderings: in-sort output is sorted as a byproduct."""
    keys, pay = mkinput(30_000, 2_000)
    st, _ = insort_aggregate(keys, pay, CFG, output_estimate=2_000)
    k = np.asarray(st.keys)
    k = k[k != EMPTY]
    assert np.all(np.diff(k.astype(np.int64)) > 0)  # sorted and duplicate-free


def test_hash_output_is_not_key_sorted():
    """The deficit the paper removes: hash output is in hash order."""
    keys, pay = mkinput(30_000, 2_000)
    st, _ = hash_aggregate(keys, pay, CFG, output_estimate=2_000)
    k = np.asarray(st.keys)
    k = k[k != EMPTY].astype(np.int64)
    assert not np.all(np.diff(k) > 0)


def test_early_aggregation_beats_traditional_spill():
    """§3: early aggregation spills less than input-driven sorting."""
    keys, _ = mkinput(40_000, 1_000)
    _, s_insort = insort_aggregate(keys, None, CFG, output_estimate=1_000)
    _, s_trad = sort_then_stream_aggregate(keys, None, CFG)
    assert s_insort.total_spill_rows < s_trad.total_spill_rows
    # traditional spill ≥ input at run generation alone
    assert s_trad.rows_spilled_run_generation == 40_000


def test_insort_competitive_with_hash_spill():
    """The paper's headline: in-sort spill ≈ hash spill for O ≫ M."""
    keys, _ = mkinput(60_000, 4_000)
    _, si = insort_aggregate(keys, None, CFG, output_estimate=4_000)
    _, sh = hash_aggregate(keys, None, CFG, output_estimate=4_000)
    # read-sort-write cycles spill a bit more than hybrid hashing (Fig 12);
    # parity bound: within 35% and far below the traditional sort.
    assert si.total_spill_rows <= 1.35 * sh.total_spill_rows + CFG.memory_rows
    _, st = sort_then_stream_aggregate(keys, None, CFG)
    assert si.total_spill_rows < 0.5 * st.total_spill_rows


def test_wide_merge_single_level():
    """§4: when O/M ≤ F one wide merge finishes with zero merge spill,
    where a traditional merge needs multiple spilling levels (Fig 14)."""
    keys, _ = mkinput(60_000, 4_000)
    cfg = ExecConfig(memory_rows=1024, page_rows=64, fanin=4, batch_rows=128)
    _, s_wide = insort_aggregate(keys, None, cfg, output_estimate=4_000)
    _, s_trad = insort_aggregate(
        keys, None, cfg, output_estimate=4_000, use_wide_merge=False
    )
    assert s_wide.merge_levels == 1  # ceil(log_F(O/M)) = 1
    assert s_wide.rows_spilled_merge == 0  # wide merge never spills
    assert s_wide.merge_levels < s_trad.merge_levels
    assert s_trad.rows_spilled_merge > 0


def test_wide_merge_depth_output_driven():
    """§4.3: merge depth is ceil(log_F(O/M)) even when O/M > F — the
    pre-levels spill, the final wide merge does not."""
    keys, _ = mkinput(60_000, 4_000)
    _, s = insort_aggregate(keys, None, CFG, output_estimate=4_000)
    from repro.core.cost_model import merge_levels_insort

    assert s.merge_levels == merge_levels_insort(4_000, CFG.memory_rows, CFG.fanin)
    assert not s.index_overflowed


def test_wide_merge_index_stays_within_memory():
    """§4.2: the wide-merge index needs well under the memory allocation."""
    keys, _ = mkinput(60_000, 4_000)
    _, s = insort_aggregate(keys, None, CFG, output_estimate=4_000)
    assert not s.index_overflowed
    assert s.max_index_occupancy <= CFG.memory_rows


def test_wrong_output_estimate_is_still_correct():
    """Optimizer mis-estimates change the plan, never the answer."""
    keys, pay = mkinput(30_000, 2_500)
    for est in (1, 100, 2_500, 10**6):
        st, _ = insort_aggregate(keys, pay, CFG, output_estimate=est)
        validate_against_oracle(st, keys, pay)


def test_instream_streaming_and_correct():
    keys, pay = mkinput(17_000, 900)
    sk = np.sort(keys)
    order = np.argsort(keys, kind="stable")
    # payload must follow its key when pre-sorting the stream
    spay = pay[order]
    st, n = instream_aggregate(jnp.asarray(sk), jnp.asarray(spay), chunk=256)
    assert int(n) == len(np.unique(keys))
    validate_against_oracle(st, sk, spay)


def test_instream_tiny_and_degenerate():
    st, n = instream_aggregate(jnp.asarray(np.zeros(5, np.uint32)), None, chunk=4)
    assert int(n) == 1
    k = np.full(7, EMPTY, np.uint32)
    st, n = instream_aggregate(jnp.asarray(k), None, chunk=4)
    assert int(n) == 0


def test_finalize_avg():
    keys = np.array([3, 3, 5], np.uint32)
    pay = np.array([[1.0], [3.0], [10.0]], np.float32)
    st = sorted_groupby(jnp.asarray(keys), jnp.asarray(pay))
    out = finalize(st)
    assert out["avg"][0, 0] == pytest.approx(2.0)
    assert out["avg"][1, 0] == pytest.approx(10.0)
    assert out["count"][0] == 2 and out["count"][1] == 1
    assert out["min"][0, 0] == pytest.approx(1.0)
    assert out["max"][0, 0] == pytest.approx(3.0)


def test_distinct_no_payload():
    keys, _ = mkinput(25_000, 1_500, width=0)
    st, _ = distinct(keys, CFG, output_estimate=1_500)
    k = np.asarray(st.keys)
    k = k[k != EMPTY]
    assert np.array_equal(np.sort(k), np.unique(keys))


def test_empty_input():
    st, stats = insort_aggregate(np.zeros((0,), np.uint32), None, CFG)
    assert int(st.occupancy()) == 0
    assert stats.total_spill_rows == 0


def test_single_key_all_duplicates():
    keys = np.full(30_000, 7, np.uint32)
    st, stats = insort_aggregate(keys, None, CFG, output_estimate=1)
    assert stats.total_spill_rows == 0  # one group always fits memory
    assert int(st.occupancy()) == 1
    assert int(st.count[0]) == 30_000


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
def test_key_dtype_context_scopes_64_bit_keys(dtype):
    """uint64 keys get real 64-bit arrays inside the context only; uint32
    keys leave JAX's dtype defaults alone."""
    import jax

    from repro.core.types import key_dtype_context

    big = np.uint64(1) << np.uint64(40) if dtype == np.uint64 else None
    with key_dtype_context(dtype):
        x = jnp.asarray(np.arange(4, dtype=dtype))
        assert x.dtype == jnp.dtype(dtype)
        if big is not None:
            y = jnp.asarray([big], dtype=jnp.uint64) + x[:1]
            assert y.dtype == jnp.uint64 and int(y[0]) == int(big)
            assert jax.config.jax_enable_x64
    assert not jax.config.jax_enable_x64
    assert jnp.asarray(np.arange(4, dtype=np.uint64)).dtype == jnp.uint32
