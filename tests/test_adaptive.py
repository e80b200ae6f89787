"""Calibrated cost model + mid-flight adaptive policy switching tests.

Three layers:

* cost-model unit tests — the constants schema gate (CI fails if the
  checked-in ``core/_cost_constants.py`` drifts from the generator
  schema), the linear crossover solve, and the sorted-input credit;
* governor unit tests — every decision path (``start``, ``hold``,
  ``small_window``, ``hysteresis``, ``switch``) forced deterministically
  with injected constants, no device involved;
* engine integration — Zipf and phase-change key streams through
  ``policy="adaptive"`` with EXACT keys/counts parity vs the one-shot
  oracle on every decision path, the O(stream/k) readback contract
  counted, the transfer-guard discipline (the governor's readback is an
  explicit ``device_get``), and the snapshot/finalize out-capacity
  retry-at-next-pow2.
"""
import logging

import jax
import numpy as np
import pytest

from repro.core import cost_model, pipeline
from repro.core.adaptive import ARMS, GovernorConfig, Observation, PolicyGovernor
from repro.core.operators import validate_against_oracle
from repro.core.types import ExecConfig, MergeOverflowError

RNG = np.random.default_rng(11)
CFG = ExecConfig(memory_rows=256, page_rows=32, fanin=4, batch_rows=64)
N = 4096


def make_constants(
    *,
    traditional=100.0,
    early=150.0,
    early_dup=None,
    rs=400.0,
    sort=30.0,
    merge=50.0,
    spill=10.0,
) -> dict:
    """A schema-complete constants entry with injected per-policy costs
    (``early_dup`` defaults to ``early`` — duplicate-independent)."""
    absorb = {"traditional": traditional, "inrun_dedup": traditional + 20,
              "early_agg": early, "rs": rs}
    absorb_dup = dict(absorb)
    if early_dup is not None:
        absorb_dup["early_agg"] = early_dup
    return {
        "schema_version": cost_model.COST_SCHEMA_VERSION,
        "absorb_row_ns": absorb,
        "absorb_dup_row_ns": absorb_dup,
        "sort_row_ns": sort,
        "merge_row_ns": merge,
        "hash_probe_row_ns": 80.0,
        "spill_write_row_ns": spill,
        "meta": {"backend": "test", "generated_by": "tests"},
    }


# traditional wins at every duplicate rate (big absorb gap, small spill)
FAVOR_TRAD = make_constants(traditional=100.0, early=400.0, rs=900.0)
# early_agg wins at every duplicate rate
FAVOR_EARLY = make_constants(traditional=400.0, early=100.0, rs=900.0)
# crossover at d = 0.3125: traditional below, early_agg above
CROSSOVER = make_constants(traditional=100.0, early=150.0, early_dup=50.0,
                           rs=900.0, merge=50.0, spill=10.0)


# ---------------------------------------------------------------------------
# constants schema gate (the CI staleness check)
# ---------------------------------------------------------------------------


def test_checked_in_constants_match_generator_schema():
    from repro.core import _cost_constants as cc

    cost_model.validate_constants(cc.COST_CONSTANTS)
    assert cc.COST_SCHEMA_VERSION == cost_model.COST_SCHEMA_VERSION
    assert "cpu" in cc.COST_CONSTANTS, "CPU defaults must stay committed"


def test_uncalibrated_backend_is_an_error():
    """A platform without its own entry must not borrow another
    platform's timings (the old silent fallback to ``cpu``)."""
    import jax

    from repro.core import _cost_constants as cc

    assert cost_model.load_cost_constants(None) is cc.COST_CONSTANTS[
        jax.default_backend()]
    with pytest.raises(KeyError, match="no calibrated cost constants"):
        cost_model.load_cost_constants("no-such-platform")
    with pytest.raises(KeyError, match="no-such-platform"):
        cost_model.cost_surface(1024, 256, backend="no-such-platform")


def test_stale_constants_fail_loudly():
    bad = {"cpu": dict(make_constants())}
    del bad["cpu"]["merge_row_ns"]
    with pytest.raises(cost_model.StaleConstantsError, match="merge_row_ns"):
        cost_model.validate_constants(bad)
    stale = {"cpu": dict(make_constants(), schema_version=0)}
    with pytest.raises(cost_model.StaleConstantsError, match="schema_version"):
        cost_model.validate_constants(stale)
    partial = {"cpu": dict(make_constants())}
    partial["cpu"]["absorb_row_ns"] = {"traditional": 1.0}
    with pytest.raises(cost_model.StaleConstantsError, match="early_agg"):
        cost_model.validate_constants(partial)


# ---------------------------------------------------------------------------
# calibrated cost model
# ---------------------------------------------------------------------------


def test_crossover_exact_linear_solve():
    d = cost_model.crossover_dup_rate("traditional", "early_agg",
                                      constants=CROSSOVER, merge_levels=1)
    assert d == pytest.approx(0.3125)
    lo = cost_model.choose_policy(d - 0.05, constants=CROSSOVER,
                                  arms=("traditional", "early_agg"))
    hi = cost_model.choose_policy(d + 0.05, constants=CROSSOVER,
                                  arms=("traditional", "early_agg"))
    assert (lo, hi) == ("traditional", "early_agg")
    # degenerate: one policy dominating puts the crossover at the clamp
    assert cost_model.crossover_dup_rate(
        "traditional", "early_agg", constants=FAVOR_TRAD) == 1.0
    assert cost_model.crossover_dup_rate(
        "traditional", "early_agg", constants=FAVOR_EARLY) == 0.0


def test_sorted_input_credit_zeroes_sort_term():
    base = cost_model.policy_cost_per_row("traditional", 0.0,
                                          constants=CROSSOVER)
    credited = cost_model.policy_cost_per_row("traditional", 0.0,
                                              constants=CROSSOVER,
                                              input_sorted=True)
    assert base - credited == pytest.approx(CROSSOVER["sort_row_ns"])
    # the merging policies never re-sort a batch from scratch: no credit
    for p in ("early_agg", "rs"):
        assert cost_model.policy_cost_per_row(
            p, 0.0, constants=CROSSOVER
        ) == cost_model.policy_cost_per_row(
            p, 0.0, constants=CROSSOVER, input_sorted=True)


def test_plan_surfaces_cost_model_and_sorted_credit():
    import repro

    keys = RNG.integers(0, 64, 2048)
    res = repro.aggregate({"k": keys}, by=repro.KeySpec.of(k=10))
    cm = res.plan["cost_model"]
    assert set(cm) >= {"crossover_dup_rate", "policy_cost_ns_per_row",
                       "chosen_policy", "estimated_dup_rate",
                       "calibrated_backend", "input_sorted"}
    assert res.plan["input_sorted"] is False
    res2 = repro.aggregate({"k": np.sort(keys)}, by=repro.KeySpec.of(k=10),
                           input_sorted=True)
    cm2 = res2.plan["cost_model"]
    assert cm2["input_sorted"] is True
    constants = cost_model.load_cost_constants()
    assert (cm["policy_cost_ns_per_row"]["traditional"]
            - cm2["policy_cost_ns_per_row"]["traditional"]
            ) == pytest.approx(constants["sort_row_ns"])


# ---------------------------------------------------------------------------
# governor decision paths (unit, injected constants, no device)
# ---------------------------------------------------------------------------


def _gov(constants, **kw):
    return PolicyGovernor(CFG, config=GovernorConfig(constants=constants, **kw))


def _obs(rows, dups, **kw):
    return Observation(rows_absorbed=rows, dup_rows=dups, rows_spilled=0,
                       table_rows=0, run_slots_used=kw.get("slots", 1))


def test_governor_start_paths():
    g = _gov(FAVOR_TRAD)
    assert g.start_arm() == "traditional"
    assert g.events[-1]["path"] == "start"
    assert _gov(FAVOR_EARLY).start_arm() == "early_agg"
    forced = _gov(FAVOR_TRAD, start="rs")
    assert forced.start_arm() == "rs"
    # the output-estimate prior feeds the same chooser
    assert _gov(CROSSOVER).start_arm(output_estimate=10_000) in ARMS


def test_governor_hold_and_switch_paths():
    g = _gov(FAVOR_TRAD, min_window_rows=64)
    assert g.decide(_obs(1024, 0), current="traditional") == "traditional"
    assert g.events[-1]["path"] == "hold"
    # rs is badly wrong under these constants: switch fires
    g2 = _gov(FAVOR_TRAD, min_window_rows=64)
    assert g2.decide(_obs(1024, 0), current="rs") == "traditional"
    ev = g2.events[-1]
    assert ev["path"] == "switch" and ev["from"] == "rs"
    assert ev["advantage"] > 0.5


def test_governor_small_window_path():
    g = _gov(FAVOR_TRAD, min_window_rows=10_000)
    assert g.decide(_obs(1024, 0), current="rs") == "rs"
    assert g.events[-1]["path"] == "small_window"
    # window is measured since the LAST decision, not since stream start
    g2 = _gov(FAVOR_TRAD, min_window_rows=512)
    g2.decide(_obs(1024, 0), current="rs")
    assert g2.decide(_obs(1100, 0), current="rs") == "rs"
    assert g2.events[-1]["path"] == "small_window"


def test_governor_hysteresis_path():
    # challenger (traditional) is better, but not by the demanded margin
    close = make_constants(traditional=95.0, early=100.0, rs=900.0,
                           merge=0.0, spill=0.0, sort=0.0)
    g = _gov(close, min_window_rows=64, hysteresis=0.5)
    assert g.decide(_obs(1024, 0), current="early_agg") == "early_agg"
    ev = g.events[-1]
    assert ev["path"] == "hysteresis" and ev["challenger"] == "traditional"
    assert 0.0 < ev["advantage"] < 0.5


def test_governor_windowed_dup_rate_crosses():
    g = _gov(CROSSOVER, min_window_rows=64, hysteresis=0.05)
    # first window: unique-ish -> below crossover, stay traditional
    assert g.decide(_obs(1000, 100), current="traditional") == "traditional"
    # second window: heavy duplicates (window rate (900-100)/1000=0.8)
    nxt = g.decide(_obs(2000, 900), current="traditional")
    assert nxt == "early_agg"
    assert g.events[-1]["path"] == "switch"


def test_governor_config_validation():
    with pytest.raises(ValueError, match="interval_chunks"):
        GovernorConfig(interval_chunks=0)
    with pytest.raises(ValueError, match="arms"):
        GovernorConfig(arms=("early_agg", "hash"))
    with pytest.raises(ValueError, match="start"):
        GovernorConfig(start="traditional", arms=("early_agg", "rs"))


def test_governor_refused_at_construction_when_it_cannot_steer():
    """Satellite contract: a governor that would silently never steer is
    refused AT CONSTRUCTION, not discovered via a bench that lies — a
    fixed-policy stream ignores it, and mesh= streams have no
    cross-shard observation reduce yet."""
    gov = PolicyGovernor(CFG)
    with pytest.raises(ValueError, match="fixed policy 'rs'"):
        pipeline.StreamingAggregator(CFG, policy="rs", key_dtype=np.uint32,
                                     governor=gov)
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="mesh"):
        pipeline.StreamingAggregator(CFG, policy="rs", key_dtype=np.uint32,
                                     governor=gov, mesh=mesh)
    # adaptive + mesh refuses too (pre-existing contract, now symmetric)
    with pytest.raises(ValueError, match="adaptive"):
        pipeline.StreamingAggregator(CFG, policy="adaptive",
                                     key_dtype=np.uint32, mesh=mesh)


# ---------------------------------------------------------------------------
# engine integration: parity on every decision path
# ---------------------------------------------------------------------------


def _phase_keys(order="uniq->dup", n=N):
    h = n // 2
    uniq = RNG.integers(1, 2**31, h).astype(np.uint32)
    dup = RNG.integers(1, 24, h).astype(np.uint32)
    parts = {"uniq": uniq, "dup": dup}
    names = order.split("->")
    return np.concatenate([parts[names[0]], parts[names[1]]])


def _zipf_keys(n=N, a=1.4, domain=4096):
    return ((RNG.zipf(a, n) - 1) % domain + 1).astype(np.uint32)


def _stream(keys, pay, chunk=256):
    for i in range(0, len(keys), chunk):
        yield keys[i:i + chunk], None if pay is None else pay[i:i + chunk]


def _run_adaptive(keys, pay, governor, *, chunk=256, cfg=CFG):
    gov = PolicyGovernor(cfg, config=governor) \
        if isinstance(governor, GovernorConfig) else governor
    agg = pipeline.StreamingAggregator(
        cfg, policy="adaptive", key_dtype=np.uint32,
        width=0 if pay is None else pay.shape[1], governor=gov)
    for k, p in _stream(keys, pay, chunk):
        agg.absorb(k, p)
    state, stats = agg.finalize()
    return state, stats, gov, agg


DECISION_SCENARIOS = [
    # (label, constants, governor kwargs, key order, expected event path)
    ("wrong_start_recovers", FAVOR_TRAD, dict(start="rs"),
     "uniq->dup", "switch"),
    ("hold_steady", FAVOR_TRAD, dict(start="traditional"),
     "uniq->dup", "hold"),
    ("crossover_switch", CROSSOVER, dict(start="traditional",
                                         hysteresis=0.05),
     "uniq->dup", "switch"),
    ("reverse_crossover", CROSSOVER, dict(hysteresis=0.05),
     "dup->uniq", "switch"),
    ("hysteresis_blocks_flap", make_constants(
        traditional=95.0, early=100.0, rs=900.0, merge=0.0, spill=0.0,
        sort=0.0), dict(start="early_agg", hysteresis=0.5),
     "uniq->dup", "hysteresis"),
    ("small_window_holds", FAVOR_TRAD, dict(start="rs", interval_chunks=1,
                                            min_window_rows=10**6),
     "uniq->dup", "small_window"),
]


@pytest.mark.parametrize(
    "label,constants,gkw,order,expect_path",
    DECISION_SCENARIOS, ids=[s[0] for s in DECISION_SCENARIOS])
def test_adaptive_decision_paths_exact_parity(label, constants, gkw, order,
                                              expect_path):
    keys = _phase_keys(order)
    pay = RNG.normal(size=(len(keys), 1)).astype(np.float32)
    cfgkw = dict(constants=constants, min_window_rows=64)
    cfgkw.update(gkw)
    state, stats, gov, agg = _run_adaptive(
        keys, pay, GovernorConfig(**cfgkw))
    validate_against_oracle(state, keys, pay)
    paths = {e["path"] for e in gov.events}
    assert expect_path in paths, (label, gov.events)
    d = stats.as_dict()
    assert d["readbacks_paid"] == stats.readbacks_paid > 0
    assert d["policy_switches"] == len(agg.policy_events)
    if expect_path == "switch":
        assert stats.policy_switches >= 1
        ev = agg.policy_events[0]
        assert set(ev) >= {"rows_seen", "from", "to", "duplicate_rate"}
    else:
        assert stats.policy_switches == 0


def test_adaptive_zipf_parity_and_default_governor():
    keys = _zipf_keys()
    pay = RNG.normal(size=(N, 2)).astype(np.float32)
    # calibrated (checked-in) constants drive the real default governor
    state, stats, gov, _agg = _run_adaptive(keys, pay, None)
    validate_against_oracle(state, keys, pay)
    assert gov is None  # StreamingAggregator built its own
    assert stats.readbacks_paid > 0
    assert 0.0 <= stats.duplicate_rate <= 1.0


def test_adaptive_switch_mid_stream_changes_arm():
    keys = _phase_keys("uniq->dup")
    gov = PolicyGovernor(CFG, config=GovernorConfig(
        constants=CROSSOVER, hysteresis=0.05, min_window_rows=64,
        start="traditional"))
    agg = pipeline.StreamingAggregator(CFG, policy="adaptive",
                                       key_dtype=np.uint32, width=0,
                                       governor=gov)
    arms_seen = []
    for k, p in _stream(keys, None):
        agg.absorb(k, p)
        arms_seen.append(agg.arm)
    state, stats = agg.finalize()
    validate_against_oracle(state, keys)
    assert arms_seen[0] == "traditional"
    assert "early_agg" in arms_seen, "the dup phase must flip the arm"
    assert stats.duplicate_rate > 0.2


# ---------------------------------------------------------------------------
# the O(stream/k) readback contract
# ---------------------------------------------------------------------------


def test_readback_count_is_stream_over_k():
    keys = _zipf_keys(n=16 * 256)
    for k_interval in (2, 4, 8):
        _st, stats, _g, agg = _run_adaptive(
            keys, None, GovernorConfig(constants=FAVOR_TRAD,
                                       interval_chunks=k_interval))
        # the readback is pipelined one boundary behind its dispatch, so
        # a no-switch stream of C chunks harvests exactly C//k - 1 times
        chunks = 16
        assert agg.readbacks_paid == chunks // k_interval - 1
        assert stats.readbacks_paid == chunks // k_interval - 1
        assert stats.policy_switches == 0
    # fixed policies stay at ZERO governor readbacks
    agg = pipeline.StreamingAggregator(CFG, policy="rs",
                                       key_dtype=np.uint32, width=0)
    for k, p in _stream(keys, None):
        agg.absorb(k, p)
    _st, stats = agg.finalize()
    assert stats.readbacks_paid == 0 and stats.policy_switches == 0


def test_adaptive_observation_is_explicit_under_transfer_guard():
    """The governor's observation readback is an EXPLICIT device_get —
    the ingest path stays legal under ``transfer_guard("disallow")``
    (which bans implicit transfers only)."""
    keys = _phase_keys("uniq->dup")
    gov = GovernorConfig(constants=FAVOR_TRAD, start="rs")
    with jax.transfer_guard("disallow"):
        state, stats, g, _agg = _run_adaptive(keys, None, gov)
    validate_against_oracle(state, keys)
    assert stats.readbacks_paid > 0
    assert stats.policy_switches >= 1  # the switch flush is also guarded


# ---------------------------------------------------------------------------
# snapshot/finalize out_capacity retry at the next pow2
# ---------------------------------------------------------------------------


def _overflow_agg(n_unique, output_rows=16):
    keys = (np.arange(n_unique, dtype=np.uint32) + 1)
    keys = np.repeat(keys, 4)
    RNG.shuffle(keys)
    agg = pipeline.StreamingAggregator(CFG, policy="rs",
                                       key_dtype=np.uint32, width=0,
                                       output_rows=output_rows)
    for k, p in _stream(keys, None, chunk=256):
        agg.absorb(k, p)
    return agg, keys


def test_finalize_retries_once_at_next_pow2(caplog):
    agg, keys = _overflow_agg(24)  # 24 uniques > 16, <= 32: retry lands
    with caplog.at_level(logging.WARNING, logger="repro.core.pipeline"):
        state, stats = agg.finalize()
    validate_against_oracle(state, keys)
    assert any("retrying once" in r.message for r in caplog.records)


def test_snapshot_retries_once_and_engine_survives(caplog):
    agg, keys = _overflow_agg(24)
    with caplog.at_level(logging.WARNING, logger="repro.core.pipeline"):
        state, stats = agg.snapshot()
    validate_against_oracle(state, keys)
    assert any("retrying once" in r.message for r in caplog.records)
    # the live engine is untouched by the snapshot retry: keep ingesting,
    # then finalize (which must also retry) and still match the oracle
    more = RNG.integers(1, 25, 256).astype(np.uint32)
    agg.absorb(more, None)
    state2, _stats2 = agg.finalize()
    validate_against_oracle(state2, np.concatenate([keys, more]))


@pytest.mark.parametrize("entry", ("snapshot", "finalize"))
@pytest.mark.parametrize("n_unique,retries", ((12, 0), (24, 1)))
def test_merge_retry_is_counted(entry, n_unique, retries):
    """A snapshot's or finalize's merge re-run at the wider capacity shows
    as ``SpillStats.merge_retries``, as an exchange retry shows as
    ``exchange_retries``."""
    agg, keys = _overflow_agg(n_unique)
    state, stats = getattr(agg, entry)()
    validate_against_oracle(state, keys)
    assert stats.merge_retries == retries
    assert stats.exchange_retries == 0


def test_retry_that_still_overflows_raises():
    agg, _keys = _overflow_agg(512)  # 512 uniques >> 32: retry can't save it
    with pytest.raises(MergeOverflowError, match="finalize"):
        agg.finalize()


# ---------------------------------------------------------------------------
# schema front door
# ---------------------------------------------------------------------------


def _batches(keys, chunk=256):
    for i in range(0, len(keys), chunk):
        yield {"k": keys[i:i + chunk]}


def test_streamed_default_is_adaptive():
    import repro

    keys = _zipf_keys(n=2048) % 1000
    res = repro.aggregate(_batches(keys), by=repro.KeySpec.of(k=10), cfg=CFG)
    assert res.plan["algorithm"] == "adaptive"
    assert res.plan["policy"] == "adaptive"
    assert res.plan["streamed"] is True
    assert "policy_switches" in res.plan and "readbacks_paid" in res.plan
    validate_against_oracle(res.state, keys)
    # a geometry adaptive can't run (M not divisible by B) falls back
    odd = ExecConfig(memory_rows=192, page_rows=32, fanin=4, batch_rows=128)
    res2 = repro.aggregate(_batches(keys), by=repro.KeySpec.of(k=10), cfg=odd)
    assert res2.plan["algorithm"] == "insort"
    validate_against_oracle(res2.state, keys)


def test_adaptive_algorithm_validation():
    import repro

    keys = np.arange(64, dtype=np.uint32)
    with pytest.raises(ValueError, match="streamed"):
        repro.aggregate({"k": keys}, by=repro.KeySpec.of(k=10),
                        algorithm="adaptive")
    odd = ExecConfig(memory_rows=192, page_rows=32, fanin=4, batch_rows=128)
    with pytest.raises(ValueError, match="divisible"):
        repro.aggregate(_batches(keys, 32), by=repro.KeySpec.of(k=10),
                        algorithm="adaptive", cfg=odd)


# ---------------------------------------------------------------------------
# service surfaces policy telemetry
# ---------------------------------------------------------------------------


def test_service_reports_policy_switch_events():
    from repro.service import AggregationService

    svc = AggregationService(
        CFG, policy="adaptive", key_dtype=np.uint32,
        governor=GovernorConfig(constants=FAVOR_TRAD, start="rs"))
    keys = _phase_keys("uniq->dup")
    for k, _p in _stream(keys, None):
        svc.ingest(k)
    state, stats = svc.snapshot()
    m = svc.metrics.summary()
    assert m["policy_switches"] >= 1
    assert m["readbacks_paid"] >= 1
    assert m["current_policy"] == "traditional"
    assert svc.current_policy == "traditional"
    validate_against_oracle(state, keys)
    state2, _ = svc.close()
    validate_against_oracle(state2, keys)
