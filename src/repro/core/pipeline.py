"""Device-resident external aggregation pipeline: scan-based run
generation fused with the wide merge into ONE compiled program.

The host drivers in :mod:`repro.core.run_generation` mirror the paper's
I/O loop: dispatch one jitted step per batch, then **block on an
occupancy readback** to decide whether to flush a run.  That round trip
— not comparisons — dominates once the per-record work is vectorized
(cf. the external-sort implementation studies in PAPERS.md), so the
external pipeline runs at host-latency instead of hardware speed.

This module removes the host from the loop.  All three read-sort-write
policies (``traditional``, ``inrun_dedup``, ``early_agg``) and
replacement selection (``rs``) run as a single jitted ``lax.scan`` over
the pre-batched input:

* the scan carry is an explicit, reusable pytree —
  :class:`~repro.core.types.StreamEngineState` — holding the stacked
  run buffer, the early-agg / replacement-selection tables, and all
  spill counters as device scalars;
* runs are written into the preallocated, stacked RunStore-shaped
  buffer via a data-dependent run-slot index carried through the scan
  (out-of-range slots drop, so "don't flush" is a no-op scatter);
* the §4.3 pre-wide traditional merge levels (needed when O/M exceeds
  the fan-in, or the wide merge's index outgrows memory) are planned
  statically from the output estimate and run on device as pairwise
  linear merges over run slots (:func:`_device_premerge`);
* the wide merge (§4) consumes the run buffer directly
  (:func:`repro.core.merge.wide_merge_device`), so
  ``repro.aggregate(..., algorithm="insort")`` compiles end-to-end;
* spill accounting is a :class:`~repro.core.types.DeviceSpillStats`
  pytree — the only host synchronization in the whole pipeline is the
  final ``finalize()`` readback of stats + run lengths.

Because the carry is a first-class pytree, the same engine also runs
**streamed**: :class:`StreamingAggregator` / :func:`aggregate_device_stream`
feed the scan super-batch by super-batch from the host, double-buffering
the ``jax.device_put`` of chunk k+1 behind the absorb of chunk k, so
inputs far larger than device memory flow through at compute speed with
zero per-chunk readbacks (finalize stays the single sync).  Chunk count
never enters trace shapes: one compile per super-batch geometry, with a
pow2-bucketed tail.

Sizing is static, derived from shapes alone: a run buffer of
``ceil(N/M)+O(1)`` slots (every flushed run carries > M unique rows, so
the slot count is bounded by input over memory), each slot page-aligned.
The batch count is bucketed to the next power of two (EMPTY batches are
no-ops) so recompiles scale with log(N), not N.  Host (NumPy) inputs are
padded to that bucketed geometry *before* the jit boundary, so calls
that differ only in N share one compilation.

The host loops remain the reference path for oracle-parity testing and
for the paper's exact per-level accounting (Fig 14); the device
pre-merge accounting deviates from the host's only for non-power-of-two
fan-ins and over-estimated run counts (it plans levels from the static
slot bound rather than the dynamic run count).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core import merge as merge_mod
from repro.core import run_generation as rg
from repro.core import sorted_ops
from repro.core.types import (
    AggState,
    DeviceSpillStats,
    ExchangeOverflowError,
    ExecConfig,
    MergeOverflowError,
    SpillStats,
    StreamEngineState,
    as_key_array,
    concat_states,
    empty_key,
    empty_like,
    empty_state,
    expand_engine_scalars,
    key_dtype_context,
    match_vma,
    max_key,
    rows_to_state,
    squeeze_engine_scalars,
)

POLICIES = ("traditional", "inrun_dedup", "early_agg", "rs")

# The adaptive streaming mode: STREAM_POLICIES is what StreamingAggregator
# accepts — "adaptive" runs the engine on the current arm's NATIVE
# geometry (so holding an arm costs exactly what the fixed policy costs)
# and lets a PolicyGovernor (repro.core.adaptive) switch the concrete
# run-generation arm between super-batches; a switch flushes the tables
# and re-shapes the state to the incoming arm's geometry (the run store
# only ever ratchets wider — closed runs own their columns).
STREAM_POLICIES = POLICIES + ("adaptive",)

# Arms the governor may switch between.  inrun_dedup is never an arm: on
# unique-heavy input it pays traditional's spill plus a useless segmented
# combine, and on duplicate-heavy input early_agg's persistent M-row
# window strictly beats its per-batch window — it cannot win either
# regime.
ADAPTIVE_ARMS = ("early_agg", "rs", "traditional")

_log = logging.getLogger(__name__)

# Trace-time log: every traced pipeline/stream program appends one entry
# here.  Tests use it as a compile counter — a second call with a
# different N but the same bucketed geometry must NOT append (the jit
# cache hits, nothing retraces).
TRACE_LOG: list[tuple] = []


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pow2_ceil(x: int) -> int:
    return 1 << (x - 1).bit_length() if x > 1 else 1


def _num_batches(n: int, chunk: int) -> int:
    """Batch count bucketed to the next power of two (EMPTY-padded batches
    are no-ops) so distinct input sizes share compilations."""
    t = (n + chunk - 1) // chunk
    return 1 << (t - 1).bit_length() if t > 1 else t


def _pad_flat(keys, payload, total: int):
    """(traced) EMPTY/zero-pad flat (keys, payload) to ``total`` rows —
    EMPTY rows are no-ops in every policy; device-side, no host
    transfer."""
    padn = total - keys.shape[0]
    kd = keys.dtype
    keys = jnp.concatenate([keys, jnp.full((padn,), empty_key(kd), kd)])
    if payload is not None:
        pad = jnp.zeros((padn,) + payload.shape[1:], payload.dtype)
        payload = jnp.concatenate([payload, pad])
    return keys, payload


def _batch(keys, payload, chunk: int, t: int):
    """(traced) pad the flat input to ``t * chunk`` rows and reshape into
    scan batches."""
    keys, payload = _pad_flat(keys, payload, t * chunk)
    bk = keys.reshape(t, chunk)
    bp = None
    if payload is not None:
        bp = payload.reshape(t, chunk, payload.shape[1])
    return bk, bp


def _stacked_empty(slots: int, rows: int, width: int, *, key_dtype, widths):
    proto = empty_state(rows, width, key_dtype=key_dtype, widths=widths)
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (slots,) + x.shape), proto)


def _pad_rows(state: AggState, rows: int) -> AggState:
    if state.capacity >= rows:
        return state
    return concat_states(state, empty_like(state, rows - state.capacity))


# ---------------------------------------------------------------------------
# the engine: init / step / finish over an explicit StreamEngineState
# ---------------------------------------------------------------------------


def _engine_geometry(policy: str, M: int, B: int, P: int):
    """Static per-policy geometry: (input chunk rows, run-slot rows,
    table capacity, second-table capacity).  Unused tables carry
    capacity 0 so the engine-state pytree stays uniform per policy."""
    if policy in ("traditional", "inrun_dedup"):
        return M, _round_up(M, P), 0, 0
    if policy == "early_agg":
        return B, _round_up(M + B, P), M, 0
    if policy == "rs":
        return B, _round_up(2 * M + 2 * B, P), M + 2 * B, M + 2 * B
    if policy == "adaptive":
        # Adaptive streams STAGE chunks at unit-M granularity (re-shaped
        # to the current arm's input unit at absorb time); the engine
        # state itself lives at the current ARM's native geometry, with
        # the slot width ratcheting up at switches (see _switch_reshape).
        # The widest (rs) shape here is the staging unit + upper bound.
        return M, _round_up(2 * M + 2 * B, P), M + 2 * B, M + 2 * B
    raise ValueError(f"unknown run-generation policy {policy!r}")


def _engine_init(policy: str, *, M: int, B: int, P: int, R: int, width: int,
                 key_dtype, widths) -> StreamEngineState:
    """A fresh engine state with ``R`` preallocated run slots (traced —
    call under jit so the buffers are born on device)."""
    _, C, capT, capT2 = _engine_geometry(policy, M, B, P)
    kd = np.dtype(key_dtype)
    ws = widths if widths is not None else (width, width, width)
    return StreamEngineState(
        table=empty_state(capT, width, key_dtype=kd, widths=ws),
        table2=empty_state(capT2, width, key_dtype=kd, widths=ws),
        frontier=jnp.zeros((), dtype=kd),
        store=_stacked_empty(R, C, width, key_dtype=kd, widths=ws),
        lens=jnp.zeros((R,), jnp.int32),
        cursor=jnp.int32(0),
        ridx=jnp.int32(0),
        spilled=jnp.int32(0),
        absorbed=jnp.int32(0),
        dups=jnp.int32(0),
    )


def _valid_rows(ck) -> jax.Array:
    """Valid (non-EMPTY) input rows in one batch (int32 device scalar)."""
    return jnp.sum(ck != empty_key(ck.dtype), dtype=jnp.int32)


def _step_sortwrite(es: StreamEngineState, ck, cp, *, dedup: bool,
                    backend: str, ws) -> StreamEngineState:
    """``traditional`` / ``inrun_dedup``: one run per M-row batch, written
    to the carried run slot (EMPTY batches are no-ops)."""
    valid = _valid_rows(ck)
    st = rows_to_state(ck, cp, widths=ws)
    if dedup:
        st = sorted_ops.absorb(st, backend=backend)
    else:
        st = sorted_ops.sort_state(st, backend=backend)
    occ = st.occupancy()
    if dedup:
        dups = valid - occ  # rows that combined within the batch
    else:
        # no combining happens, but the sorted batch still *observes* its
        # duplicates: adjacent equal-key pairs (EMPTY pads sort last and
        # never match a valid key, so no masking is needed beyond EMPTY)
        k = st.keys
        dups = jnp.sum(
            (k[1:] == k[:-1]) & (k[1:] != empty_key(k.dtype)),
            dtype=jnp.int32,
        )
    R, C = es.run_slots, es.slot_rows
    slot = jnp.where(occ > 0, es.ridx, R)
    store = jax.tree.map(
        lambda d, s: d.at[slot].set(s, mode="drop"), es.store, _pad_rows(st, C)
    )
    lens = es.lens.at[slot].set(occ, mode="drop")
    return dataclasses.replace(
        es, store=store, lens=lens,
        ridx=es.ridx + (occ > 0).astype(jnp.int32),
        spilled=es.spilled + occ,
        absorbed=es.absorbed + valid,
        dups=es.dups + dups,
    )


def _step_early_agg(es: StreamEngineState, ck, cp, *, M: int, backend: str,
                    ws) -> StreamEngineState:
    """``early_agg`` (§3): the ordered in-memory index absorbs each sorted
    batch; when occupancy exceeds M the whole index content is written to
    the carried run slot and memory restarts empty."""
    R, C = es.run_slots, es.slot_rows
    capT = es.table.capacity  # M for the fixed policy; M + 2B under adaptive
    valid = _valid_rows(ck)
    occ_before = es.table.occupancy()
    batch = sorted_ops.absorb(rows_to_state(ck, cp, widths=ws), backend=backend)
    merged = sorted_ops.merge_absorb(
        es.table, batch, backend=backend, assume_unique=True
    )  # capacity capT + B
    occ = merged.occupancy()
    flush = occ > M
    # memory full: the entire index content becomes one sorted run in the
    # carried slot; otherwise the (out-of-range) write drops.
    slot = jnp.where(flush, es.ridx, R)
    store = jax.tree.map(
        lambda d, s: d.at[slot].set(s, mode="drop"), es.store,
        _pad_rows(merged, C),
    )
    lens = es.lens.at[slot].set(occ, mode="drop")
    kept = jax.tree.map(lambda x: x[:capT], merged)  # trim to table capacity
    table0 = empty_like(es.table, capT)
    table = jax.tree.map(lambda e, k: jnp.where(flush, e, k), table0, kept)
    return dataclasses.replace(
        es, table=table, store=store, lens=lens,
        ridx=es.ridx + flush.astype(jnp.int32),
        spilled=es.spilled + jnp.where(flush, occ, 0),
        absorbed=es.absorbed + valid,
        dups=es.dups + (valid - (occ - occ_before)),
    )


def _step_rs(es: StreamEngineState, ck, cp, *, M: int, B: int, backend: str,
             ws) -> StreamEngineState:
    """Replacement selection (§3.3): the two-table partitioned b-tree is
    the carry, and the eviction scan is a bounded inner ``while_loop``
    writing B-row quanta at the carried (run-slot, cursor) position.  A
    run closes when the open partition drains (host semantics) or when
    its slot is within one quantum of capacity (the device buffer's
    close-early rule — always legal, runs only need to be sorted)."""
    C = es.slot_rows
    cap = es.table.capacity  # M + 2B
    arB = jnp.arange(B, dtype=jnp.int32)
    valid = _valid_rows(ck)
    occ_before = es.table.occupancy() + es.table2.occupancy()
    batch = sorted_ops.absorb(rows_to_state(ck, cp, widths=ws), backend=backend)
    rt, nt = rg.rs_split_absorb(es.table, es.table2, es.frontier, batch,
                                backend=backend)
    dups = valid - (rt.occupancy() + nt.occupancy() - occ_before)
    es = dataclasses.replace(
        es, table=rt, table2=nt,
        absorbed=es.absorbed + valid, dups=es.dups + dups,
    )

    def close_fn(s):
        # the open run is exhausted (or its slot is full): record its
        # length, then merge both partitions into a fresh open partition —
        # with occupancy 0 this is exactly the host's promote-next-table.
        lens = s.lens.at[jnp.where(s.cursor > 0, s.ridx, s.run_slots)].set(
            s.cursor, mode="drop"
        )
        ridx = s.ridx + (s.cursor > 0).astype(jnp.int32)
        merged = jax.tree.map(
            lambda x: x[:cap],
            sorted_ops.merge_absorb(s.table, s.table2, backend=backend,
                                    assume_unique=True),
        )
        return dataclasses.replace(
            s, table=merged, table2=empty_like(s.table2, cap),
            frontier=jnp.zeros((), s.frontier.dtype), lens=lens,
            cursor=jnp.int32(0), ridx=ridx,
        )

    def evict_fn(s):
        evicted, rest, frontier, n_ev = rg.rs_evict_step(s.table, B)
        rows = s.cursor + arB
        store = jax.tree.map(
            lambda d, v: d.at[s.ridx, rows].set(v, mode="drop"), s.store,
            evicted,
        )
        return dataclasses.replace(
            s, table=rest, frontier=frontier, store=store,
            cursor=s.cursor + n_ev, spilled=s.spilled + n_ev,
        )

    def overflow_step(s):
        return jax.lax.cond(
            (s.table.occupancy() == 0) | (s.cursor + B > C),
            lambda s: match_vma(close_fn(s), s), evict_fn, s,
        )

    def overflow_cond(s):
        return s.table.occupancy() + s.table2.occupancy() > M

    return jax.lax.while_loop(overflow_cond, overflow_step, es)


def _engine_step(es: StreamEngineState, ck, cp, *, policy: str, M: int,
                 B: int, backend: str, ws) -> StreamEngineState:
    """Advance the engine by one input batch (the ``lax.scan`` body)."""
    if policy in ("traditional", "inrun_dedup"):
        return _step_sortwrite(es, ck, cp, dedup=(policy == "inrun_dedup"),
                               backend=backend, ws=ws)
    if policy == "early_agg":
        return _step_early_agg(es, ck, cp, M=M, backend=backend, ws=ws)
    if policy == "rs":
        return _step_rs(es, ck, cp, M=M, B=B, backend=backend, ws=ws)
    raise ValueError(f"unknown run-generation policy {policy!r}")


def _engine_finish(es: StreamEngineState, *, policy: str, backend: str):
    """Drain the engine: flush resident tables into run slots.

    Returns ``(store, lens, table, spilled, nruns, overflow)`` — the
    inputs of the merge phase.  For ``early_agg`` the resident table is
    mirrored into the next slot so a downstream wide merge always
    consumes the complete picture; it counts as a spilled run only when
    earlier slots spilled (host-reference semantics).  For ``rs`` the
    open run finishes with the open partition's remainder (its own slot
    when there is room, the next slot otherwise), then the next-run
    partition is written as the last run."""
    R, C = es.run_slots, es.slot_rows
    if policy in ("traditional", "inrun_dedup"):
        return es.store, es.lens, es.table, es.spilled, es.ridx, es.ridx > R
    if policy == "early_agg":
        occ_t = es.table.occupancy()
        store = jax.tree.map(
            lambda d, s: d.at[es.ridx].set(s, mode="drop"), es.store,
            _pad_rows(es.table, C),
        )
        lens = es.lens.at[es.ridx].set(occ_t, mode="drop")
        spilled = es.spilled + jnp.where(es.ridx > 0, occ_t, 0)
        nruns = es.ridx + ((es.ridx > 0) & (occ_t > 0)).astype(jnp.int32)
        overflow = es.ridx + 1 > R
        return (store, lens, es.table, jnp.where(es.ridx > 0, spilled, 0),
                nruns, overflow)
    # rs drain
    rt, nt = es.table, es.table2
    occ_r = rt.occupancy()
    occ_n = nt.occupancy()
    cursor = es.cursor
    evicted_any = (es.ridx > 0) | (cursor > 0)
    arC = jnp.arange(rt.capacity, dtype=jnp.int32)

    def drain_append(args):
        buf, lens, ridx = args
        buf = jax.tree.map(
            lambda d, s: d.at[ridx, cursor + arC].set(s, mode="drop"), buf, rt
        )
        ln = cursor + occ_r
        lens = lens.at[jnp.where(ln > 0, ridx, R)].set(ln, mode="drop")
        return buf, lens, ridx + (ln > 0).astype(jnp.int32)

    def drain_split(args):
        buf, lens, ridx = args
        lens = lens.at[ridx].set(cursor, mode="drop")  # cursor > 0 here
        ridx = ridx + 1
        buf = jax.tree.map(
            lambda d, s: d.at[ridx, arC].set(s, mode="drop"), buf, rt
        )
        lens = lens.at[jnp.where(occ_r > 0, ridx, R)].set(occ_r, mode="drop")
        return buf, lens, ridx + (occ_r > 0).astype(jnp.int32)

    buf, lens, ridx = jax.lax.cond(
        cursor + occ_r <= C, drain_append, drain_split,
        (es.store, es.lens, es.ridx),
    )
    buf = jax.tree.map(lambda d, s: d.at[ridx, arC].set(s, mode="drop"), buf, nt)
    lens = lens.at[jnp.where(occ_n > 0, ridx, R)].set(occ_n, mode="drop")
    ridx = ridx + (occ_n > 0).astype(jnp.int32)
    spilled = es.spilled + occ_r + occ_n
    nruns = jnp.where(evicted_any, ridx, 0)
    overflow = ridx > R
    return buf, lens, rt, jnp.where(evicted_any, spilled, 0), nruns, overflow


# ---------------------------------------------------------------------------
# the fused program
# ---------------------------------------------------------------------------


def _slots_for(n_pad: int, M: int, extra: int) -> int:
    # every closed run carries > M unique rows (early-agg flushes at
    # occupancy > M; every RS run drains a partition that held > M rows),
    # so input-over-memory bounds the slot count.
    return n_pad // (M + 1) + extra


def _stream_run_slots(policy: str, n_pad: int, M: int) -> int:
    """Run-slot bound from the padded row count alone — the host can size
    (and grow) the store with zero device readbacks."""
    if policy in ("traditional", "inrun_dedup"):
        return max(1, n_pad // M)  # one run per M-row batch
    if policy == "adaptive":
        # worst arm mix: the traditional arm writes one run per M-row
        # batch, and each mid-flight switch can close at most two extra
        # (< M-row) runs — those are re-anchored into _base_slots at
        # switch time, so the rolling bound only needs the rs finish
        # slack on top of input-over-memory.
        return max(1, n_pad // M) + 4
    return _slots_for(n_pad, M, 2 if policy == "early_agg" else 4)


def _static_run_slots(policy: str, n: int, M: int, B: int) -> int:
    """Run-slot bound from shapes alone (host-side twin of the sizing in
    :func:`_pipeline_body`, used to plan pre-merge levels statically)."""
    chunk = _engine_geometry(policy, M, B, 1)[0]
    return _stream_run_slots(policy, _num_batches(n, chunk) * chunk, M)


def _pad_slots(store: AggState, lens, R_new: int):
    R, C = store.keys.shape
    widths = (store.sum.shape[-1], store.min.shape[-1], store.max.shape[-1])
    extra = _stacked_empty(
        R_new - R, C, max(widths), key_dtype=store.keys.dtype, widths=widths
    )
    store = jax.tree.map(lambda a, b: jnp.concatenate([a, b], 0), store, extra)
    lens = jnp.concatenate([lens, jnp.zeros((R_new - R,), jnp.int32)])
    return store, lens


def _device_premerge(store: AggState, lens, *, fanin: int, levels: int, backend: str):
    """§4.3 pre-wide traditional merge levels, on device.

    Each level merges groups of ``2^ceil(log2 F)`` run slots as a
    balanced tree of pairwise linear merge-absorbs (``lax.map`` over slot
    pairs; each pass halves the slot count and doubles slot capacity, so
    the buffer footprint is constant).  Empty slots merge as no-ops, so
    the statically planned level count is safe whatever the dynamic run
    count.  Spill accounting matches the host planner: a group's merged
    output counts as merge spill only if the group actually combined ≥ 2
    live runs (singletons are carried, not rewritten).  For non-power-of-
    two fan-ins the effective group width rounds up to the next power of
    two (slightly fewer, wider groups than the host reference).
    """
    spilled = jnp.int32(0)
    steps = jnp.int32(0)
    nlev = jnp.int32(0)
    sub = max(1, (fanin - 1).bit_length())  # pairwise passes per level
    G = 1 << sub
    for _ in range(levels):
        R = store.keys.shape[0]
        if R <= 1:
            break
        Rpad = _round_up(R, G)
        if Rpad > R:
            store, lens = _pad_slots(store, lens, Rpad)
        sizes = jnp.sum(lens.reshape(-1, G) > 0, axis=1, dtype=jnp.int32)
        for _ in range(sub):

            def step(pair):
                sa, sb = pair
                m = sorted_ops.merge_absorb(sa, sb, backend=backend)
                return m, m.occupancy()

            a = jax.tree.map(lambda x: x[0::2], store)
            b = jax.tree.map(lambda x: x[1::2], store)
            store, lens = jax.lax.map(step, (a, b))
        active = sizes >= 2
        spilled = spilled + jnp.sum(jnp.where(active, lens, 0), dtype=jnp.int32)
        steps = steps + jnp.sum(active, dtype=jnp.int32)
        nlev = nlev + jnp.any(active).astype(jnp.int32)
    return store, lens, spilled, steps, nlev


def _merge_phase(store, lens, spilled, nruns, overflow, *, page_rows: int,
                 index_rows: int, fanin: int, premerge_levels: int,
                 backend: str, out_capacity: int, rows_retired=None,
                 out_buffer=None):
    """§4.3 pre-merge levels + the wide merge + stats assembly — shared
    by the one-shot program, the streamed finalize, and the merge-on-read
    snapshot (which passes a fresh ``out_buffer`` so emission never
    aliases live engine state, plus the ``rows_retired`` accumulator)."""
    zero = jnp.int32(0)
    store, lens, spill_m, msteps, mlevels = _device_premerge(
        store, lens, fanin=fanin, levels=premerge_levels, backend=backend
    )
    out, out_cur, pages_read, max_occ, ix_overflow, dropped = (
        merge_mod.wide_merge_device(
            store, lens, page_rows=page_rows, index_rows=index_rows,
            out_capacity=out_capacity, backend=backend, out=out_buffer,
        )
    )
    # merge/emission stats are charged only when run generation actually
    # spilled — the in-memory case's pass through the merge is a formality
    # the host reference never pays (it returns the table directly).
    spilled_any = nruns > 0
    one = jnp.where(spilled_any, 1, 0).astype(jnp.int32)
    stats = DeviceSpillStats(
        rows_spilled_run_generation=spilled,
        rows_spilled_merge=spill_m,  # pre-levels only; the wide merge never spills
        runs_generated=nruns,
        merge_steps=msteps + one,
        merge_levels=mlevels + one,
        pages_read=jnp.where(spilled_any, pages_read, 0).astype(jnp.int32),
        rows_emitted=jnp.where(spilled_any, out_cur, 0).astype(jnp.int32),
        index_overflowed=spilled_any & ix_overflow,
        max_index_occupancy=jnp.where(spilled_any, max_occ, 0).astype(jnp.int32),
        run_buffer_overflowed=overflow,
        merge_dropped_rows=dropped,
        rows_exchanged=zero,
        rows_retired=zero if rows_retired is None else rows_retired,
        exchange_dropped=jnp.bool_(False),
        exchange_quota=zero,
        exchange_max_fill=zero,
    )
    return out, stats


def _pipeline_body(
    keys,
    payload,
    *,
    policy: str,
    memory_rows: int,
    batch_rows: int,
    page_rows: int,
    index_rows: int,
    fanin: int,
    premerge_levels: int,
    backend: str,
    widths,
    merge: bool,
):
    """Traceable single-device pipeline: run generation scan → §4.3
    pre-merge levels → wide merge.  Jitted directly as
    :func:`_pipeline_jit`; the mesh-sharded program traces it once per
    shard inside ``shard_map`` (:func:`_sharded_fn`)."""
    TRACE_LOG.append(("pipeline", policy, int(keys.shape[0]), merge))
    M, B, P = memory_rows, batch_rows, page_rows
    chunk, _, _, _ = _engine_geometry(policy, M, B, P)
    t = _num_batches(keys.shape[0], chunk)
    n_pad = t * chunk
    bk, bp = _batch(keys, payload, chunk, t)
    width = 0 if payload is None else payload.shape[-1]
    ws = widths if widths is not None else (width, width, width)
    R = _stream_run_slots(policy, n_pad, M)
    es = match_vma(_engine_init(policy, M=M, B=B, P=P, R=R, width=width,
                                key_dtype=keys.dtype, widths=ws), keys)

    def body(carry, xs):
        ck, cp = xs
        return match_vma(_engine_step(carry, ck, cp, policy=policy, M=M, B=B,
                                      backend=backend, ws=ws), carry), None

    es, _ = jax.lax.scan(body, es, (bk, bp))
    store, lens, table, spilled, nruns, overflow = _engine_finish(
        es, policy=policy, backend=backend
    )

    if not merge:
        zero = jnp.int32(0)
        rg_stats = DeviceSpillStats(
            rows_spilled_run_generation=spilled,
            rows_spilled_merge=zero,
            runs_generated=nruns,
            merge_steps=zero,
            merge_levels=zero,
            pages_read=zero,
            rows_emitted=zero,
            index_overflowed=jnp.bool_(False),
            max_index_occupancy=zero,
            run_buffer_overflowed=overflow,
            merge_dropped_rows=jnp.bool_(False),
            rows_exchanged=zero,
            rows_retired=zero,
            exchange_dropped=jnp.bool_(False),
            exchange_quota=zero,
            exchange_max_fill=zero,
        )
        return store, lens, table, rg_stats

    return _merge_phase(
        store, lens, spilled, nruns, overflow, page_rows=P,
        index_rows=index_rows, fanin=fanin, premerge_levels=premerge_levels,
        backend=backend, out_capacity=max(n_pad, 1),
    )


_pipeline_jit = functools.partial(
    jax.jit,
    static_argnames=(
        "policy", "memory_rows", "batch_rows", "page_rows", "index_rows",
        "fanin", "premerge_levels", "backend", "widths", "merge",
    ),
)(_pipeline_body)


# ---------------------------------------------------------------------------
# mesh-sharded pipeline: per-shard run generation + key-range exchange
# ---------------------------------------------------------------------------


def resolve_mesh_axis(mesh, mesh_axis: str | None) -> str:
    """The mesh axis the pipeline shards over (default: the first)."""
    if mesh_axis is None:
        return mesh.axis_names[0]
    if mesh_axis not in mesh.axis_names:
        raise ValueError(
            f"mesh has no axis {mesh_axis!r}; axes: {mesh.axis_names}"
        )
    return mesh_axis


@functools.lru_cache(maxsize=None)
def _sharded_fn(
    mesh,
    axis: str,
    *,
    policy: str,
    memory_rows: int,
    batch_rows: int,
    page_rows: int,
    index_rows: int,
    fanin: int,
    premerge_levels: int,
    backend: str,
    widths,
    exchange_quota: int | None = None,
):
    """ONE compiled program for the whole mesh (§2.1: partitioning and
    sorting are the same physical property):

    1. each shard runs the full single-device pipeline
       (:func:`_pipeline_body`: run-generation scan into its own run
       buffer, statically planned §4.3 pre-merge levels, local wide
       merge) over its slice of the input — local early aggregation
       before any wire traffic;
    2. the shards exchange their sorted, duplicate-free outputs by
       sampled key range (:func:`~repro.distributed.groupby.
       exchange_sorted_fragments` — the same searchsorted cuts +
       ``all_to_all`` as the distributed group-by), so only unique rows
       travel;
    3. each range owner PAGE-STREAMS the ``world`` sorted fragments it
       received through the §4 wide merge — output globally sorted by
       (owner, key), EMPTY-padded per shard.

    The per-peer quota is capacity-bounded
    (:func:`~repro.distributed.groupby.default_exchange_quota` unless
    ``exchange_quota`` overrides — the host retry path passes a wider
    one), so the wire + fragment-merge footprint per shard is
    O(quota_bound + merge_page) instead of O(world × capacity); a send
    segment over quota trips ``exchange_dropped``, which ``finalize()``
    raises as the retryable
    :class:`~repro.core.types.ExchangeOverflowError`.  Stats are reduced
    across shards on device (:meth:`DeviceSpillStats.cross_shard`), so
    ``finalize()`` remains the program's single host readback and the
    loud-failure invariants hold per shard and globally.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import groupby as gb_mod

    world = mesh.shape[axis]

    def body(keys, payload):
        out, dstats = _pipeline_body(
            keys, payload, policy=policy, memory_rows=memory_rows,
            batch_rows=batch_rows, page_rows=page_rows,
            index_rows=index_rows, fanin=fanin,
            premerge_levels=premerge_levels, backend=backend,
            widths=widths, merge=True,
        )
        merged, ex = gb_mod.exchange_and_merge(
            out, axis, world, backend=backend, quota=exchange_quota,
            page_rows=page_rows,
        )
        dstats = dataclasses.replace(
            dstats,
            merge_dropped_rows=dstats.merge_dropped_rows | ex.merge_dropped,
            rows_exchanged=ex.rows_sent,
            exchange_dropped=ex.send_dropped,
            exchange_quota=jnp.int32(ex.quota),
            exchange_max_fill=ex.max_fill,
        )
        return merged, dstats.cross_shard(axis)

    state_specs = AggState(
        keys=P(axis), count=P(axis), sum=P(axis, None),
        min=P(axis, None), max=P(axis, None),
    )
    n_stats = len(dataclasses.fields(DeviceSpillStats))
    # the stats out_specs are P(): psum/pmax above make them replicated,
    # which shard_map's varying-manual-axes check verifies.
    inner = jax.shard_map(
        body, mesh=mesh, check_vma=dispatch.checks_vma(backend),
        in_specs=(P(axis), P(axis, None)),
        out_specs=(state_specs, DeviceSpillStats(*(P(),) * n_stats)),
    )

    def run(keys, payload):
        # pad so every shard sees the same static n_loc, then hand each
        # shard its contiguous slice
        n_loc = -(-keys.shape[0] // world)
        keys, payload = _pad_flat(keys, payload, world * n_loc)
        return inner(keys, payload)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def _canon_inputs(keys, payload):
    """Host-side canonicalization that never touches device values: numpy
    inputs get the reference dtype treatment; jax arrays pass through
    (so pre-placed device inputs incur zero extra transfers)."""
    if not isinstance(keys, jax.Array):
        keys = rg._np_keys(np.asarray(keys))
    if payload is not None:
        if not isinstance(payload, jax.Array):
            payload = np.asarray(payload, dtype=np.float32)
        if payload.ndim == 1:
            payload = payload[:, None]
    return keys, payload


def _host_pad_flat(keys: np.ndarray, payload, total: int):
    """Host twin of :func:`_pad_flat`: EMPTY keys and zero payload rows
    up to ``total`` rows."""
    n = keys.shape[0]
    if total == n:
        return keys, payload
    keys = np.concatenate(
        [keys, np.full(total - n, empty_key(keys.dtype), keys.dtype)])
    if payload is not None:
        payload = np.concatenate(
            [payload, np.zeros((total - n,) + payload.shape[1:], payload.dtype)])
    return keys, payload


def _place_sharded(keys, payload, mesh, axis: str):
    """Row-shard host ``keys`` / ``payload`` over ``mesh[axis]``."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    with key_dtype_context(np.dtype(keys.dtype)):
        return (jax.device_put(keys, NamedSharding(mesh, P(axis))),
                jax.device_put(payload, NamedSharding(mesh, P(axis, None))))


def _host_pad_for_geometry(keys, payload, policy: str, cfg: ExecConfig):
    """Pad HOST (NumPy) inputs to the pow2-bucketed batch geometry before
    the jit boundary, so the jit cache keys on geometry rather than N —
    a second call with a different N in the same bucket reuses the
    compiled program.  Device-resident (jax.Array) inputs pass through
    and pad inside the jit instead (no host round trip, at the cost of a
    per-N trace)."""
    if isinstance(keys, jax.Array) or isinstance(payload, jax.Array):
        return keys, payload
    chunk, _, _, _ = _engine_geometry(policy, cfg.memory_rows,
                                      cfg.batch_rows, cfg.page_rows)
    return _host_pad_flat(keys, payload,
                          _num_batches(keys.shape[0], chunk) * chunk)


def generate_runs_device(
    keys,
    payload=None,
    cfg: ExecConfig | None = None,
    *,
    policy: str = "early_agg",
    backend: str = "auto",
    widths: tuple[int, int, int] | None = None,
):
    """Scan-based run generation, entirely device-resident.

    Returns ``(store_state, lens, table, dstats)`` — a stacked run buffer
    (leading dims ``(R, C)``), per-slot run lengths, the resident table,
    and a :class:`DeviceSpillStats` pytree.  Nothing in this call blocks
    on the device; call ``dstats.finalize()`` (or read ``lens``) for the
    single host sync.  The host reference with identical semantics is
    :func:`repro.core.run_generation.generate_runs` (one blocking
    occupancy readback **per batch**).
    """
    cfg = cfg or ExecConfig()
    backend = dispatch.resolve_backend_name(backend)
    keys, payload = _canon_inputs(keys, payload)
    if payload is None:
        widths = (0, 0, 0) if widths is None else widths
    keys, payload = _host_pad_for_geometry(keys, payload, policy, cfg)
    with key_dtype_context(np.dtype(keys.dtype)):
        return _pipeline_jit(
            as_key_array(keys), payload, policy=policy,
            memory_rows=cfg.memory_rows, batch_rows=cfg.batch_rows,
            page_rows=cfg.page_rows, index_rows=cfg.memory_rows,
            fanin=cfg.fanin, premerge_levels=0,
            backend=backend, widths=widths, merge=False,
        )


def aggregate_device(
    keys,
    payload=None,
    cfg: ExecConfig | None = None,
    *,
    policy: str = "rs",
    backend: str = "auto",
    widths: tuple[int, int, int] | None = None,
    index_rows: int | None = None,
    output_estimate: int | None = None,
    mesh=None,
    mesh_axis: str | None = None,
    exchange_quota: int | None = None,
) -> tuple[AggState, DeviceSpillStats]:
    """Run generation + pre-merge levels + wide merge as ONE compiled
    program (§3 + §4).

    Pure device computation: the returned state and stats are device
    arrays and this function never synchronizes (safe under
    ``jax.transfer_guard("disallow")`` with device-resident inputs,
    once compiled).  Output is sorted by key, duplicate-free, EMPTY-
    padded to the batched input capacity.  ``output_estimate`` drives the
    §4.3 plan exactly like the host path: it fixes the (static) number of
    pre-wide merge levels; a wrong estimate shifts work between merge
    styles but never changes the answer.

    ``mesh`` (a :class:`jax.sharding.Mesh`) shards the whole pipeline
    over ``mesh_axis`` (default: the mesh's first axis): every device
    runs run generation + pre-merge + wide merge over its slice of the
    input, then a sampled key-range ``all_to_all`` exchanges the sorted,
    duplicate-free per-shard outputs (capacity-bounded per-peer quota —
    ``exchange_quota`` overrides the sampled-cut default) and each range
    owner page-streams its fragments through the §4 wide merge — output
    globally sorted by (owner, key), each shard's slice EMPTY-padded.
    Stats are psum/pmax-reduced across shards on device, so this still
    performs zero host syncs.  ``mesh=None`` is bit-for-bit today's
    single-device program.
    """
    cfg = cfg or ExecConfig()
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}; expected one of {POLICIES}")
    backend = dispatch.resolve_backend_name(backend)
    keys, payload = _canon_inputs(keys, payload)
    if payload is None:
        widths = (0, 0, 0) if widths is None else widths
    if keys.shape[0] == 0:  # static early-out: nothing to scan or merge
        width = 0 if payload is None else payload.shape[1]
        kd = np.dtype(keys.dtype)
        kd = kd if kd == np.uint64 else np.dtype(np.uint32)
        with key_dtype_context(kd):
            return (
                empty_state(0, width, key_dtype=kd, widths=widths),
                DeviceSpillStats.zeros(),
            )
    from repro.core.insort import plan_pre_merge_levels  # lazy: avoids cycle

    # `is None`, not falsy: an explicit 0 estimate must plan like the host
    est = (cfg.memory_rows * cfg.fanin if output_estimate is None
           else output_estimate)
    if mesh is None:
        r_static = _static_run_slots(policy, keys.shape[0], cfg.memory_rows,
                                     cfg.batch_rows)
        pre = plan_pre_merge_levels(est, cfg, r_static)
        keys, payload = _host_pad_for_geometry(keys, payload, policy, cfg)
        with key_dtype_context(np.dtype(keys.dtype)):
            return _pipeline_jit(
                as_key_array(keys), payload, policy=policy,
                memory_rows=cfg.memory_rows, batch_rows=cfg.batch_rows,
                page_rows=cfg.page_rows, index_rows=index_rows or cfg.memory_rows,
                fanin=cfg.fanin, premerge_levels=pre,
                backend=backend, widths=widths, merge=True,
            )
    dispatch.check_shardable(backend)
    axis = resolve_mesh_axis(mesh, mesh_axis)
    world = int(mesh.shape[axis])
    # the §4.3 plan is per shard: levels from the shard's static run-slot
    # bound (each shard generates runs over ~N/world rows)
    n_loc = -(-keys.shape[0] // world)
    r_static = _static_run_slots(policy, n_loc, cfg.memory_rows,
                                 cfg.batch_rows)
    pre = plan_pre_merge_levels(est, cfg, r_static)
    if payload is None:  # fixed (n, 0) payload: one in_spec tree
        payload = np.zeros((keys.shape[0], 0), np.float32)
    if not isinstance(keys, jax.Array):
        # host input goes straight to its shards: each device receives
        # only its contiguous slice, never the whole input via device 0
        keys, payload = _host_pad_flat(keys, payload, world * n_loc)
        keys, payload = _place_sharded(keys, payload, mesh, axis)
    fn = _sharded_fn(
        mesh, axis, policy=policy,
        memory_rows=cfg.memory_rows, batch_rows=cfg.batch_rows,
        page_rows=cfg.page_rows, index_rows=index_rows or cfg.memory_rows,
        fanin=cfg.fanin, premerge_levels=pre,
        backend=backend, widths=widths, exchange_quota=exchange_quota,
    )
    with key_dtype_context(np.dtype(keys.dtype)):
        return fn(as_key_array(keys), payload)


def _shard_out_capacity(policy: str, n: int, world: int,
                        cfg: ExecConfig) -> int:
    """Host twin of the mesh pipeline's per-shard merge output capacity
    (``max(n_pad, 1)`` inside :func:`_pipeline_body` for the shard's
    padded slice) — the statically lossless ceiling of the exchange
    retry ladder (a quota >= the per-shard capacity cannot drop)."""
    n_loc = -(-n // world)
    chunk, _, _, _ = _engine_geometry(policy, cfg.memory_rows,
                                      cfg.batch_rows, cfg.page_rows)
    return max(_num_batches(n_loc, chunk) * chunk, 1)


def insort_aggregate_device(
    keys,
    payload=None,
    cfg: ExecConfig | None = None,
    *,
    policy: str = "rs",
    backend: str = "auto",
    widths: tuple[int, int, int] | None = None,
    index_rows: int | None = None,
    output_estimate: int | None = None,
    mesh=None,
    mesh_axis: str | None = None,
    exchange_quota: int | None = None,
) -> tuple[AggState, SpillStats]:
    """:func:`aggregate_device` + the one host readback of spill stats —
    the device twin of :func:`repro.core.insort.insort_aggregate`.

    On a mesh, a cross-shard exchange whose sampled quota proved too
    small for the data's skew retries ONCE at the next pow2 quota
    (capped at the statically lossless per-shard capacity) with a loud
    log — the readback already paid here is the same one the retry
    needs, so this is the natural host decision point.  A second
    overflow propagates the :class:`ExchangeOverflowError`."""
    state, dstats = aggregate_device(
        keys, payload, cfg, policy=policy, backend=backend, widths=widths,
        index_rows=index_rows, output_estimate=output_estimate,
        mesh=mesh, mesh_axis=mesh_axis, exchange_quota=exchange_quota,
    )
    try:
        return state, dstats.finalize()
    except ExchangeOverflowError as e:
        if mesh is None:
            raise  # impossible without an exchange; don't mask bugs
        cfg_ = cfg or ExecConfig()
        axis = resolve_mesh_axis(mesh, mesh_axis)
        world = int(mesh.shape[axis])
        cap_loc = _shard_out_capacity(policy, np.asarray(keys).shape[0],
                                      world, cfg_)
        quota2 = min(_pow2_ceil(e.quota + 1), _pow2_ceil(cap_loc))
        if quota2 <= e.quota:
            raise  # already at the lossless ceiling; a retry cannot help
        _log.warning(
            "mesh exchange overflowed its per-peer quota=%d (fullest "
            "segment %d rows); retrying once at quota=%d",
            e.quota, e.max_fill, quota2,
        )
        state, dstats = aggregate_device(
            keys, payload, cfg, policy=policy, backend=backend,
            widths=widths, index_rows=index_rows,
            output_estimate=output_estimate, mesh=mesh, mesh_axis=mesh_axis,
            exchange_quota=quota2,
        )
        stats = dstats.finalize()
        return state, dataclasses.replace(stats, exchange_retries=1)


# ---------------------------------------------------------------------------
# streamed pipeline: double-buffered super-batches over the same engine
# ---------------------------------------------------------------------------
#
# The jitted pieces below advance / grow / finalize a StreamEngineState.
# All three donate the incoming state (argnum 0): XLA reuses its buffers
# for the output, so the steady-state device footprint is ONE engine
# state plus the (at most two) staged input chunks in flight.


def _absorb_chunk_body(es, bk, bp, *, policy, memory_rows, batch_rows,
                       backend, widths, local_slots, with_obs=False):
    TRACE_LOG.append(("absorb", policy, tuple(bk.shape), es.run_slots))
    # The scan carries only a LOCAL window of the run store — the slots
    # this chunk can actually reach (its exact run bound + the open
    # slot), spliced back in one dynamic_update_slice.  Carrying the full
    # store would make every scan step pay O(R) for the carry, i.e. each
    # absorb would slow down as the stream grows; with the window the
    # per-chunk cost is independent of how much has already streamed.
    # The host grow schedule guarantees R >= ridx + local_slots, so the
    # clamp below never actually moves the window over occupied slots.
    R, L = es.run_slots, min(local_slots, es.run_slots)
    ridx0 = jnp.clip(es.ridx, 0, R - L)
    loc = dataclasses.replace(
        es,
        store=jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, ridx0, L, axis=0),
            es.store),
        lens=jax.lax.dynamic_slice_in_dim(es.lens, ridx0, L, axis=0),
        ridx=es.ridx - ridx0,
    )

    def body(carry, xs):
        ck, cp = xs
        return match_vma(_engine_step(carry, ck, cp, policy=policy,
                                      M=memory_rows, B=batch_rows,
                                      backend=backend, ws=widths), carry), None

    loc, _ = jax.lax.scan(body, loc, (bk, bp))
    es = dataclasses.replace(
        loc,
        store=jax.tree.map(
            lambda a, l: jax.lax.dynamic_update_slice_in_dim(
                a, l, ridx0, axis=0),
            es.store, loc.store),
        lens=jax.lax.dynamic_update_slice_in_dim(es.lens, loc.lens, ridx0,
                                                 axis=0),
        ridx=ridx0 + loc.ridx,
    )
    if not with_obs:
        return es
    # adaptive streams get the governor's decision scalars as an extra
    # output of the SAME program: a separate _observe dispatch would hold
    # a pending read on the engine buffers and force the next (donating)
    # absorb into a defensive copy of the whole state — folded in here,
    # donation stays clean and the observation is free.
    return es, _observe_body(es)


_absorb_chunk = jax.jit(
    _absorb_chunk_body, donate_argnums=(0,),
    static_argnames=("policy", "memory_rows", "batch_rows", "backend",
                     "widths", "local_slots", "with_obs"),
)


def _engine_init_body(*, policy, memory_rows, batch_rows, page_rows,
                      run_slots, width, key_dtype, widths):
    TRACE_LOG.append(("init", policy, run_slots))
    return _engine_init(
        policy, M=memory_rows, B=batch_rows, P=page_rows, R=run_slots,
        width=width, key_dtype=key_dtype, widths=widths,
    )


# every argument is static: the jit exists so the state is BORN on device
# (no eager host constants — streaming works under a transfer guard)
_engine_init_jit = jax.jit(
    _engine_init_body,
    static_argnames=("policy", "memory_rows", "batch_rows", "page_rows",
                     "run_slots", "width", "key_dtype", "widths"),
)


def _grow_store_body(es, *, run_slots):
    TRACE_LOG.append(("grow", run_slots))
    store, lens = _pad_slots(es.store, es.lens, run_slots)
    return dataclasses.replace(es, store=store, lens=lens)


# no donation: the grown store's shapes differ from the old state's, so
# XLA could not reuse the buffers anyway (it would only warn)
_grow_store = jax.jit(_grow_store_body, static_argnames=("run_slots",))


def _trim_slots(es, trim: int):
    """Drop the run slots past the exact bound: the pow2 growth schedule
    overshoots so absorbs stay cache hits, but by finalize the total row
    count is host-known and runs can only occupy the first ``trim``
    slots — merging the (empty) overshoot would cost real merge work."""
    if trim >= es.store.keys.shape[0]:
        return es
    store = jax.tree.map(lambda a: a[:trim], es.store)
    return dataclasses.replace(es, store=store, lens=es.lens[:trim])


def _finalize_stream_body(es, retired, *, policy, page_rows, index_rows,
                          fanin, premerge_levels, backend, out_capacity,
                          trim):
    """Drain + pre-merge + wide merge of a stream engine state.

    This ONE program serves both the destructive finalize and the
    merge-on-read snapshot: it only *reads* ``es`` and emits into a
    fresh output buffer, so (un-donated) it is non-destructive by
    construction — the snapshot path simply keeps the input state alive.
    ``retired`` threads the service's eviction accumulator into the
    stats (``None`` when no eviction ever ran)."""
    TRACE_LOG.append(("finalize", policy, out_capacity))
    es = _trim_slots(es, trim)
    ws = (es.store.sum.shape[-1], es.store.min.shape[-1],
          es.store.max.shape[-1])  # store planes are stacked (R, C, w)
    fresh_out = empty_state(out_capacity, max(ws), key_dtype=es.key_dtype,
                            widths=ws)
    store, lens, table, spilled, nruns, overflow = _engine_finish(
        es, policy=policy, backend=backend
    )
    return _merge_phase(
        store, lens, spilled, nruns, overflow, page_rows=page_rows,
        index_rows=index_rows, fanin=fanin, premerge_levels=premerge_levels,
        backend=backend, out_capacity=out_capacity, rows_retired=retired,
        out_buffer=fresh_out,
    )


# no donation: the merged output's shapes differ from the engine state's
# leaves, so the donated buffers would go unused (XLA warns, no benefit).
# Non-donation is also load-bearing for the service: snapshot_device()
# runs this very program on the LIVE engine state.
_finalize_stream = jax.jit(
    _finalize_stream_body,
    static_argnames=("policy", "page_rows", "index_rows", "fanin",
                     "premerge_levels", "backend", "out_capacity", "trim"),
)


# ---------------------------------------------------------------------------
# key eviction / TTL: retire expired key ranges from the live engine
# ---------------------------------------------------------------------------


def _retire_sorted_prefix(planes: AggState, cut, valid):
    """Shift every slot's sorted row-planes left by its prefix ``cut`` and
    restore the EMPTY fill beyond the surviving rows.

    ``planes`` leaves are (R, C[, w]); ``cut``/``valid`` are (R,) with
    ``cut <= valid`` (every slot is ascending-sorted with EMPTY — the max
    sentinel — padding its tail, so a ``searchsorted`` cut can never
    reach into the pad).  The fills reproduce :func:`empty_state` byte
    for byte, so a fully retired slot is indistinguishable from a fresh
    one."""
    C = planes.keys.shape[1]
    kd = planes.keys.dtype
    ar = jnp.arange(C, dtype=jnp.int32)
    idx2 = jnp.minimum(ar[None, :] + cut[:, None], max(C - 1, 0))
    live = ar[None, :] < (valid - cut)[:, None]
    inf = np.float32(np.inf)
    fills = AggState(keys=jnp.asarray(empty_key(kd), kd),
                     count=jnp.int32(0), sum=jnp.float32(0),
                     min=jnp.float32(inf), max=jnp.float32(-inf))

    def shift(a, f):
        if a.shape[1] == 0:
            return a
        if a.ndim == 2:
            return jnp.where(live, jnp.take_along_axis(a, idx2, axis=1), f)
        return jnp.where(live[:, :, None],
                         jnp.take_along_axis(a, idx2[:, :, None], axis=1), f)

    return jax.tree.map(shift, planes, fills)


def _evict_compact_body(es, threshold, retired, *, policy, backend):
    """Retire every resident row with key < ``threshold`` from the live
    engine state and compact the surviving run slots to the store prefix.

    Every component of the engine keeps its rows ascending-sorted with
    EMPTY-padded tails (closed slots are whole sorted runs, the RS open
    slot's ``[0, cursor)`` prefix is ascending by the frontier invariant,
    tables are OrderedIndexes), so retirement is a per-slot
    ``searchsorted`` prefix cut — no scatter, no readback.  Surviving
    closed runs are permuted to the slot prefix (stable, order
    preserving) so the host's input-over-memory slot bound can be
    re-baselined from the returned ``ridx`` and absorbs keep splicing at
    the high-water mark.  ``retired`` accumulates the number of state
    rows removed (``None`` on first eviction): nothing leaves the engine
    without being counted here or emitted by a snapshot/finalize."""
    del backend  # uniform across backends: pure lax gather/permute
    TRACE_LOG.append(("evict", policy))
    R, C = es.run_slots, es.slot_rows
    arR = jnp.arange(R, dtype=jnp.int32)
    thr = jnp.asarray(threshold, es.store.keys.dtype)
    is_open = arR == es.ridx
    # per-slot valid rows: closed slots carry lens, the RS open slot's
    # prefix length is the cursor (its lens stays 0 until the run closes)
    valid = jnp.maximum(es.lens, jnp.where(is_open, es.cursor, 0))
    cut = jax.vmap(
        lambda row: jnp.searchsorted(row, thr, side="left").astype(jnp.int32)
    )(es.store.keys)
    cut = jnp.minimum(cut, valid)
    store = _retire_sorted_prefix(es.store, cut, valid)
    lens_new = es.lens - jnp.minimum(cut, es.lens)
    cursor = es.cursor - jnp.where(
        (es.ridx >= 0) & (es.ridx < R),
        cut[jnp.clip(es.ridx, 0, max(R - 1, 0))], 0,
    )
    # compact: surviving closed runs first (order preserved), then the
    # open slot, then the all-EMPTY retired slots
    order = jnp.where(lens_new > 0, 0, jnp.where(is_open, 1, 2))
    perm = jnp.argsort(order, stable=True)
    store = jax.tree.map(lambda a: a[perm], store)
    lens_new = lens_new[perm]
    ridx = jnp.sum(lens_new > 0, dtype=jnp.int32)
    delta = jnp.sum(cut, dtype=jnp.int32)
    # resident tables (early-agg index / RS partitions): same prefix cut,
    # lifted to one (1, capT) slot
    table, table2 = es.table, es.table2
    for name in ("table", "table2"):
        t = getattr(es, name)
        if t.capacity == 0:
            continue
        occ = t.occupancy()
        cut_t = jnp.searchsorted(t.keys, thr, side="left").astype(jnp.int32)
        cut_t = jnp.minimum(cut_t, occ)
        lifted = jax.tree.map(lambda a: a[None], t)
        lifted = _retire_sorted_prefix(lifted, cut_t[None], occ[None])
        t = jax.tree.map(lambda a: a[0], lifted)
        delta = delta + cut_t
        if name == "table":
            table = t
        else:
            table2 = t
    zero = jnp.int32(0)
    retired = delta + (zero if retired is None else retired)
    es = dataclasses.replace(
        es, table=table, table2=table2, store=store, lens=lens_new,
        cursor=cursor, ridx=ridx,
    )
    return es, retired


# donated: eviction rewrites the state in place (same shapes throughout)
_evict_compact = jax.jit(
    _evict_compact_body, static_argnames=("policy", "backend"),
    donate_argnums=(0,),
)


# ---------------------------------------------------------------------------
# adaptive streaming: observation readback + the policy-transition program
# ---------------------------------------------------------------------------


def _observe_body(es: StreamEngineState):
    """The decision scalars the adaptive governor steers on, packed into
    ONE int32 vector so the amortized readback is a single small
    transfer: (rows absorbed, duplicate encounters, rows spilled,
    resident table occupancy, run slots used)."""
    TRACE_LOG.append(("observe", es.run_slots))
    occ_t = es.table.occupancy() + es.table2.occupancy()
    return jnp.stack([es.absorbed, es.dups, es.spilled, occ_t, es.ridx])


_observe = jax.jit(_observe_body)


def _switch_flush_body(es: StreamEngineState, *, policy: str,
                       backend: str) -> StreamEngineState:
    """Close out the CURRENT policy arm so the next chunk can be absorbed
    under a different one: close the open replacement-selection run (rs
    only), then flush the resident table content as one closed sorted run
    and reset the tables/frontier/cursor to their fresh state.

    This is what makes mid-flight switching legal: after the transition
    every arm sees exactly the state it would after its own ``init`` —
    empty tables, closed sorted runs in the store — and the finalize
    merge is policy-agnostic over the store (each arm's runs are sorted;
    the wide merge aggregates across and within runs)."""
    TRACE_LOG.append(("switch", policy, es.run_slots))
    R, C = es.run_slots, es.slot_rows
    if policy == "rs":
        # close the open run at its current cursor (no-op when cursor==0)
        lens = es.lens.at[jnp.where(es.cursor > 0, es.ridx, R)].set(
            es.cursor, mode="drop"
        )
        ridx = es.ridx + (es.cursor > 0).astype(jnp.int32)
        # collapse both partitions into one sorted resident table (the
        # run/next distinction is meaningless once the run is closed)
        cap = es.table.capacity
        merged = jax.tree.map(
            lambda x: x[:cap],
            sorted_ops.merge_absorb(es.table, es.table2, backend=backend,
                                    assume_unique=True),
        )
        es = dataclasses.replace(
            es, table=merged, table2=empty_like(es.table2, es.table2.capacity),
            frontier=jnp.zeros((), es.frontier.dtype), lens=lens,
            cursor=jnp.int32(0), ridx=ridx,
        )
    if es.table.capacity:
        # flush the resident (sorted, unique) table as one closed run
        occ = es.table.occupancy()
        slot = jnp.where(occ > 0, es.ridx, R)
        store = jax.tree.map(
            lambda d, s: d.at[slot].set(s, mode="drop"), es.store,
            _pad_rows(es.table, C),
        )
        lens = es.lens.at[slot].set(occ, mode="drop")
        es = dataclasses.replace(
            es, store=store, lens=lens,
            ridx=es.ridx + (occ > 0).astype(jnp.int32),
            spilled=es.spilled + occ,
            table=empty_like(es.table, es.table.capacity),
        )
    return es


# donated: the transition rewrites the state in place (same shapes), so
# back-to-back switch → absorb reuses the engine buffers
_switch_flush = jax.jit(
    _switch_flush_body, static_argnames=("policy", "backend"),
    donate_argnums=(0,),
)


def _switch_reshape_body(es: StreamEngineState, *, slot_rows, capT, capT2,
                         width, widths):
    """Re-shape a just-flushed engine state to the incoming arm's NATIVE
    geometry.  The tables are empty after :func:`_switch_flush_body`, so
    they are simply re-allocated at the new capacities; the run store
    only ever RATCHETS wider (closed runs own their columns, narrowing
    could drop rows) — every slot's rows are left-packed with EMPTY
    tails, so splicing the old store into a fresh wider empty one
    preserves the per-slot invariant.

    Keeping each arm on its native shapes is what makes an adaptive
    stream that holds one arm run the exact per-chunk programs the fixed
    policy runs — no wide-geometry tax on the steady state; only an
    actual switch pays this (one state copy)."""
    TRACE_LOG.append(("reshape", slot_rows, capT, capT2))
    kd = es.store.keys.dtype
    ws = widths if widths is not None else (width, width, width)
    store = es.store
    if slot_rows != es.slot_rows:
        empty = _stacked_empty(es.run_slots, slot_rows, width,
                               key_dtype=kd, widths=ws)
        store = jax.tree.map(
            lambda e, a: jax.lax.dynamic_update_slice(e, a, (0,) * e.ndim),
            empty, store)
    return dataclasses.replace(
        es,
        table=empty_state(capT, width, key_dtype=kd, widths=ws),
        table2=empty_state(capT2, width, key_dtype=kd, widths=ws),
        store=store,
    )


# no donation: the reshaped state's buffer shapes differ from the input's
# so XLA could not alias them anyway — switches are rare (one copy each)
_switch_reshape = jax.jit(
    _switch_reshape_body,
    static_argnames=("slot_rows", "capT", "capT2", "width", "widths"),
)


@dataclasses.dataclass
class StagedChunk:
    """A super-batch already on device: ``jax.device_put`` was dispatched
    (asynchronously) but the engine has not absorbed it yet — the unit of
    double buffering."""

    bk: jax.Array  # (t, chunk) batched keys, EMPTY-padded tail
    bp: jax.Array  # (t, chunk, V) batched payload
    rows: int  # valid input rows in this chunk
    rows_padded: int  # t * chunk


class StreamingAggregator:
    """Feed the fused external-aggregation engine super-batch by
    super-batch from the host.

    The carry between chunks is a :class:`StreamEngineState` that never
    leaves the device; absorbing a chunk is ONE jitted dispatch with the
    previous state donated, and the host performs **zero** readbacks
    until :meth:`finalize` (the single sync — same contract as the
    one-shot :func:`aggregate_device`).

    Typical use is through :func:`aggregate_device_stream`, which adds
    the double-buffered drive loop; the raw protocol is::

        agg = StreamingAggregator(cfg, policy="rs", key_dtype=np.uint32,
                                  width=V)
        staged = agg.stage(keys0, pay0)     # async H2D of chunk 0
        for keys, pay in chunks:
            nxt = agg.stage(keys, pay)      # H2D of k+1 in flight while…
            agg.absorb_staged(staged)       # …the device absorbs chunk k
            staged = nxt
        agg.absorb_staged(staged)
        state, stats = agg.finalize()

    Sizing is host-computed from the cumulative padded row count (every
    flushed run holds > M rows, so slots are bounded by input over
    memory): the run store grows geometrically (pow2 slot counts) with a
    jitted, donated concat — never a readback.  Chunk geometry is
    pow2-bucketed, so the number of distinct compiled programs is
    O(log max-chunk-rows + log total-rows), independent of chunk count.

    ``mesh`` streams per-shard slices of every chunk through the same
    engine under ``shard_map``; finalize then runs the key-range exchange
    + per-owner merge of the one-shot sharded pipeline, returning a
    globally (owner, key)-sorted state and cross-shard-reduced stats.

    ``policy="adaptive"`` keeps the engine state at the current arm's
    NATIVE geometry (a switch re-shapes it — tables re-allocated, the
    run store ratcheting wider only) and lets a
    :class:`repro.core.adaptive.PolicyGovernor` pick the concrete
    run-generation arm (early_agg / rs / traditional) from the engine's
    own observed duplicate rate: every ``k``-th chunk the
    host reads ONE small decision vector back (an explicit
    ``jax.device_get`` — legal under ``jax.transfer_guard("disallow")``)
    and may dispatch a policy-transition program before the next absorb.
    The zero-readback contract relaxes to **O(stream/k) scalar
    readbacks**, counted in ``readbacks_paid`` and surfaced via
    ``SpillStats``.  Adaptive mode requires ``mesh=None`` and
    ``memory_rows % batch_rows == 0`` (chunks are staged at unit-M
    granularity and re-shaped per arm).
    """

    def __init__(
        self,
        cfg: ExecConfig | None = None,
        *,
        policy: str = "rs",
        key_dtype=np.uint32,
        width: int = 0,
        widths: tuple[int, int, int] | None = None,
        backend: str = "auto",
        index_rows: int | None = None,
        output_estimate: int | None = None,
        output_rows: int | None = None,
        mesh=None,
        mesh_axis: str | None = None,
        governor=None,
    ):
        cfg = cfg or ExecConfig()
        if policy not in STREAM_POLICIES:
            raise ValueError(
                f"unknown policy {policy!r}; expected one of {STREAM_POLICIES}"
            )
        self.cfg = cfg
        self.policy = policy
        self.backend = dispatch.resolve_backend_name(backend)
        self.key_dtype = np.dtype(key_dtype)
        if self.key_dtype not in (np.dtype(np.uint32), np.dtype(np.uint64)):
            raise TypeError(
                f"key_dtype must be uint32 or uint64, got {self.key_dtype}"
            )
        self.width = int(width)
        self.widths = (tuple(widths) if widths is not None
                       else (self.width,) * 3)
        self.index_rows = index_rows or cfg.memory_rows
        self.output_estimate = output_estimate
        self.output_rows = output_rows
        self._chunk = _engine_geometry(policy, cfg.memory_rows,
                                       cfg.batch_rows, cfg.page_rows)[0]
        self.mesh = mesh
        self.axis = (resolve_mesh_axis(mesh, mesh_axis)
                     if mesh is not None else None)
        self.world = int(mesh.shape[self.axis]) if mesh is not None else 1
        if mesh is not None:
            dispatch.check_shardable(self.backend)
            self._fns = _mesh_stream_fns(
                mesh, self.axis, policy=policy,
                memory_rows=cfg.memory_rows, batch_rows=cfg.batch_rows,
                page_rows=cfg.page_rows, index_rows=self.index_rows,
                fanin=cfg.fanin, backend=self.backend, widths=self.widths,
                width=self.width, key_dtype_name=self.key_dtype.name,
            )
        self._es: StreamEngineState | None = None
        self._R = 0  # per-shard run slots currently allocated
        self._finalized = False
        self.rows_seen = 0
        self.rows_padded = 0  # cumulative padded rows (all shards)
        # service-mode extras (inert until snapshot()/evict_below() are
        # used): the device-resident retired-row accumulator, and the
        # slot-accounting baseline taken at the last eviction — eviction
        # compacts live runs to the store prefix and re-anchors the
        # host's input-over-memory slot bound there.
        self._retired = None  # created device-side by the first evict
        self._base_slots = 0  # live closed runs (+ slack) at the baseline
        self._rows_since_evict = 0  # padded rows absorbed since baseline
        # adaptive-mode extras (inert for fixed policies): the concrete
        # run-generation arm the next absorb uses, the governor steering
        # it, and the observation/switch accounting.
        self.policy_events: list[dict] = []
        self.readbacks_paid = 0
        self._chunks_absorbed = 0
        self._last_dup_rate = 0.0
        self._pending_obs = None  # boundary observation awaiting harvest
        self._last_obs_vec = None  # newest boundary-chunk observation
        if policy == "adaptive":
            if mesh is not None:
                raise ValueError(
                    "policy='adaptive' does not compose with mesh= yet — "
                    "pick a fixed policy for sharded streams"
                )
            if cfg.memory_rows % cfg.batch_rows:
                raise ValueError(
                    "policy='adaptive' needs memory_rows divisible by "
                    f"batch_rows (chunks are staged at unit-M granularity "
                    f"and re-shaped per arm), got M={cfg.memory_rows} "
                    f"B={cfg.batch_rows}"
                )
            from repro.core import adaptive as adaptive_mod

            self._governor = (governor if isinstance(
                governor, adaptive_mod.PolicyGovernor)
                else adaptive_mod.PolicyGovernor(cfg, config=governor))
            # the engine state is created lazily at the first absorb, at
            # THIS arm's native geometry — not at a one-size-fits-all
            # wide shape (see _switch_reshape for the switch-time cost)
            self._arm = self._governor.start_arm(
                output_estimate=output_estimate)
        else:
            if governor is not None:
                # refusing loudly here is the satellite contract: a
                # governor that silently never steers is indistinguishable
                # from a working adaptive stream until the bench lies.
                if mesh is not None:
                    raise ValueError(
                        "governor= was passed on a mesh= stream, but the "
                        "adaptive governor does not compose with mesh= "
                        "yet (it needs a cross-shard observation reduce — "
                        "a documented ROADMAP follow-on).  It would have "
                        "silently run the fixed policy "
                        f"{policy!r}; pick a fixed policy and drop "
                        "governor=, or run unsharded with "
                        "policy='adaptive'"
                    )
                raise ValueError(
                    f"governor= was passed with fixed policy {policy!r}; "
                    "it would have been silently ignored — use "
                    "policy='adaptive' to let the governor steer, or "
                    "drop governor="
                )
            self._governor = None
            self._arm = policy

    # -- staging ---------------------------------------------------------

    def _prep(self, keys, payload):
        """Host-side canonicalize + pad one chunk to its pow2-bucketed
        batch geometry (NumPy only — under a transfer guard the explicit
        ``device_put`` in :meth:`stage` is the sole device touch)."""
        keys = rg._np_keys(np.asarray(keys))
        if keys.dtype != self.key_dtype:
            raise TypeError(
                f"chunk key dtype {keys.dtype} != aggregator key_dtype "
                f"{self.key_dtype}"
            )
        n = keys.shape[0]
        if payload is None:
            payload = np.zeros((n, self.width), np.float32)
        else:
            payload = np.asarray(payload, dtype=np.float32)
            if payload.ndim == 1:
                payload = payload[:, None]
        if payload.shape != (n, self.width):
            raise ValueError(
                f"chunk payload shape {payload.shape} != "
                f"({n}, width={self.width})"
            )
        n_loc = -(-n // self.world)
        t = _num_batches(n_loc, self._chunk)
        n_pad = self.world * t * self._chunk
        if n_pad > n:
            keys = np.concatenate([
                keys,
                np.full(n_pad - n, empty_key(self.key_dtype), self.key_dtype),
            ])
            payload = np.concatenate([
                payload, np.zeros((n_pad - n, self.width), np.float32),
            ])
        bk = keys.reshape(self.world * t, self._chunk)
        bp = payload.reshape(self.world * t, self._chunk, self.width)
        return bk, bp, n, n_pad

    def stage(self, keys, payload=None) -> StagedChunk | None:
        """Start the (asynchronous) host→device transfer of one chunk.

        Returns a :class:`StagedChunk` to pass to :meth:`absorb_staged`
        later — staging chunk k+1 before absorbing chunk k is what hides
        the transfer behind compute.  Empty chunks return None."""
        if np.asarray(keys).shape[0] == 0:
            return None
        bk, bp, n, n_pad = self._prep(keys, payload)
        with key_dtype_context(self.key_dtype):
            if self.mesh is not None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                bk = jax.device_put(bk, NamedSharding(self.mesh, P(self.axis)))
                bp = jax.device_put(bp, NamedSharding(self.mesh, P(self.axis)))
            else:
                bk, bp = jax.device_put((bk, bp))
        return StagedChunk(bk=bk, bp=bp, rows=n, rows_padded=n_pad)

    # -- absorbing -------------------------------------------------------

    def _bound(self, rows_padded: int) -> int:
        return _stream_run_slots(self.policy, rows_padded // self.world,
                                 self.cfg.memory_rows)

    def _local_slots(self, chunk_padded: int) -> int:
        """Run slots one chunk can reach: its exact bound + the open slot
        (the absorb scan carries only this window of the store).
        Adaptive streams size the window for the CURRENT arm — the
        traditional arm's unit-M chunk reaches 2 slots, not the
        conservative arm-mix bound the cumulative schedule uses."""
        arm = self._arm if self.policy == "adaptive" else self.policy
        return _stream_run_slots(arm, chunk_padded // self.world,
                                 self.cfg.memory_rows) + 1

    def _bound_total(self, rows_since_baseline: int) -> int:
        """Slot bound honouring the eviction baseline: live runs present
        at the last evict (``_base_slots``, with finish slack) plus the
        input-over-memory bound of the rows absorbed since."""
        return self._base_slots + self._bound(rows_since_baseline)

    def _slots_needed(self, rows_padded_total: int, chunk_padded: int) -> int:
        # the store must cover the cumulative bound AND the local window
        # the next absorb splices at the current high-water mark (the
        # dynamic_update_slice must never clamp over occupied slots)
        prev = rows_padded_total - chunk_padded
        return _pow2_ceil(max(
            self._bound_total(rows_padded_total),
            self._bound_total(prev) + self._local_slots(chunk_padded),
        ))

    def absorb_staged(self, staged: StagedChunk | None) -> None:
        """Absorb a previously staged chunk: one jitted scan dispatch, the
        engine state donated — no host synchronization."""
        if staged is None:
            return
        if self._finalized:
            raise RuntimeError("StreamingAggregator already finalized")
        needed = self._slots_needed(
            self._rows_since_evict + staged.rows_padded, staged.rows_padded
        )
        local = self._local_slots(staged.rows_padded)
        with key_dtype_context(self.key_dtype):
            if self._es is None:
                self._R = needed
                if self.mesh is None:
                    # adaptive: init at the START ARM's native geometry
                    # (self._arm == self.policy for fixed streams)
                    self._es = _engine_init_jit(
                        policy=self._arm,
                        memory_rows=self.cfg.memory_rows,
                        batch_rows=self.cfg.batch_rows,
                        page_rows=self.cfg.page_rows, run_slots=needed,
                        width=self.width, key_dtype=self.key_dtype.name,
                        widths=self.widths,
                    )
                else:
                    self._es = self._fns.init(needed)()
            elif needed > self._R:
                self._R = needed
                if self.mesh is None:
                    self._es = _grow_store(self._es, run_slots=needed)
                else:
                    self._es = self._fns.grow(needed)(self._es)
            if self.mesh is None:
                bk, bp = staged.bk, staged.bp
                arm_chunk = _engine_geometry(
                    self._arm, self.cfg.memory_rows, self.cfg.batch_rows,
                    self.cfg.page_rows)[0]
                if arm_chunk != bk.shape[-1]:
                    # adaptive: chunks are staged at unit-M granularity;
                    # re-batch (a device-side reshape, no transfer) to the
                    # current arm's input granularity.  The batch count is
                    # spelled out because a width-0 payload has zero
                    # elements and cannot infer a -1 dimension.
                    t_arm = bk.shape[0] * (bk.shape[-1] // arm_chunk)
                    bk = bk.reshape(t_arm, arm_chunk)
                    bp = bp.reshape(t_arm, arm_chunk, bp.shape[-1])
                # the observation vector is only harvested at governor
                # boundaries (every k-th chunk), so only the absorb that
                # completes an interval pays for emitting it — the other
                # k-1 chunks run the same program a fixed-policy stream
                # does (two jit cache entries per arm, not 2x compiles
                # per chunk)
                want_obs = (
                    self._governor is not None
                    and (self._chunks_absorbed + 1)
                    % self._governor.interval == 0
                )
                out = _absorb_chunk(
                    self._es, bk, bp, policy=self._arm,
                    memory_rows=self.cfg.memory_rows,
                    batch_rows=self.cfg.batch_rows, backend=self.backend,
                    widths=self.widths, local_slots=local,
                    with_obs=want_obs,
                )
                if want_obs:
                    self._es, self._last_obs_vec = out
                else:
                    self._es = out
            else:
                self._es = self._fns.absorb(local)(
                    self._es, staged.bk, staged.bp)
        self.rows_seen += staged.rows
        self.rows_padded += staged.rows_padded
        self._rows_since_evict += staged.rows_padded
        self._chunks_absorbed += 1
        if (self._governor is not None
                and self._chunks_absorbed % self._governor.interval == 0):
            self._maybe_adapt()

    def absorb(self, keys, payload=None) -> None:
        """stage + absorb in one call (no overlap — prefer the staged
        protocol or :func:`aggregate_device_stream` for throughput)."""
        self.absorb_staged(self.stage(keys, payload))

    # -- adaptive policy switching ----------------------------------------

    @property
    def arm(self) -> str:
        """The concrete run-generation policy the next absorb will use
        (== ``policy`` for fixed-policy streams)."""
        return self._arm

    def observe(self):
        """Read the engine's decision scalars back (ONE explicit
        ``jax.device_get`` of a 5-int vector — counted in
        ``readbacks_paid``).  Returns a
        :class:`repro.core.adaptive.Observation`."""
        from repro.core import adaptive as adaptive_mod

        if self._es is None:
            return adaptive_mod.Observation(0, 0, 0, 0, 0)
        with key_dtype_context(self.key_dtype):
            vec = jax.device_get(_observe(self._es))
        self.readbacks_paid += 1
        return adaptive_mod.Observation(
            rows_absorbed=int(vec[0]), dup_rows=int(vec[1]),
            rows_spilled=int(vec[2]), table_rows=int(vec[3]),
            run_slots_used=int(vec[4]),
        )

    def _maybe_adapt(self) -> None:
        """Governor boundary: harvest the observation that rode out of
        the PREVIOUS boundary's absorb (its chunk retired an interval
        ago, so the explicit ``device_get`` returns without draining the
        dispatch queue), keep this boundary's for the next one, and ask
        the governor for the next arm.  Pipelining the readback costs
        the governor one interval of decision lag but keeps the ingest
        loop free of host→device sync bubbles; only an actual switch
        pays a fresh synchronous :meth:`observe` (its slot re-anchor
        needs the current high-water mark, not the lagged one)."""
        from repro.core import adaptive as adaptive_mod

        pending = self._pending_obs
        self._pending_obs = self._last_obs_vec
        if pending is None:
            return  # first boundary: the observation pipeline is priming
        vec = jax.device_get(pending)
        self.readbacks_paid += 1
        obs = adaptive_mod.Observation(
            rows_absorbed=int(vec[0]), dup_rows=int(vec[1]),
            rows_spilled=int(vec[2]), table_rows=int(vec[3]),
            run_slots_used=int(vec[4]),
        )
        self._last_dup_rate = obs.duplicate_rate
        nxt = self._governor.decide(obs, current=self._arm)
        if nxt != self._arm:
            obs_now = self.observe()  # fresh + synchronous, counted
            self._last_dup_rate = obs_now.duplicate_rate
            self._switch_arm(nxt, obs_now)

    def _switch_arm(self, to: str, obs) -> None:
        """Transition the engine to arm ``to``: close the open rs run,
        flush the resident tables as one closed run (a donated in-place
        program), re-shape the state to ``to``'s native geometry (tables
        re-allocated at the new capacity, store ratcheted wider if
        needed), and re-anchor the host's run-slot accounting at the
        observed high-water mark (the flushed runs can carry < M rows,
        so input-over-memory alone no longer bounds the slot count)."""
        with key_dtype_context(self.key_dtype):
            self._es = _switch_flush(self._es, policy=self._arm,
                                     backend=self.backend)
            _, C_to, capT_to, capT2_to = _engine_geometry(
                to, self.cfg.memory_rows, self.cfg.batch_rows,
                self.cfg.page_rows)
            C_new = max(C_to, self._es.slot_rows)
            if (C_new != self._es.slot_rows
                    or capT_to != self._es.table.capacity
                    or capT2_to != self._es.table2.capacity):
                self._es = _switch_reshape(
                    self._es, slot_rows=C_new, capT=capT_to,
                    capT2=capT2_to, width=self.width, widths=self.widths)
        self.policy_events.append({
            "rows_seen": self.rows_seen,
            "from": self._arm,
            "to": to,
            "duplicate_rate": round(obs.duplicate_rate, 4),
        })
        self._arm = to
        # observed ridx + ≤2 transition runs + the rs finish slack
        self._base_slots = int(obs.run_slots_used) + 2 + 4
        self._rows_since_evict = 0
        self._pending_obs = None  # observed the pre-flush state: stale
        self._last_obs_vec = None

    def _patch_stats(self, stats: SpillStats) -> SpillStats:
        """Surface the adaptive observation block on the host stats.
        Fixed-policy streams that never observed return ``stats``
        unchanged, preserving exact as_dict parity with the one-shot
        pipeline."""
        if self._governor is None and not self.readbacks_paid:
            return stats
        return dataclasses.replace(
            stats,
            duplicate_rate=self._last_dup_rate,
            policy_switches=len(self.policy_events),
            readbacks_paid=self.readbacks_paid,
        )

    def wait(self) -> None:
        """Block until every dispatched absorb/switch has completed on
        device (benchmark phase boundaries; never needed for
        correctness)."""
        if self._es is not None:
            jax.block_until_ready(jax.tree.leaves(self._es))

    # -- finalizing ------------------------------------------------------

    def finalize_device(self) -> tuple[AggState, DeviceSpillStats]:
        """Drain + pre-merge + wide merge (+ mesh exchange).  Returns
        device values and performs NO host sync — the transfer-guard-safe
        half of :meth:`finalize`.  Consumes (donates) the engine state."""
        if self._finalized:
            raise RuntimeError("StreamingAggregator already finalized")
        self._finalized = True
        if self._es is None:  # nothing absorbed: empty result
            with key_dtype_context(self.key_dtype):
                return (
                    empty_state(0, self.width, key_dtype=self.key_dtype,
                                widths=self.widths),
                    DeviceSpillStats.zeros(),
                )
        pre, out_cap, trim = self._merge_plan(bucketed=False)
        es, self._es = self._es, None
        return self._run_merge(es, pre, out_cap, trim)

    def _retry_capacity(self, entry_point: str, err: Exception, es,
                        pre: int, out_cap: int, trim: int):
        """The wide merge dropped rows: re-run the (non-donating) merge
        program ONCE with the output capacity at the next pow2 and one
        more pre-merge level (fewer, bigger runs also shrink the merge
        index's resident width).  Loud by design; a second overflow
        propagates."""
        out_cap2 = _pow2_ceil(out_cap + 1)
        _log.warning(
            "%s overflowed its out_capacity=%d (%s); retrying once at "
            "out_capacity=%d with %d pre-merge levels",
            entry_point, out_cap, err, out_cap2, pre + 1,
        )
        state, dstats = self._run_merge(es, pre + 1, out_cap2, trim)
        return state, dstats.finalize(entry_point=entry_point)

    def _retry_exchange(self, entry_point: str, err, es,
                        pre: int, out_cap: int, trim: int):
        """The mesh exchange's capacity-bounded quota was too small for
        the data's skew: re-run the (non-donating) merge + exchange
        program ONCE at the next pow2 quota, capped at the statically
        lossless per-shard output capacity.  Loud by design; a second
        overflow propagates (same contract as :meth:`_retry_capacity`)."""
        quota2 = min(_pow2_ceil(err.quota + 1), _pow2_ceil(out_cap))
        if quota2 <= err.quota:
            raise err  # already at the lossless ceiling
        _log.warning(
            "%s exchange overflowed its per-peer quota=%d (fullest "
            "segment %d rows); retrying once at quota=%d",
            entry_point, err.quota, err.max_fill, quota2,
        )
        state, dstats = self._run_merge(es, pre, out_cap, trim,
                                        exchange_quota=quota2)
        stats = dstats.finalize(entry_point=entry_point)
        return state, dataclasses.replace(
            stats, exchange_retries=stats.exchange_retries + 1)

    def finalize(self) -> tuple[AggState, SpillStats]:
        """:meth:`finalize_device` + the ONE host readback of spill stats
        (raises loudly on run-buffer overflow; a merge-output overflow —
        or, on a mesh, an exchange-quota overflow — is retried once at
        the next pow2 before raising)."""
        if self._finalized:
            raise RuntimeError("StreamingAggregator already finalized")
        if self._es is None:  # nothing absorbed: empty result
            state, dstats = self.finalize_device()
            return state, self._patch_stats(dstats.finalize())
        pre, out_cap, trim = self._merge_plan(bucketed=False)
        es, self._es = self._es, None
        self._finalized = True
        state, dstats = self._run_merge(es, pre, out_cap, trim)
        try:
            stats = dstats.finalize()
        except ExchangeOverflowError as e:
            state, stats = self._retry_exchange(
                "finalize", e, es, pre, out_cap, trim)
        except MergeOverflowError as e:
            state, stats = self._retry_capacity(
                "finalize", e, es, pre, out_cap, trim)
        return state, self._patch_stats(stats)

    # -- merge-on-read snapshots + eviction (the service protocol) -------

    def _merge_plan(self, *, bucketed: bool) -> tuple[int, int, int]:
        """Static merge-phase plan ``(premerge_levels, out_capacity,
        trim)``.  ``bucketed`` pow2-buckets the capacity statics so a
        long-lived session's periodic snapshots hit O(log N) compiled
        programs instead of one per snapshot; pre-merge levels are always
        planned from the EXACT slot bound (extra all-EMPTY trim slots are
        merge no-ops and never perturb stats, but the level plan itself
        must match the one-shot pipeline's for stats parity)."""
        from repro.core.insort import plan_pre_merge_levels  # lazy: cycle

        est = (self.cfg.memory_rows * self.cfg.fanin
               if self.output_estimate is None else self.output_estimate)
        rows_loc = self.rows_padded // self.world
        r_static = self._bound_total(self._rows_since_evict)
        pre = plan_pre_merge_levels(est, self.cfg, r_static)
        if bucketed:
            out_cap = self.output_rows or _pow2_ceil(max(1, rows_loc))
            trim = min(_pow2_ceil(r_static), self._R)
        else:
            out_cap = max(1, self.output_rows or rows_loc)
            trim = min(r_static, self._R)  # merge the exact bound, not pow2
        return pre, out_cap, trim

    def _run_merge(self, es, pre: int, out_cap: int, trim: int,
                   exchange_quota: int | None = None):
        """Dispatch the (non-donating) drain + merge program on ``es``.
        ``exchange_quota`` overrides the mesh exchange's derived per-peer
        quota (the :meth:`_retry_exchange` path)."""
        with key_dtype_context(self.key_dtype):
            if self.mesh is None:
                return _finalize_stream(
                    es, self._retired, policy=self._arm,
                    page_rows=self.cfg.page_rows, index_rows=self.index_rows,
                    fanin=self.cfg.fanin, premerge_levels=pre,
                    backend=self.backend, out_capacity=out_cap, trim=trim,
                )
            if self._retired is None:
                return self._fns.finalize(
                    pre, out_cap, trim, False, exchange_quota)(es)
            return self._fns.finalize(
                pre, out_cap, trim, True, exchange_quota)(es, self._retired)

    def snapshot_device(self) -> tuple[AggState, DeviceSpillStats]:
        """Merge-on-read snapshot: answer the current aggregate WITHOUT
        consuming the engine.

        Runs the same statically planned drain + pre-merge + wide merge
        program as :meth:`finalize_device` — it is non-donating and emits
        into a fresh output buffer, so the live engine state is untouched
        (byte-for-byte) and ingest continues afterwards.  Zero host
        syncs; snapshot dispatch is ordered before any subsequent donated
        absorb by JAX's program-order execution, so overlapping ingest is
        safe.  Capacity statics are pow2-bucketed to bound compile count
        over a session's lifetime."""
        if self._finalized:
            raise RuntimeError("StreamingAggregator already finalized")
        if self._es is None:  # nothing absorbed (or created) yet
            with key_dtype_context(self.key_dtype):
                return (
                    empty_state(0, self.width, key_dtype=self.key_dtype,
                                widths=self.widths),
                    DeviceSpillStats.zeros(),
                )
        pre, out_cap, trim = self._merge_plan(bucketed=True)
        return self._run_merge(self._es, pre, out_cap, trim)

    def snapshot(self) -> tuple[AggState, SpillStats]:
        """:meth:`snapshot_device` + the host readback of spill stats
        (overflow errors name the snapshot entry point; a merge-output
        overflow is retried once at the next pow2 capacity — legal
        because the snapshot program never consumes the live state)."""
        state, dstats = self.snapshot_device()
        try:
            stats = dstats.finalize(entry_point="snapshot")
        except ExchangeOverflowError as e:
            pre, out_cap, trim = self._merge_plan(bucketed=True)
            state, stats = self._retry_exchange(
                "snapshot", e, self._es, pre, out_cap, trim)
        except MergeOverflowError as e:
            pre, out_cap, trim = self._merge_plan(bucketed=True)
            state, stats = self._retry_capacity(
                "snapshot", e, self._es, pre, out_cap, trim)
        return state, self._patch_stats(stats)

    def evict_below(self, threshold) -> int:
        """Retire every resident row whose key is ``< threshold`` from
        the live engine (TTL / watermark eviction for sessionization).

        Keys are retired from the run store AND the resident tables by
        sorted prefix cuts, surviving runs are compacted to the store
        prefix, and the host re-anchors its slot accounting at the new
        high-water mark — this is the ONE host sync of the service
        protocol (a single scalar readback at the eviction boundary).
        Retired rows are accumulated device-side and surface as
        ``SpillStats.rows_retired`` on every later snapshot/finalize:
        nothing is silently dropped.  Returns the cumulative retired-row
        count."""
        if self._finalized:
            raise RuntimeError("StreamingAggregator already finalized")
        thr = int(threshold)
        if not (0 <= thr <= int(max_key(self.key_dtype))):
            raise ValueError(
                f"eviction threshold {threshold!r} out of range for "
                f"{self.key_dtype} keys (must be <= max_key, below the "
                f"EMPTY sentinel)"
            )
        if self._es is None:  # nothing resident: nothing to retire
            return 0 if self._retired is None else int(
                np.sum(np.asarray(self._retired)))
        with key_dtype_context(self.key_dtype):
            if self.mesh is None:
                thr_dev = jax.device_put(np.asarray(thr, self.key_dtype))
                self._es, self._retired = _evict_compact(
                    self._es, thr_dev, self._retired, policy=self.policy,
                    backend=self.backend,
                )
                new_ridx = int(self._es.ridx)
            else:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                thr_dev = jax.device_put(
                    np.asarray(thr, self.key_dtype),
                    NamedSharding(self.mesh, P()),
                )
                args = (() if self._retired is None else (self._retired,))
                self._es, self._retired, ridx_max = self._fns.evict(
                    self._retired is not None
                )(self._es, thr_dev, *args)
                new_ridx = int(ridx_max)
        slack = {"traditional": 0, "inrun_dedup": 0,
                 "early_agg": 2, "rs": 4, "adaptive": 6}[self.policy]
        self._base_slots = new_ridx + slack
        self._rows_since_evict = 0
        return int(np.sum(np.asarray(self._retired)))


def _as_chunk(c):
    """Normalize one element of a chunk stream to ``(keys, payload)``."""
    if isinstance(c, (tuple, list)):
        if len(c) != 2:
            raise ValueError(
                "chunk must be a keys array or a (keys, payload) pair, got "
                f"a {type(c).__name__} of length {len(c)}"
            )
        return c[0], c[1]
    return c, None


def rebatch_chunks(chunks, rows: int):
    """Re-chunk an iterable of ``keys`` / ``(keys, payload)`` chunks into
    ``rows``-row super-batches (host NumPy — the chunked source adapter
    for arbitrary-granularity producers).  The final partial super-batch
    is yielded as-is."""
    if rows <= 0:
        raise ValueError(f"rows must be positive, got {rows}")
    kbuf: list[np.ndarray] = []
    pbuf: list = []
    have = 0
    for c in chunks:
        k, p = _as_chunk(c)
        k = np.asarray(k)
        if k.shape[0] == 0:
            continue
        kbuf.append(k)
        pbuf.append(None if p is None else np.asarray(p))
        have += k.shape[0]
        while have >= rows:
            keys = np.concatenate(kbuf) if len(kbuf) > 1 else kbuf[0]
            if any(p is None for p in pbuf):
                pay = None
            else:
                pb = [p[:, None] if p.ndim == 1 else p for p in pbuf]
                pay = np.concatenate(pb) if len(pb) > 1 else pb[0]
            yield keys[:rows], None if pay is None else pay[:rows]
            kbuf = [keys[rows:]] if keys.shape[0] > rows else []
            pbuf = [pay[rows:]] if (pay is not None and keys.shape[0] > rows) \
                else ([None] * len(kbuf))
            have -= rows
    if have:
        keys = np.concatenate(kbuf) if len(kbuf) > 1 else kbuf[0]
        if any(p is None for p in pbuf):
            pay = None
        else:
            pb = [p[:, None] if p.ndim == 1 else p for p in pbuf]
            pay = np.concatenate(pb) if len(pb) > 1 else pb[0]
        yield keys, pay


def aggregate_device_stream(
    chunks,
    cfg: ExecConfig | None = None,
    *,
    policy: str = "rs",
    backend: str = "auto",
    widths: tuple[int, int, int] | None = None,
    key_dtype=None,
    width: int | None = None,
    index_rows: int | None = None,
    output_estimate: int | None = None,
    output_rows: int | None = None,
    super_batch_rows: int | None = None,
    mesh=None,
    mesh_axis: str | None = None,
    governor=None,
) -> tuple[AggState, DeviceSpillStats]:
    """The streamed, double-buffered twin of :func:`aggregate_device`:
    aggregate an input that never needs to be device- (or even host-)
    resident at once.

    ``chunks`` is an iterable/generator of ``keys`` arrays or
    ``(keys, payload)`` pairs (host NumPy).  Each chunk is staged with an
    explicit ``jax.device_put`` *before* the previous chunk's absorb is
    dispatched, so the k+1 transfer overlaps the k compute (JAX async
    dispatch); the device carries one engine state (donated between
    steps) plus at most two staged chunks — the peak device footprint is
    bounded by the super-batch size, not N.  ``super_batch_rows``
    re-chunks the stream to that many rows per absorb (default: chunks
    are absorbed as produced).

    ``key_dtype`` / ``width`` pin the stream's schema; by default they
    are inferred from the first chunk.  ``output_rows`` bounds the merge
    output capacity (device bytes) when the unique-key count is known to
    be far below the input size; an under-estimate is flagged loudly via
    ``merge_dropped_rows`` — never a silent truncation.

    Returns ``(state, DeviceSpillStats)`` with zero host syncs performed;
    see :func:`insort_aggregate_device_stream` for the finalized-stats
    variant.  Exact parity: for any chunking whose chunk sizes are
    multiples of the engine's input batch (``memory_rows`` for the
    read-sort-write policies, ``batch_rows`` for early-agg/RS), the
    result state AND SpillStats are identical to the one-shot pipeline
    on the concatenated input — EMPTY-padded batches are no-ops in every
    policy.
    """
    cfg = cfg or ExecConfig()
    agg, stream = _stream_setup(
        chunks, cfg, policy=policy, backend=backend, widths=widths,
        key_dtype=key_dtype, width=width, index_rows=index_rows,
        output_estimate=output_estimate, output_rows=output_rows,
        super_batch_rows=super_batch_rows, mesh=mesh, mesh_axis=mesh_axis,
        governor=governor,
    )
    if agg is None:  # empty stream
        return stream
    staged = None
    for keys, payload in stream:
        nxt = agg.stage(keys, payload)  # H2D of k+1 in flight while …
        if staged is not None:
            agg.absorb_staged(staged)  # … the device absorbs chunk k
        staged = nxt
    agg.absorb_staged(staged)
    return agg.finalize_device()


def _stream_setup(
    chunks,
    cfg: ExecConfig,
    *,
    policy: str = "rs",
    backend: str = "auto",
    widths=None,
    key_dtype=None,
    width=None,
    index_rows=None,
    output_estimate=None,
    output_rows=None,
    super_batch_rows=None,
    mesh=None,
    mesh_axis=None,
    governor=None,
):
    """Shared stream-driver setup: peek the first non-empty chunk to fix
    the schema, build the aggregator.  Returns ``(agg, stream)``; for an
    empty stream ``agg`` is None and ``stream`` is the empty
    ``(state, DeviceSpillStats)`` result."""
    it = iter(chunks)
    first = None
    for c in it:
        k, p = _as_chunk(c)
        if np.asarray(k).shape[0]:
            first = (np.asarray(k), p)
            break
    if first is None:  # empty stream: mirror the one-shot empty early-out
        kd = np.dtype(key_dtype or np.uint32)
        w = int(width or 0)
        with key_dtype_context(kd):
            return None, (
                empty_state(0, w, key_dtype=kd, widths=widths),
                DeviceSpillStats.zeros(),
            )
    if key_dtype is None:
        key_dtype = rg._np_keys(first[0]).dtype
    if width is None:
        if first[1] is None:
            width = 0
        else:
            p0 = np.asarray(first[1])
            width = 1 if p0.ndim == 1 else p0.shape[1]
    stream = itertools.chain([first], (_as_chunk(c) for c in it))
    if super_batch_rows:
        stream = rebatch_chunks(stream, super_batch_rows)
    agg = StreamingAggregator(
        cfg, policy=policy, key_dtype=key_dtype, width=width, widths=widths,
        backend=backend, index_rows=index_rows,
        output_estimate=output_estimate, output_rows=output_rows,
        mesh=mesh, mesh_axis=mesh_axis, governor=governor,
    )
    return agg, stream


def insort_aggregate_device_stream(
    chunks, cfg: ExecConfig | None = None, **kw
) -> tuple[AggState, SpillStats]:
    """:func:`aggregate_device_stream` + the one host readback of spill
    stats — the streamed twin of :func:`insort_aggregate_device`.

    ``policy="adaptive"`` streams cannot use this one-dispatch form's
    device-only return (the governor needs its periodic readbacks
    anyway), so they are driven through the same loop but finalized with
    the retrying host path and observation-annotated stats."""
    if kw.get("policy") == "adaptive":
        cfg = cfg or ExecConfig()
        agg, stream = _stream_setup(chunks, cfg, **kw)
        if agg is None:  # empty stream
            state, dstats = stream
            return state, dstats.finalize()
        staged = None
        for keys, payload in stream:
            nxt = agg.stage(keys, payload)
            if staged is not None:
                agg.absorb_staged(staged)
            staged = nxt
        agg.absorb_staged(staged)
        return agg.finalize()
    state, dstats = aggregate_device_stream(chunks, cfg, **kw)
    return state, dstats.finalize()


# ---------------------------------------------------------------------------
# mesh-sharded streaming: the same engine under shard_map
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _mesh_stream_fns(
    mesh,
    axis: str,
    *,
    policy: str,
    memory_rows: int,
    batch_rows: int,
    page_rows: int,
    index_rows: int,
    fanin: int,
    backend: str,
    widths,
    width: int,
    key_dtype_name: str,
):
    """Jitted shard_map programs advancing a PER-SHARD engine state:
    ``init(R)()``, ``absorb(es, bk, bp)``, ``grow(R)(es)``, and
    ``finalize(premerge_levels, out_capacity)(es)`` (per-shard drain +
    merge, then the key-range exchange + per-owner merge of the sharded
    one-shot pipeline).  Scalar engine leaves are carried (1,)-shaped so
    every leaf has a shardable leading axis
    (:func:`~repro.core.types.expand_engine_scalars`)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed import groupby as gb_mod

    kd = np.dtype(key_dtype_name)
    world = mesh.shape[axis]
    vma = dispatch.checks_vma(backend)
    agg_spec = AggState(
        keys=P(axis), count=P(axis), sum=P(axis, None),
        min=P(axis, None), max=P(axis, None),
    )
    store_spec = AggState(
        keys=P(axis, None), count=P(axis, None), sum=P(axis, None, None),
        min=P(axis, None, None), max=P(axis, None, None),
    )
    state_spec = StreamEngineState(
        table=agg_spec, table2=agg_spec, frontier=P(axis), store=store_spec,
        lens=P(axis), cursor=P(axis), ridx=P(axis), spilled=P(axis),
        absorbed=P(axis), dups=P(axis),
    )
    n_stats = len(dataclasses.fields(DeviceSpillStats))

    @functools.lru_cache(maxsize=None)
    def init_fn(run_slots: int):
        def body():
            es = _engine_init(
                policy, M=memory_rows, B=batch_rows, P=page_rows,
                R=run_slots, width=width, key_dtype=kd, widths=widths,
            )
            return expand_engine_scalars(es)

        return jax.jit(jax.shard_map(
            body, mesh=mesh, check_vma=vma, in_specs=(), out_specs=state_spec,
        ))

    @functools.lru_cache(maxsize=None)
    def absorb_fn(local_slots: int):
        def body(es, bk, bp):
            es = _absorb_chunk_body(
                squeeze_engine_scalars(es), bk, bp, policy=policy,
                memory_rows=memory_rows, batch_rows=batch_rows,
                backend=backend, widths=widths, local_slots=local_slots,
            )
            return expand_engine_scalars(es)

        return jax.jit(
            jax.shard_map(
                body, mesh=mesh, check_vma=vma,
                in_specs=(state_spec, P(axis, None), P(axis, None, None)),
                out_specs=state_spec,
            ),
            donate_argnums=(0,),
        )

    @functools.lru_cache(maxsize=None)
    def grow_fn(run_slots: int):
        def body(es):
            es = squeeze_engine_scalars(es)
            store, lens = _pad_slots(es.store, es.lens, run_slots)
            return expand_engine_scalars(
                dataclasses.replace(es, store=store, lens=lens)
            )

        # no donation: shapes change across the grow
        return jax.jit(
            jax.shard_map(body, mesh=mesh, check_vma=vma,
                          in_specs=(state_spec,), out_specs=state_spec),
        )

    @functools.lru_cache(maxsize=None)
    def finalize_fn(premerge_levels: int, out_capacity: int, trim: int,
                    with_retired: bool = False,
                    exchange_quota: int | None = None):
        def body(es, *rest):
            es = _trim_slots(squeeze_engine_scalars(es), trim)
            fresh_out = empty_state(out_capacity, width, key_dtype=kd,
                                    widths=widths)
            store, lens, table, spilled, nruns, overflow = _engine_finish(
                es, policy=policy, backend=backend
            )
            # per-shard retired rows go into the stats BEFORE cross_shard
            # psums them into the global total
            retired = rest[0][0] if with_retired else None
            out, dstats = _merge_phase(
                store, lens, spilled, nruns, overflow, page_rows=page_rows,
                index_rows=index_rows, fanin=fanin,
                premerge_levels=premerge_levels, backend=backend,
                out_capacity=out_capacity, rows_retired=retired,
                out_buffer=fresh_out,
            )
            merged, ex = gb_mod.exchange_and_merge(
                out, axis, world, backend=backend, quota=exchange_quota,
                page_rows=page_rows,
            )
            dstats = dataclasses.replace(
                dstats,
                merge_dropped_rows=dstats.merge_dropped_rows | ex.merge_dropped,
                rows_exchanged=ex.rows_sent,
                exchange_dropped=ex.send_dropped,
                exchange_quota=jnp.int32(ex.quota),
                exchange_max_fill=ex.max_fill,
            )
            return merged, dstats.cross_shard(axis)

        in_specs = (state_spec,) + ((P(axis),) if with_retired else ())
        # no donation: outputs don't share the state leaves' shapes —
        # which is also what makes this program double as the per-shard
        # merge-on-read snapshot (the live state survives the call)
        return jax.jit(
            jax.shard_map(
                body, mesh=mesh, check_vma=vma, in_specs=in_specs,
                out_specs=(agg_spec, DeviceSpillStats(*(P(),) * n_stats)),
            ),
        )

    @functools.lru_cache(maxsize=None)
    def evict_fn(with_retired: bool):
        def body(es, thr, *rest):
            es, retired = _evict_compact_body(
                squeeze_engine_scalars(es), thr,
                rest[0][0] if with_retired else None,
                policy=policy, backend=backend,
            )
            ridx_max = jax.lax.pmax(es.ridx, axis)
            return expand_engine_scalars(es), retired[None], ridx_max

        in_specs = ((state_spec, P())
                    + ((P(axis),) if with_retired else ()))
        return jax.jit(
            jax.shard_map(
                body, mesh=mesh, check_vma=vma, in_specs=in_specs,
                out_specs=(state_spec, P(axis), P()),
            ),
            donate_argnums=(0,),
        )

    class _Fns:
        pass

    fns = _Fns()
    fns.init = init_fn
    fns.absorb = absorb_fn
    fns.grow = grow_fn
    fns.finalize = finalize_fn
    fns.evict = evict_fn
    return fns
