"""Run generation for external in-sort aggregation (paper §3).

Read-sort-write cycles (the paper's production choice, §5) with three
spill policies that reproduce the paper's comparison space:

* ``traditional``  — fill memory with raw rows, sort, write a run of
  exactly M rows (Fig 2 top: no data reduction before the final merge).
* ``inrun_dedup``  — fill memory with raw rows, sort, aggregate duplicates
  *within the run* before writing (Bitton/DeWitt [3], Fig 2 bottom).
* ``early_agg``    — the paper's §3: every input batch is sorted, deduped,
  and absorbed into the ordered in-memory index; memory holds only
  *unique* keys, so a run is written only once M distinct keys
  accumulated.  If the output fits memory, nothing spills (Fig 6).

The drivers here are host-orchestrated (like the paper's I/O loop) around
jitted fixed-shape steps, blocking on an occupancy readback after every
batch: they are the **reference path** — exact, per-batch spill
accounting in the paper's unit (rows), used by the cost-model study and
as the oracle-parity baseline.  The production path is
:mod:`repro.core.pipeline`, which runs the same policies as a single
jitted ``lax.scan`` with device-resident run buffers and O(1) host syncs
per input; the step primitives (:func:`rs_split_absorb`,
:func:`rs_evict_step`) are shared so both paths execute the same
per-batch state machine.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sorted_ops
from repro.core.types import (
    EMPTY,
    AggState,
    ExecConfig,
    SpillStats,
    as_key_array,
    concat_states,
    empty_key,
    empty_like,
    empty_state,
    key_dtype_context,
    rows_to_state,
)


@dataclasses.dataclass
class Run:
    """One sorted, EMPTY-padded run on "temporary storage" (HBM/host)."""

    state: AggState
    length: int  # occupied rows


@functools.partial(jax.jit, static_argnames=("backend",))
def _absorb_batch(table: AggState, batch_keys, batch_payload, *, backend="xla"):
    """One read-sort-write step: sort/dedupe the batch (paper §5), merge it
    into the ordered index, and report the new occupancy."""
    batch = sorted_ops.absorb(
        rows_to_state(batch_keys, batch_payload, widths=table.widths),
        backend=backend,
    )
    # table and batch are both duplicate-free ordered indexes: the insert
    # is a linear merge + pair-combine, never a sort.
    merged = sorted_ops.merge_absorb(table, batch, backend=backend, assume_unique=True)
    return merged, merged.occupancy()


@functools.partial(jax.jit, static_argnames=("capacity", "dedup", "backend", "widths"))
def _sort_chunk(keys, payload, capacity: int, *, dedup: bool, backend="xla",
                widths=None):
    """Sort (and optionally dedup) one chunk, padded to the fixed run
    capacity.  Chunks are produced at ≤ capacity rows, so only padding is
    ever needed; trimming would silently drop rows."""
    state = rows_to_state(keys, payload, widths=widths)
    assert state.capacity <= capacity, (
        f"chunk of {state.capacity} rows exceeds run capacity {capacity}"
    )
    if dedup:
        state = sorted_ops.absorb(state, backend=backend)
    else:
        state = sorted_ops.sort_state(state, backend=backend)
    pad = capacity - state.capacity
    if pad > 0:
        state = concat_states(state, empty_like(state, pad))
    return state, state.occupancy()


def _np_chunks(keys: np.ndarray, payload: np.ndarray | None, size: int):
    n = keys.shape[0]
    for s in range(0, n, size):
        e = min(n, s + size)
        k = keys[s:e]
        p = None if payload is None else payload[s:e]
        if k.shape[0] < size:  # fixed shapes: pad the final batch with EMPTY
            padn = size - k.shape[0]
            k = np.concatenate([k, np.full((padn,), empty_key(k.dtype), dtype=k.dtype)])
            if p is not None:
                p = np.concatenate([p, np.zeros((padn,) + p.shape[1:], p.dtype)])
        yield k, p


def _np_keys(keys: np.ndarray) -> np.ndarray:
    """Host-side key canonicalization: uint64 is preserved, everything
    else becomes the legacy uint32."""
    keys = np.asarray(keys)
    if keys.dtype != np.uint64:
        keys = keys.astype(np.uint32)
    return keys


def generate_runs(
    keys: np.ndarray,
    payload: np.ndarray | None,
    cfg: ExecConfig,
    *,
    policy: str = "early_agg",
    backend: str = "xla",
    widths: tuple[int, int, int] | None = None,
) -> tuple[list[Run], AggState | None, SpillStats]:
    """Consume an unsorted input stream; return (runs, resident_table, stats).

    ``resident_table`` is non-None only for ``early_agg`` — the in-memory
    index content at end-of-input.  If no runs were written the operation
    completed entirely in memory (paper Fig 6) and the table *is* the
    result.
    """
    keys = _np_keys(keys)
    if payload is not None:
        payload = np.asarray(payload, dtype=np.float32)
        if payload.ndim == 1:
            payload = payload[:, None]
    width = 0 if payload is None else payload.shape[1]
    M, B = cfg.memory_rows, cfg.batch_rows
    stats = SpillStats()
    runs: list[Run] = []

    with key_dtype_context(keys):
        if policy in ("traditional", "inrun_dedup"):
            # memory buffers M raw rows; sort(+dedup) on write.
            for ck, cp in _np_chunks(keys, payload, M):
                state, occ = _sort_chunk(
                    as_key_array(ck), None if cp is None else jnp.asarray(cp),
                    M, dedup=(policy == "inrun_dedup"), backend=backend,
                    widths=widths,
                )
                length = int(occ)
                runs.append(Run(state=state, length=length))
                stats.rows_spilled_run_generation += length
                stats.runs_generated += 1
            return runs, None, stats

        if policy != "early_agg":
            raise ValueError(f"unknown run-generation policy {policy!r}")

        # --- early aggregation: ordered in-memory index absorbs duplicates ---
        table = empty_state(M, width, key_dtype=keys.dtype, widths=widths)
        for ck, cp in _np_chunks(keys, payload, B):
            merged, occ = _absorb_batch(
                table, as_key_array(ck), None if cp is None else jnp.asarray(cp),
                backend=backend,
            )  # capacity M + B
            n = int(occ)
            if n > M:
                # memory full: write the entire index content as one sorted run
                # (read-sort-write cycle; runs ≈ M *unique* rows, paper §5).
                runs.append(Run(state=merged, length=n))
                stats.rows_spilled_run_generation += n
                stats.runs_generated += 1
                table = empty_state(M, width, key_dtype=keys.dtype, widths=widths)
            else:
                table = jax.tree.map(lambda x: x[: M], merged)  # trim back to M

        if not runs:
            return [], table, stats
        # flush the final partial run
        occ = int(table.occupancy())
        if occ > 0:
            pad = empty_like(table, B)
            runs.append(Run(state=concat_states(table, pad), length=occ))
            stats.rows_spilled_run_generation += occ
            stats.runs_generated += 1
        return runs, None, stats


# ---------------------------------------------------------------------------
# replacement selection with an ordered index (§3.3)
# ---------------------------------------------------------------------------
#
# "Run generation using an in-memory index can produce runs twice the size
#  of memory without an additional comparison and without a flag in each
#  row in memory.  Eviction … repeatedly scans the in-memory index; …
#  the current key value of the eviction scan governs assignment of new
#  input rows to partitions and runs."
#
# Two tables model the partitioned b-tree: `run_table` holds keys ≥ the
# eviction frontier (they may still join the open run), `next_table` holds
# keys below it (they must wait for the next run).  Absorption therefore
# continues at rate ~M/O for the whole input — matching hybrid hashing in
# the O ∈ (M, 2M] band (paper §4.4, Example 5), where read-sort-write
# cycles give up their resident table on every flush.


def _mask_state(state: AggState, keep) -> AggState:
    return AggState(
        keys=jnp.where(keep, state.keys, empty_key(state.keys.dtype)),
        count=jnp.where(keep, state.count, 0),
        sum=jnp.where(keep[:, None], state.sum, 0.0),
        min=jnp.where(keep[:, None], state.min, jnp.float32(jnp.inf)),
        max=jnp.where(keep[:, None], state.max, jnp.float32(-jnp.inf)),
    )


def rs_split_absorb(run_table, next_table, frontier, batch, *, backend="xla"):
    """Partition one **sorted, deduped** batch at the eviction frontier and
    absorb each half into its table (traceable; shared by the host
    reference loop and the device-resident scan body)."""
    valid = batch.keys != empty_key(batch.keys.dtype)
    # the sorted batch splits at the frontier into a `lo` prefix and a
    # `hi` suffix; masking keeps `lo` sorted as-is, while `hi` must be
    # rolled left past the masked prefix to restore the sorted/EMPTY-
    # padded invariant merge_absorb requires.
    n_lo = jnp.sum(valid & (batch.keys < frontier), dtype=jnp.int32)
    hi = _mask_state(batch, valid & (batch.keys >= frontier))
    hi = jax.tree.map(lambda x: jnp.roll(x, -n_lo, axis=0), hi)
    lo = _mask_state(batch, valid & (batch.keys < frontier))
    cap_r, cap_n = run_table.capacity, next_table.capacity
    run_table = jax.tree.map(
        lambda x: x[:cap_r],
        sorted_ops.merge_absorb(run_table, hi, backend=backend, assume_unique=True),
    )
    next_table = jax.tree.map(
        lambda x: x[:cap_n],
        sorted_ops.merge_absorb(next_table, lo, backend=backend, assume_unique=True),
    )
    return run_table, next_table


@functools.partial(jax.jit, static_argnames=("backend",))
def _rs_absorb(run_table, next_table, frontier, bkeys, bpay, *, backend="xla"):
    batch = sorted_ops.absorb(
        rows_to_state(bkeys, bpay, widths=run_table.widths), backend=backend
    )
    run_table, next_table = rs_split_absorb(
        run_table, next_table, frontier, batch, backend=backend
    )
    return run_table, next_table, run_table.occupancy(), next_table.occupancy()


def rs_evict_step(run_table, quantum: int):
    """Advance the eviction scan: pop the lowest ``quantum`` rows
    (traceable; shared by the host loop and the device scan)."""
    cap = run_table.capacity
    evicted = jax.tree.map(lambda x: x[:quantum], run_table)
    src = jnp.minimum(jnp.arange(cap) + quantum, cap - 1)
    rest = jax.tree.map(lambda x: jnp.take(x, src, axis=0), run_table)
    live = jnp.arange(cap) < jnp.maximum(run_table.occupancy() - quantum, 0)
    rest = _mask_state(rest, live)
    kd = evicted.keys.dtype
    valid = evicted.keys != empty_key(kd)
    frontier = jnp.max(jnp.where(valid, evicted.keys, jnp.zeros((), kd)))
    # dtype pinned: x64 mode would promote the sum to int64 and break
    # scan/while carries built around int32 cursors
    n_evicted = jnp.sum(valid, dtype=jnp.int32)
    return evicted, rest, frontier, n_evicted


@functools.partial(jax.jit, static_argnames=("quantum", "backend"))
def _rs_evict(run_table, quantum: int, *, backend="xla"):
    del backend  # pure jnp; kept for call-site symmetry
    return rs_evict_step(run_table, quantum)


def generate_runs_rs(
    keys: np.ndarray,
    payload: np.ndarray | None,
    cfg: ExecConfig,
    *,
    backend: str = "xla",
    widths: tuple[int, int, int] | None = None,
) -> tuple[list[Run], AggState | None, SpillStats]:
    """Replacement-selection run generation with early aggregation (§3.3).

    Returns (runs, resident_table_if_no_spill, stats).  Runs approach 2M
    rows for random input; absorption continues at ~M/O throughout.
    """
    keys = _np_keys(keys)
    if payload is not None:
        payload = np.asarray(payload, dtype=np.float32)
        if payload.ndim == 1:
            payload = payload[:, None]
    width = 0 if payload is None else payload.shape[1]
    M, B = cfg.memory_rows, cfg.batch_rows
    cap = M + 2 * B
    stats = SpillStats()
    runs: list[Run] = []
    with key_dtype_context(keys):
        return _generate_runs_rs_body(
            keys, payload, cfg, backend=backend, widths=widths,
            width=width, cap=cap, stats=stats, runs=runs,
        )


def _generate_runs_rs_body(keys, payload, cfg, *, backend, widths, width, cap,
                           stats, runs):
    M, B = cfg.memory_rows, cfg.batch_rows
    run_table = empty_state(cap, width, key_dtype=keys.dtype, widths=widths)
    next_table = empty_state(cap, width, key_dtype=keys.dtype, widths=widths)
    frontier = jnp.zeros((), keys.dtype)
    # evicted pieces of the open run, kept on the host: their lengths are
    # data-dependent, and device slices would compile once per length
    open_chunks: list[AggState] = []
    open_len = 0

    def close_run():
        nonlocal open_chunks, open_len
        if open_len == 0:
            return
        state = jax.tree.map(lambda *xs: jnp.asarray(np.concatenate(xs, 0)),
                             *open_chunks)
        runs.append(Run(state=state, length=open_len))
        stats.runs_generated += 1
        open_chunks, open_len = [], 0

    for ck, cp in _np_chunks(keys, payload, B):
        run_table, next_table, occ_r, occ_n = _rs_absorb(
            run_table, next_table, frontier, as_key_array(ck),
            None if cp is None else jnp.asarray(cp), backend=backend,
        )
        occ_r, occ_n = int(occ_r), int(occ_n)
        while occ_r + occ_n > M:
            if occ_r == 0:
                # open run exhausted: close it, promote the next partition
                close_run()
                run_table, next_table = next_table, empty_like(next_table, cap)
                frontier = jnp.zeros((), keys.dtype)
                occ_r, occ_n = occ_n, 0
                continue
            evicted, run_table, frontier, n_ev = _rs_evict(run_table, B, backend=backend)
            n_ev = int(n_ev)
            trimmed = jax.tree.map(lambda x: np.asarray(x)[:n_ev], evicted)
            open_chunks.append(trimmed)
            open_len += n_ev
            stats.rows_spilled_run_generation += n_ev
            occ_r -= n_ev

    if not runs and open_len == 0:
        # everything absorbed in memory (run_table ∪ next_table, but with
        # no eviction ever, next_table is empty and frontier 0)
        return [], run_table, stats
    # drain: finish the open run with run_table's remainder, then the rest.
    # Both tables satisfy the OrderedIndex invariant throughout (merge,
    # trim, and evict-shift all preserve it), so no re-sort is needed.
    occ_r = int(run_table.occupancy())
    if occ_r > 0:
        open_chunks.append(jax.tree.map(lambda x: np.asarray(x)[:occ_r],
                                        run_table))
        open_len += occ_r
        stats.rows_spilled_run_generation += occ_r
    close_run()
    occ_n = int(next_table.occupancy())
    if occ_n > 0:
        runs.append(Run(
            state=jax.tree.map(lambda x: x[: occ_n + B], next_table),
            length=occ_n,
        ))
        stats.rows_spilled_run_generation += occ_n
        stats.runs_generated += 1
    return runs, None, stats
