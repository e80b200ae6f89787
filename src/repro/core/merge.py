"""Merging for external in-sort aggregation: traditional F-way merge and
the paper's wide merge (§4).

Traditional merging is limited to fan-in F (one input buffer per run);
aggregation during a merge step caps its output at the operation's final
output size O.  Wide merging instead keeps an ordered in-memory index over
the *active key range* and streams pages from **any** number of runs
through a single shared input buffer, guided by a priority queue over each
run's next unread page's low key.  Keys below the merge frontier (the
minimum unread key across all runs) are final and stream out of the left
edge of the index (Fig 9/10).

Shapes are static: runs live in a stacked "temporary storage" buffer, the
page loop is a ``lax.while_loop``, and emission scatters into a fixed
output buffer — the JAX rendering of paged I/O.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sorted_ops
from repro.core.run_generation import Run
from repro.core.types import (
    AggState,
    ExecConfig,
    SpillStats,
    concat_states,
    empty_key,
    empty_like,
    empty_state,
    key_dtype_context,
    match_vma,
    slice_rows,
)


# ---------------------------------------------------------------------------
# stacked run storage ("temporary storage")
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunStore:
    """R runs padded to a common page-aligned capacity C."""

    state: AggState  # fields have leading dims (R, C)
    lens: jax.Array  # (R,) int32

    @property
    def num_runs(self) -> int:
        return self.state.keys.shape[0]

    @property
    def capacity(self) -> int:
        return self.state.keys.shape[1]


def stack_runs(runs: list[Run], page_rows: int, width: int) -> RunStore:
    cap = max(1, max(r.length for r in runs))
    cap = int(math.ceil(cap / page_rows) * page_rows)
    padded = []
    for r in runs:
        s = r.state
        if s.capacity < cap:
            s = concat_states(s, empty_like(s, cap - s.capacity))
        else:
            s = jax.tree.map(lambda x: x[:cap], s)
        padded.append(s)
    state = jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *padded)
    lens = jnp.asarray([r.length for r in runs], dtype=jnp.int32)
    return RunStore(state=state, lens=lens)


def fragments_to_store(recv: AggState, world: int, quota: int):
    """View ``world`` concatenated fixed-``quota`` sorted fragments (the
    cross-shard exchange's receive buffer, fields shaped
    ``(world * quota, ...)``) as the stacked run-store layout the wide
    merge consumes: fields reshaped to ``(R=world, C=quota)`` plus the
    per-fragment live lengths (fragments are left-packed, EMPTY-padded).
    ``quota`` must be a multiple of the merge page size the caller will
    use — :func:`_page_of`'s clamped ``dynamic_slice`` double-reads rows
    otherwise."""
    store = jax.tree.map(
        lambda x: x.reshape((world, quota) + x.shape[1:]), recv
    )
    lens = jnp.sum(
        store.keys != empty_key(store.keys.dtype), axis=1, dtype=jnp.int32
    )
    return store, lens


def _page_of(store_state: AggState, r, start, page_rows: int) -> AggState:
    """DMA one page (P rows) of run ``r`` into the shared input buffer."""

    r = jnp.asarray(r, jnp.int32)
    start = jnp.asarray(start, jnp.int32)

    def f(x):
        sizes = (1, page_rows) + x.shape[2:]
        # uniform index dtype: x64 mode would otherwise mix int64/int32
        starts = (r, start) + (jnp.int32(0),) * (x.ndim - 2)
        return jax.lax.dynamic_slice(x, starts, sizes)[0]

    return jax.tree.map(f, store_state)


# ---------------------------------------------------------------------------
# traditional F-way merge (with/without aggregation during the merge)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("aggregate", "backend"))
def _merge_group(states: tuple[AggState, ...], *, aggregate: bool, backend="xla"):
    """Merge a group of **already-sorted** runs with a balanced tree of
    linear merges — the runs carry the sorted invariant from run
    generation, so the former concat + full-argsort of the union was pure
    waste.  ``aggregate=True`` combines duplicates as it merges (the
    shared :func:`sorted_ops.merge_absorb_many` tree); ``aggregate=False``
    keeps the raw sorted multiset (a tree of interleaves) for merge plans
    that defer aggregation (Fig 2 top)."""
    states = list(states)
    if len(states) == 1 and aggregate:
        # a lone run may still carry intra-run duplicates (traditional
        # policy): combining a sorted state needs no merge at all
        out = sorted_ops.segmented_combine(states[0], backend=backend)
        return out, out.occupancy()
    if aggregate:
        out = sorted_ops.merge_absorb_many(states, backend=backend)
    else:
        out = sorted_ops.interleave_many(states, backend=backend)
    return out, out.occupancy()


def _pad_group(states: tuple[AggState, ...]) -> tuple[AggState, ...]:
    """Pad group members to their common max capacity before the jitted
    merge tree: heterogeneous run lengths (replacement selection) would
    otherwise key a fresh compilation on every distinct capacity tuple."""
    cap = max(s.capacity for s in states)
    return tuple(
        s if s.capacity == cap else concat_states(s, empty_like(s, cap - s.capacity))
        for s in states
    )


def traditional_merge(
    runs: list[Run],
    cfg: ExecConfig,
    *,
    aggregate_during_merge: bool,
    stats: SpillStats,
    backend: str = "xla",
    stop_at: int = 1,
) -> list[Run]:
    """Merge runs F at a time until ``stop_at`` or fewer remain.

    Every merge step's output is written back to temporary storage and
    counted as spill — except the final step when ``stop_at == 1`` (its
    output streams to the consumer, Fig 2).
    """
    F = cfg.fanin
    width = runs[0].state.width if runs else 0
    while len(runs) > stop_at:
        nxt: list[Run] = []
        level_groups = [runs[i : i + F] for i in range(0, len(runs), F)]
        for group in level_groups:
            if len(group) == 1:  # singleton: carried over, no re-write I/O
                nxt.append(group[0])
                continue
            merged, occ = _merge_group(
                _pad_group(tuple(g.state for g in group)),
                aggregate=aggregate_during_merge, backend=backend,
            )
            length = int(occ)
            nxt.append(Run(state=merged, length=length))
            stats.merge_steps += 1
            is_final = len(level_groups) == 1 and len(nxt) <= stop_at
            if not is_final:
                stats.rows_spilled_merge += length
        stats.merge_levels += 1
        runs = nxt
    return runs


def final_merge_traditional(
    runs: list[Run], cfg: ExecConfig, *, aggregate: bool, stats: SpillStats,
    backend: str = "xla",
) -> AggState:
    """Reduce to ≤F runs with traditional merging, then stream the final
    merge (never spilled) — optionally aggregating in-stream (Fig 2 top)."""
    runs = traditional_merge(
        runs, cfg, aggregate_during_merge=aggregate, stats=stats, backend=backend,
        stop_at=cfg.fanin,
    )
    # output phase: one last merge tree, aggregating in-stream
    out, _ = _merge_group(
        _pad_group(tuple(r.state for r in runs)), aggregate=True, backend=backend
    )
    stats.merge_steps += 1
    stats.merge_levels += 1
    return out


def trim_to_capacity(state: AggState, capacity: int):
    """Trim a compacted (sorted, EMPTY-padded) state to ``capacity`` rows,
    returning ``(trimmed, dropped)`` where ``dropped`` flags that the cut
    removed LIVE rows — data loss, never acceptable silently.  Traceable;
    the flag is a device scalar so callers inside ``jit``/``shard_map``
    reduce and surface it exactly like the wide merge's
    ``merge_dropped_rows`` (raise at the one host readback)."""
    dropped = state.occupancy() > capacity
    return jax.tree.map(lambda x: x[:capacity], state), dropped


# ---------------------------------------------------------------------------
# wide merge (§4)
# ---------------------------------------------------------------------------


def wide_merge_device(
    store_state: AggState,
    lens: jax.Array,
    *,
    page_rows: int,
    index_rows: int,
    out_capacity: int | None = None,
    backend: str = "xla",
    out: AggState | None = None,
):
    """Traceable core of the wide merge (§4): page loop as a
    ``lax.while_loop`` over a stacked run store.  Jit-wrapped by
    :func:`wide_merge` for standalone use and inlined into the fused
    device-resident pipeline (:mod:`repro.core.pipeline`) so run
    generation + merge compile to ONE program.  Returns device scalars
    ``(out, rows_emitted, pages_read, max_index_occupancy, overflow,
    dropped)`` — no host syncs; ``dropped`` is the hard failure signal
    (live rows trimmed), ``overflow`` the soft model-exceeded flag.

    ``out`` lets the caller provide the output buffer (an all-invalid
    :class:`AggState` matching the store's key dtype and plane widths) —
    the merge-on-read snapshot path emits into a *fresh* caller buffer
    so the program never aliases live engine state.  When absent, a
    fresh buffer of ``out_capacity`` rows is allocated here."""
    R, C = store_state.keys.shape
    P = page_rows
    W = index_rows + P  # index tile + headroom for one incoming page
    kd = store_state.keys.dtype
    width = store_state.sum.shape[-1]
    widths = (
        store_state.sum.shape[-1],
        store_state.min.shape[-1],
        store_state.max.shape[-1],
    )
    n_pages = (lens + P - 1) // P
    arange_R = jnp.arange(R)

    def next_low_keys(cursors):
        # priority queue over each run's next unread page's low key
        pos = jnp.clip(cursors * P, 0, C - 1)
        k = store_state.keys[arange_R, pos]
        return jnp.where(cursors < n_pages, k, empty_key(kd))

    if out is None:
        if out_capacity is None:
            raise ValueError("wide_merge_device needs out= or out_capacity=")
        out0 = empty_state(out_capacity, width, key_dtype=kd, widths=widths)
    else:
        if out.key_dtype != np.dtype(kd) or out.widths != widths:
            raise ValueError(
                f"caller-provided out buffer (dtype {out.key_dtype}, widths "
                f"{out.widths}) does not match the run store (dtype "
                f"{np.dtype(kd)}, widths {widths})"
            )
        out0 = out
        out_capacity = out.capacity

    def cond(carry):
        cursors, *_ = carry
        return jnp.any(cursors < n_pages)

    def body(carry):
        cursors, index, out, out_cur, pages_read, max_occ, overflow = carry
        low = next_low_keys(cursors)
        rstar = jnp.argmin(low)  # EMPTY == uint32 max ⇒ exhausted runs lose
        start = cursors[rstar] * P
        page = _page_of(store_state, rstar, start, P)
        # absorb the page into the ordered index (batched insert, §3.4):
        # both sides are sorted, so this is a linear merge — O(W+P) per
        # page instead of the former O((W+P)·log(W+P)) re-sort.  Pages
        # may carry intra-run duplicates (replacement-selection runs), so
        # the general combine path is used, not the pair-combine.
        merged = sorted_ops.merge_absorb(index, page, backend=backend)  # cap W + P
        cursors = cursors.at[rstar].add(1)
        # merge frontier: the least key any run can still deliver
        frontier = jnp.min(next_low_keys(cursors))
        keys = merged.keys
        # int32 throughout: x64 mode would silently promote sums to int64
        # and break the while_loop carry signature
        occ = merged.occupancy().astype(jnp.int32)
        final_mask = keys < frontier  # EMPTY never < frontier unless frontier==EMPTY
        e = jnp.sum(final_mask.astype(jnp.int32)).astype(jnp.int32)
        # emit the final prefix out of the left edge of the index
        idx = jnp.where(jnp.arange(W + P) < e, out_cur + jnp.arange(W + P), out_capacity)

        def scatter(dst, src):
            return dst.at[idx].set(src, mode="drop")

        out = jax.tree.map(scatter, out, merged)
        out_cur = out_cur + e
        # shift the index left by e (drop emitted rows), trim back to W
        src = jnp.minimum(jnp.arange(W) + e, W + P - 1)
        shifted = jax.tree.map(lambda x: jnp.take(x, src, axis=0), merged)
        live = jnp.arange(W) < (occ - e)
        new_keys = jnp.where(live, shifted.keys, empty_key(kd))
        index = AggState(new_keys, shifted.count, shifted.sum, shifted.min, shifted.max)
        resident = occ - e
        max_occ = jnp.maximum(max_occ, resident)
        overflow = overflow | (resident > index_rows)
        return (cursors, index, out, out_cur, pages_read + 1, max_occ, overflow)

    carry = match_vma((
        jnp.zeros((R,), jnp.int32),
        empty_state(W, width, key_dtype=kd, widths=widths),
        out0,
        jnp.int32(0),
        jnp.int32(0),
        jnp.int32(0),
        jnp.bool_(False),
    ), (store_state, lens))
    cursors, index, out, out_cur, pages_read, max_occ, overflow = jax.lax.while_loop(
        cond, body, carry
    )
    # resident > W means the left-shift trim cut live rows, and out_cur
    # past out_capacity means emitted rows fell off the scatter's "drop"
    # edge: either way that is data loss, not just "more memory than the
    # model allows" (the soft `overflow` flag at resident > index_rows).
    # Callers must fail loudly.
    dropped = (max_occ > W) | (out_cur > out_capacity)
    return out, out_cur, pages_read, max_occ, overflow, dropped


_wide_merge_jit = functools.partial(
    jax.jit, static_argnames=("page_rows", "index_rows", "out_capacity", "backend")
)(wide_merge_device)


def wide_merge(
    runs: list[Run],
    cfg: ExecConfig,
    *,
    stats: SpillStats,
    out_capacity: int | None = None,
    index_rows: int | None = None,
    backend: str = "xla",
) -> AggState:
    """Final merge step with unbounded fan-in (§4). Never spills.

    ``index_rows`` defaults to the memory allocation M; the paper shows the
    wide merge often needs well under M (Example 4: ~40%).
    """
    width = runs[0].state.width
    with key_dtype_context(runs[0].state):
        store = stack_runs(runs, cfg.page_rows, width)
        if out_capacity is None:
            out_capacity = int(sum(r.length for r in runs))
        out, out_cur, pages_read, max_occ, overflow, dropped = _wide_merge_jit(
            store.state,
            store.lens,
            page_rows=cfg.page_rows,
            index_rows=index_rows or cfg.memory_rows,
            out_capacity=out_capacity,
            backend=backend,
        )
    if bool(dropped):
        # name the actual condition: the two drop sites have different fixes
        w_cap = (index_rows or cfg.memory_rows) + cfg.page_rows
        if int(max_occ) > w_cap:
            cause = (f"the merge index overflowed (resident {int(max_occ)} "
                     f"> index_rows + page_rows = {w_cap})")
        else:
            cause = (f"the output overran its capacity (emitted "
                     f"{int(out_cur)} > {out_capacity})")
        raise RuntimeError(
            f"wide merge during finalize dropped rows: {cause}; merge "
            "fewer runs at once (pre-merge levels) or raise index_rows / "
            "the output estimate"
        )
    stats.merge_steps += 1
    stats.merge_levels += 1
    stats.pages_read += int(pages_read)
    stats.max_index_occupancy = max(stats.max_index_occupancy, int(max_occ))
    stats.index_overflowed = bool(overflow) or stats.index_overflowed
    emitted = int(out_cur)
    stats.rows_emitted += emitted
    # Accounting invariants: the merge emits every distinct key exactly
    # once, and never more rows than the runs held.
    total_in = int(sum(r.length for r in runs))
    assert emitted <= total_in, (emitted, total_in)
    if out_capacity >= total_in:  # nothing could have been dropped
        assert emitted == int(out.occupancy()), (emitted, int(out.occupancy()))
    return out
