"""Vectorized ordered-index primitives (the TPU adaptation of the paper's
in-memory b-tree).

The paper replaces priority queues with an ordered in-memory index whose
batched usage pattern it spells out in §3.4: *sort the incoming batch, then
turn the per-row search into a merge*.  On a vector machine that whole
recipe collapses into four primitives over fixed-capacity tiles:

* ``sort_state``          — key-sort a tile (EMPTY keys sink to the end);
* ``segmented_combine``   — absorb equal keys by combining aggregate states
                            (the b-tree "absorb" of §3);
* ``absorb``              — sort + combine: canonicalize *unsorted* rows;
* ``merge_absorb``        — batched insert of one **sorted** state into
                            another: a linear merge (searchsorted-rank
                            scatter on XLA, the merge-path kernel on
                            Pallas) — never a full sort of the union.

``merge_absorb`` requires both inputs key-sorted (duplicates within either
input are fine; they combine in the same pass).  Full argsort remains only
in ``sort_state``/``absorb`` for genuinely unsorted input.

This module is the thin user-facing layer: the engine lives in
:mod:`repro.core.ordered_index` (XLA) and :mod:`repro.kernels` (Pallas),
selected per call through the registry in :mod:`repro.core.dispatch`
(``backend="xla" | "pallas" | "auto"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import dispatch
from repro.core.ordered_index import OrderedIndex  # noqa: F401  (re-export)
from repro.core.types import (
    AggState,
    key_dtype_context,
    rows_to_state,
    take,
)


# ---------------------------------------------------------------------------
# sorting
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames="backend", inline=True)
def _sort_state(state: AggState, *, backend: str) -> AggState:
    return take(state, dispatch.get_backend(backend).argsort(state.keys))


def sort_state(state: AggState, *, backend: str = "auto") -> AggState:
    """Key-sort all rows of a state; EMPTY (=key dtype max) rows sink to
    the end."""
    with key_dtype_context(state):
        return _sort_state(state, backend=backend)


# ---------------------------------------------------------------------------
# segmented combine (absorb duplicates)
# ---------------------------------------------------------------------------


def segmented_combine(state: AggState, *, backend: str = "auto") -> AggState:
    """Combine adjacent equal-key rows of a key-sorted state.

    Output keeps the input capacity: unique groups are compacted to the
    front (still sorted), the tail is EMPTY.  This is the vectorized
    equivalent of inserting a sorted batch into the paper's b-tree and
    letting existing keys absorb the new rows.
    """
    with key_dtype_context(state):
        return _segmented_combine(state, backend=backend)


@functools.partial(jax.jit, static_argnames="backend", inline=True)
def _segmented_combine(state: AggState, *, backend: str) -> AggState:
    return dispatch.get_backend(backend).segmented_combine(state)


def absorb(state: AggState, *, backend: str = "auto") -> AggState:
    """sort + combine: canonicalize any state to sorted/compacted form."""
    return segmented_combine(sort_state(state, backend=backend), backend=backend)


def merge_absorb(
    table: AggState,
    incoming: AggState,
    *,
    backend: str = "auto",
    assume_unique: bool = False,
) -> AggState:
    """Batched insert of ``incoming`` into the ordered index ``table``.

    Both inputs must be **key-sorted** (EMPTY-padded; duplicates within
    either input are combined too).  Returns a state of capacity
    ``len(table) + len(incoming)`` — sorted, duplicate-free, EMPTY-padded
    — via a linear merge: no full argsort on any backend.  The caller
    decides whether the result still fits "memory" (paper: whether the
    b-tree must spill).

    ``assume_unique=True`` promises both inputs are also duplicate-free
    (the OrderedIndex invariant): merged groups then hold at most two
    rows and the absorb drops to a single pair-combine.
    """
    with key_dtype_context(table):
        return _merge_absorb(table, incoming, backend=backend,
                             assume_unique=assume_unique)


@functools.partial(jax.jit, static_argnames=("backend", "assume_unique"),
                   inline=True)
def _merge_absorb(table: AggState, incoming: AggState, *, backend: str,
                  assume_unique: bool) -> AggState:
    return dispatch.get_backend(backend).merge_sorted(
        table, incoming, assume_unique=assume_unique
    )


def interleave(a: AggState, b: AggState, *, backend: str = "auto") -> AggState:
    """Linear merge of two **key-sorted** states WITHOUT combining
    duplicates: the raw sorted multiset union, capacity ``|a| + |b|``,
    EMPTY rows ranked to the tail.  Traditional merge levels that defer
    aggregation (the paper's Fig 2 top baseline) are trees of exactly
    this operation.  Backends without a fused kernel fall back to the
    XLA rank-gather interleave."""
    with key_dtype_context(a):
        return _interleave(a, b, backend=backend)


@functools.partial(jax.jit, static_argnames="backend", inline=True)
def _interleave(a: AggState, b: AggState, *, backend: str) -> AggState:
    from repro.core import ordered_index as oi

    fn = dispatch.get_backend(backend).interleave or oi.interleave_sorted
    return fn(a, b)


def _merge_tree(states: list[AggState], pair_fn) -> AggState:
    """Balanced binary tree reduction over ≥1 states with ``pair_fn``
    (odd element carried to the next round)."""
    assert states, "merge tree needs at least one state"
    states = list(states)
    while len(states) > 1:
        nxt = [
            pair_fn(states[i], states[i + 1])
            for i in range(0, len(states) - 1, 2)
        ]
        if len(states) % 2:
            nxt.append(states[-1])
        states = nxt
    return states[0]


def merge_absorb_many(
    states: list[AggState], *, backend: str = "auto", assume_unique: bool = False
) -> AggState:
    """Balanced tree of linear merges over already-sorted states (the
    multi-fragment absorb used by the distributed group-by, the hash
    splice, and the traditional merge's aggregating groups).  Capacity of
    the result is the summed input capacity."""
    return _merge_tree(
        list(states),
        lambda a, b: merge_absorb(a, b, backend=backend, assume_unique=assume_unique),
    )


def interleave_many(states: list[AggState], *, backend: str = "auto") -> AggState:
    """Balanced tree of non-combining linear merges: the raw sorted
    multiset union of already-sorted states (traditional merge levels
    that defer aggregation).  Capacity is the summed input capacity."""
    return _merge_tree(
        list(states), lambda a, b: interleave(a, b, backend=backend)
    )


# ---------------------------------------------------------------------------
# fused in-memory fast path (what the LM framework calls)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("backend", "widths"))
def _sorted_groupby_jit(keys, payload, *, backend: str, widths):
    return absorb(rows_to_state(keys, payload, widths=widths), backend=backend)


def sorted_groupby(
    keys: jax.Array,
    payload: jax.Array | None = None,
    *,
    backend: str = "auto",
    widths: tuple[int, int, int] | None = None,
) -> AggState:
    """One-shot device group-by: the `O ≤ M` case of the paper (Fig 6).

    Sorted output comes for free — the "interesting orderings" property the
    paper leans on for group-by + order-by fusion.  ``widths`` restricts
    which value planes the result carries (see
    :class:`repro.core.schema.AggSpec`).
    """
    with key_dtype_context(keys):
        return _sorted_groupby_jit(keys, payload, backend=backend, widths=widths)


def unique_count(state: AggState) -> jax.Array:
    return state.occupancy()


def finalize(state: AggState, aggs: tuple[str, ...] = ("count", "sum", "min", "max", "avg")):
    """Turn accumulator state into user-facing aggregate columns."""
    out = {"key": state.keys}
    valid = state.valid()
    for a in aggs:
        if a == "count":
            out["count"] = state.count
        elif a == "sum":
            out["sum"] = state.sum
        elif a == "min":
            out["min"] = jnp.where(valid[:, None], state.min, 0.0)
        elif a == "max":
            out["max"] = jnp.where(valid[:, None], state.max, 0.0)
        elif a == "avg":
            c = jnp.maximum(state.count, 1).astype(jnp.float32)[:, None]
            out["avg"] = state.sum / c
        else:
            raise ValueError(f"unknown aggregate {a!r}")
    return out
