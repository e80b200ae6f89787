"""Schema front door: composite keys, declarative aggregates, and the
single ``aggregate()`` entry point.

The paper's thesis is that one sort-based algorithm can serve as a
system's *only* aggregation operator.  This module is the API rendering
of that thesis: instead of per-algorithm functions over a hard-wired
``uint32`` key and a fixed count/sum/min/max accumulator, callers
describe

* **what the key is** — :class:`KeySpec`, an ordered list of named
  integer key columns with bit widths, packed most-significant-first
  into ONE machine sort key (``uint32`` when ≤ 32 total bits, else
  ``uint64``).  Packing most-significant-first makes the single sort
  realize the lexicographic ordering of the column list, which is what
  lets the engine exploit *interesting orderings* generically: any
  prefix of the column list is sorted for free (Guravannavar et al.'s
  order-enforcement payoff), and rollup over any hierarchy needs one
  sort (§2.2).
* **what to compute** — :class:`AggSpec`, the requested aggregates
  (count, sum, min, max, plus finalizers like avg).  The engine's
  :class:`~repro.core.types.AggState` then carries only the value
  planes the request needs: ``AggSpec("count")`` drops all three float
  planes from every kernel and every spilled run.

``aggregate()`` routes through the backend registry
(:mod:`repro.core.dispatch`) and the analytic cost model
(:mod:`repro.core.cost_model`), and returns an :class:`AggResult` whose
relation is sorted by the composite key — `group_by`, `distinct`,
`rollup`, … in :mod:`repro.core.operators` are thin wrappers.

64-bit keys on the host are plain NumPy ``uint64``; device computation
runs inside :func:`repro.core.types.key_dtype_context`, and the Pallas
kernels compare them as a (hi, lo) pair of uint32 lanes — no native
64-bit ops on the TPU path.
"""
from __future__ import annotations

import dataclasses
import itertools
import logging
from collections.abc import Iterator
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import cost_model
from repro.core import dispatch
from repro.core import hash_agg as hash_mod
from repro.core import insort as insort_mod
from repro.core import merge_join as mj_mod
from repro.core import sorted_ops
from repro.core.trace import span
from repro.core.types import (
    AggState,
    ExecConfig,
    SpillStats,
    concat_states,
    empty_key,
    empty_like,
    key_dtype_context,
    key_dtype_for_bits,
    max_key,
)


# ---------------------------------------------------------------------------
# KeySpec — composite sort keys
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KeyColumn:
    """One named integer key column occupying ``bits`` bits of the packed
    key.  Values must lie in ``[0, 2**bits)``."""

    name: str
    bits: int

    def __post_init__(self):
        if not self.name:
            raise ValueError("key column needs a name")
        if not 1 <= self.bits <= 64:
            raise ValueError(f"column {self.name!r}: bits must be in [1, 64]")

    @property
    def max_value(self) -> int:
        return (1 << self.bits) - 1


@dataclasses.dataclass(frozen=True)
class KeySpec:
    """An ordered list of key columns, major (most significant) first.

    ``KeySpec.of(year=23, month=4, day=5)`` packs ``(year << 9) |
    (month << 5) | day`` into a uint32; totals over 32 bits widen to
    uint64 (the paper's composite keys stop competing for 32 bits).  The
    packed EMPTY sentinel (all ones) is reserved: the all-max column
    combination is rejected by :meth:`pack`.
    """

    columns: tuple[KeyColumn, ...]

    def __post_init__(self):
        if not self.columns:
            raise ValueError("KeySpec needs at least one column")
        names = [c.name for c in self.columns]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate key column names: {names}")
        if self.total_bits > 64:
            raise ValueError(
                f"composite key needs {self.total_bits} bits; the engine "
                "supports at most 64"
            )

    # -- construction ----------------------------------------------------
    @classmethod
    def of(cls, **bits_by_name: int) -> "KeySpec":
        """``KeySpec.of(year=23, month=4, day=5)`` — order is significance
        order, major first (Python keeps kwargs ordered)."""
        return cls(tuple(KeyColumn(n, b) for n, b in bits_by_name.items()))

    # -- properties ------------------------------------------------------
    @property
    def names(self) -> tuple[str, ...]:
        return tuple(c.name for c in self.columns)

    @property
    def total_bits(self) -> int:
        return sum(c.bits for c in self.columns)

    @property
    def key_dtype(self) -> np.dtype:
        return key_dtype_for_bits(self.total_bits)

    @property
    def empty(self) -> np.unsignedinteger:
        return empty_key(self.key_dtype)

    @property
    def max_packed(self) -> np.unsignedinteger:
        return max_key(self.key_dtype)

    def shift_of(self, name: str) -> int:
        """Bit position of a column's least-significant bit in the packed key."""
        shift = 0
        for c in reversed(self.columns):
            if c.name == name:
                return shift
            shift += c.bits
        raise KeyError(f"no key column {name!r} in {self.names}")

    def prefix(self, n: int) -> "KeySpec":
        """The KeySpec of the first (most significant) ``n`` columns."""
        if not 1 <= n <= len(self.columns):
            raise ValueError(f"prefix length {n} not in [1, {len(self.columns)}]")
        return KeySpec(self.columns[:n])

    # -- packing ---------------------------------------------------------
    def _as_columns(self, columns) -> list[np.ndarray]:
        if isinstance(columns, Mapping):
            missing = [n for n in self.names if n not in columns]
            if missing:
                raise KeyError(f"missing key columns: {missing}")
            cols = [columns[n] for n in self.names]
        else:
            cols = list(columns)
            if len(cols) != len(self.columns):
                raise ValueError(
                    f"expected {len(self.columns)} key columns, got {len(cols)}"
                )
        return [np.asarray(c) for c in cols]

    def pack(self, columns, *, validate: bool = True) -> np.ndarray:
        """Pack named columns (mapping or significance-ordered sequence)
        into one sort-key vector of :attr:`key_dtype`.

        Packing happens host-side in NumPy — uint64 needs no JAX x64
        flag here.  ``validate=True`` checks every column against its bit
        budget and rejects the reserved EMPTY bit pattern.
        """
        with span("repro.pack"):
            cols = self._as_columns(columns)
            out = np.zeros(cols[0].shape, dtype=np.uint64)
            for spec, col in zip(self.columns, cols):
                col = col.astype(np.uint64)
                if validate and col.size and int(col.max()) > spec.max_value:
                    raise ValueError(
                        f"column {spec.name!r} exceeds its {spec.bits}-bit "
                        f"budget (max value {int(col.max())} > "
                        f"{spec.max_value})"
                    )
                out = (out << np.uint64(spec.bits)) | col
            packed = out.astype(self.key_dtype)
            if (validate and packed.size
                    and bool((packed == self.empty).any())):
                raise ValueError(
                    "the all-ones column combination packs to the reserved "
                    "EMPTY sentinel; reduce a column's max value or widen a "
                    "column"
                )
        return packed

    def unpack(self, keys) -> dict[str, np.ndarray]:
        """Packed keys → named columns (EMPTY rows map to all-max columns)."""
        keys = np.asarray(keys).astype(np.uint64)
        out: dict[str, np.ndarray] = {}
        shift = 0
        for c in reversed(self.columns):
            mask = np.uint64((1 << c.bits) - 1)
            out[c.name] = ((keys >> np.uint64(shift)) & mask).astype(
                np.uint32 if c.bits <= 32 else np.uint64
            )
            shift += c.bits
        return {n: out[n] for n in self.names}


# ---------------------------------------------------------------------------
# AggSpec — declarative aggregates
# ---------------------------------------------------------------------------

_FINALIZERS = {"avg": ("sum", "count")}
_STORED = ("count", "sum", "min", "max")
_KNOWN = set(_STORED) | set(_FINALIZERS)


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """The requested aggregates: any of count/sum/min/max plus finalizers
    (currently ``avg`` = sum/count).  The stored accumulator carries only
    the value planes the request needs — ``AggSpec("count")`` spills no
    float columns at all."""

    names: tuple[str, ...]

    def __init__(self, *names: str):
        if len(names) == 1 and isinstance(names[0], (tuple, list)):
            names = tuple(names[0])
        if not names:
            names = ("count",)
        unknown = [n for n in names if n not in _KNOWN]
        if unknown:
            raise ValueError(f"unknown aggregates {unknown}; known: {sorted(_KNOWN)}")
        object.__setattr__(self, "names", tuple(dict.fromkeys(names)))

    @property
    def stored(self) -> tuple[str, ...]:
        """Accumulator fields needed (requested + finalizer inputs)."""
        need = set()
        for n in self.names:
            need.update(_FINALIZERS.get(n, (n,)))
        return tuple(n for n in _STORED if n in need or n == "count")

    def plane_widths(self, payload_width: int) -> tuple[int, int, int]:
        """(sum, min, max) plane widths for a V-wide payload."""
        stored = self.stored
        return (
            payload_width if "sum" in stored else 0,
            payload_width if "min" in stored else 0,
            payload_width if "max" in stored else 0,
        )

    def needs_payload(self) -> bool:
        return any(w for w in self.plane_widths(1))

    def finalize(self, state: AggState) -> dict[str, Any]:
        """Accumulator → the requested user-facing aggregate columns."""
        valid = state.valid()
        out: dict[str, Any] = {}
        for n in self.names:
            if n == "count":
                out["count"] = state.count
            elif n == "sum":
                out["sum"] = state.sum
            elif n == "min":
                out["min"] = jnp.where(valid[:, None], state.min, 0.0)
            elif n == "max":
                out["max"] = jnp.where(valid[:, None], state.max, 0.0)
            elif n == "avg":
                c = jnp.maximum(state.count, 1).astype(jnp.float32)[:, None]
                out["avg"] = state.sum / c
        return out


# ---------------------------------------------------------------------------
# the front door
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class AggResult:
    """Sorted result relation of :func:`aggregate`.

    ``state`` is the raw accumulator (keys sorted ascending, EMPTY-padded
    tail); ``relation()`` unpacks it into named key columns + the
    requested aggregate columns, dropping the padding.
    """

    state: AggState
    stats: SpillStats
    by: KeySpec
    aggs: AggSpec
    plan: dict[str, Any]

    @property
    def keys(self):
        return self.state.keys

    def occupancy(self) -> int:
        return int(self.state.occupancy())

    def relation(self) -> dict[str, np.ndarray]:
        """Named key columns + aggregate columns, padding removed, rows
        sorted by the composite key (major column first)."""
        keys = np.asarray(self.state.keys)
        # mask with the STATE's sentinel: a rollup prefix level may carry a
        # narrower KeySpec (≤32 bits) over a still-uint64 engine state
        mask = keys != empty_key(keys.dtype)
        out = {n: c[mask] for n, c in self.by.unpack(keys).items()}
        for name, col in self.aggs.finalize(self.state).items():
            out[name] = np.asarray(col)[mask]
        return out

    @property
    def sorted_by(self) -> dict[str, Any]:
        """The order property this relation carries: rows ascend by the
        packed composite key, i.e. lexicographically by every ``by``
        column (major first) — established by the ONE sort the aggregation
        paid.  Downstream operators consume it instead of re-sorting:
        :meth:`merge_join` and :meth:`rollup` run with a zero sort term,
        which is what the plan's ``input_sorted`` / ``inputs_sorted``
        cost-model credit records."""
        return {
            "columns": self.by.names,
            "prefix_len": len(self.by.columns),
            "key_dtype": str(np.dtype(self.by.key_dtype)),
        }

    def _ordered_state(self) -> AggState:
        """The state with the single-device OrderedIndex layout (keys
        ascending, ONE EMPTY tail).  Mesh-produced relations are globally
        sorted but EMPTY-padded per shard; one compaction gather — not a
        sort — closes the interior gaps."""
        if self.plan.get("mesh"):
            return mj_mod.compact_state(self.state)
        return self.state

    def merge_join(
        self,
        other: "AggResult",
        *,
        how: str = "inner",
        backend: str = "auto",
        mesh=None,
        mesh_axis: str | None = None,
    ) -> "JoinResult":
        """Merge join with another aggregated relation, consuming BOTH
        sides' established key order — no sort, no scatter (§2.5 +
        the "interesting orderings" payoff).

        ``how``: ``"inner"`` (aligned per-side aggregate packets plus the
        group-join product columns), ``"semi"`` (this side's groups with
        a match), ``"anti"`` (groups without one).  Join keys must agree
        between the two sides — same packed dtype and same column bit
        layout — and a mismatch raises immediately (a silent truncation
        would join garbage).

        ``mesh`` runs the sharded form: both sides are partitioned by ONE
        jointly sampled cut vector through the existing key-range
        ``all_to_all``, each owner merges its fragments and joins
        locally — order survives the shuffle, so there is still no sort
        anywhere.  ``stats.rows_exchanged`` counts both sides' shuffle
        volume on top of whatever the inputs already paid."""
        _check_join_compat(self.by, other.by)
        if how not in mj_mod.JOIN_HOWS:
            raise ValueError(
                f"unknown join how={how!r}; expected one of {mj_mod.JOIN_HOWS}"
            )
        backend = dispatch.resolve_backend_name(backend)
        stats = SpillStats.reduce_shards([self.stats, other.stats])
        plan: dict[str, Any] = {
            "operator": "merge_join",
            "how": how,
            "backend": backend,
            "inputs_sorted": True,
            "sorted_by": [self.sorted_by, other.sorted_by],
            "left_plan": self.plan,
            "right_plan": other.plan,
        }
        with key_dtype_context(self.by.key_dtype):
            if mesh is not None:
                left, right, exchange, axis, world = _mesh_merge_join(
                    self._ordered_state(), other._ordered_state(),
                    mesh, mesh_axis, how=how, backend=backend,
                )
                stats = dataclasses.replace(
                    stats,
                    rows_exchanged=(stats.rows_exchanged
                                    + exchange["rows_exchanged"]),
                    exchange_quota=max(stats.exchange_quota,
                                       exchange["quota"]),
                    exchange_max_fill=max(stats.exchange_max_fill,
                                          exchange["max_fill"]),
                    exchange_retries=(stats.exchange_retries
                                      + exchange["retries"]),
                )
                plan["mesh"] = {"axis": axis, "world": world,
                                "exchange": exchange}
            else:
                left, right = mj_mod.merge_join(
                    self._ordered_state(), other._ordered_state(),
                    how=how, backend=backend,
                )
            products = None
            if how == "inner":
                products = _join_products_state(left, right)
        plan["cost_model"] = cost_model.join_cost_surface(
            self.state.capacity, other.state.capacity, inputs_sorted=True,
        )
        plan["cost_model_resort_baseline"] = cost_model.join_cost_surface(
            self.state.capacity, other.state.capacity, inputs_sorted=False,
        )
        return JoinResult(
            left=left, right=right, products=products, by=self.by,
            left_aggs=self.aggs, right_aggs=other.aggs, stats=stats,
            plan=plan, how=how,
        )

    def rollup(
        self, levels: Sequence[int] | None = None, *, backend: str = "auto"
    ) -> dict[tuple[str, ...], "AggResult"]:
        """Coarser prefix levels peeled from this ALREADY-sorted result —
        §2.2's "rollup from one sort", as an operator over the result
        instead of a fresh aggregation: no input re-read, no sort, no
        spill.  Returns ``{prefix column names: AggResult}`` like the
        module-level :func:`rollup`."""
        backend = dispatch.resolve_backend_name(backend)
        out: dict[tuple[str, ...], AggResult] = {}
        with key_dtype_context(self.by.key_dtype):
            state = self._ordered_state()
            for names, st, spec in _iter_prefix_levels(
                state, self.by, levels, backend
            ):
                plan = dict(self.plan)
                plan.pop("mesh", None)  # compacted above: tail layout again
                plan["rollup"] = {"level": names, "sorts": 0,
                                  "from_order": self.sorted_by}
                out[names] = AggResult(
                    state=st, stats=self.stats, by=spec, aggs=self.aggs,
                    plan=plan,
                )
        return out


def _replicate(x: jax.Array) -> jax.Array:
    if not isinstance(x.sharding, NamedSharding):
        return x
    return jax.device_put(x, NamedSharding(x.sharding.mesh, PartitionSpec()))


def _check_join_compat(left_by: KeySpec, right_by: KeySpec) -> None:
    """Joining two relations requires ONE shared packed key space: same
    key dtype and same column bit layout.  Anything else raises loudly —
    the seed prototype silently truncated to uint32, which joins garbage
    on >32-bit keys."""
    if left_by.key_dtype != right_by.key_dtype:
        raise TypeError(
            f"join key dtype mismatch: left packs to "
            f"{np.dtype(left_by.key_dtype)} ({left_by.total_bits} bits, "
            f"columns {left_by.names}), right to "
            f"{np.dtype(right_by.key_dtype)} ({right_by.total_bits} bits, "
            f"columns {right_by.names}) — repack both sides with one "
            "KeySpec bit layout"
        )
    lb = tuple(c.bits for c in left_by.columns)
    rb = tuple(c.bits for c in right_by.columns)
    if lb != rb:
        raise TypeError(
            f"join key layout mismatch: left columns {left_by.names} pack "
            f"as bits {lb}, right columns {right_by.names} as {rb} — equal "
            "packed keys would not mean equal column values"
        )


def _join_products_state(left: AggState, right: AggState) -> AggState:
    """The group-join product columns (§2.5) materialized as an AggState
    sharing the join's key vector, sum plane = [join_count,
    Σ_L·|R| (V_L cols), |L|·Σ_R (V_R cols)].  Carrying the products as
    sum planes makes rollup exact: SUM over join pairs is additive
    across fine keys, so peeling a prefix level segmented-combines the
    products right along with the per-side packets."""
    prods = mj_mod.group_join_products(left, right)
    plane = jnp.concatenate(
        [
            prods["join_count"][:, None],
            prods["sum_left_x_count_right"],
            prods["count_left_x_sum_right"],
        ],
        axis=1,
    )
    n = left.capacity
    return AggState(
        keys=left.keys,
        count=left.count,
        sum=plane,
        min=jnp.zeros((n, 0), jnp.float32),
        max=jnp.zeros((n, 0), jnp.float32),
    )


@dataclasses.dataclass
class JoinResult:
    """Result of an order-consuming :meth:`AggResult.merge_join`.

    ``left`` and ``right`` are per-side aggregate packets **aligned on
    ONE sorted key vector** (right is None for semi/anti); ``products``
    carries the §2.5 group-join product columns as sum planes (inner
    only).  Because everything shares one key order, the result is
    itself an ordered relation: :meth:`rollup` peels prefix levels from
    it with segmented combines — still zero sorts downstream of the
    sources' original ones."""

    left: AggState
    right: AggState | None
    products: AggState | None
    by: KeySpec
    left_aggs: AggSpec
    right_aggs: AggSpec
    stats: SpillStats
    plan: dict[str, Any]
    how: str = "inner"

    @property
    def state(self) -> AggState:
        return self.left

    @property
    def keys(self):
        return self.left.keys

    def occupancy(self) -> int:
        return int(self.left.occupancy())

    @property
    def sorted_by(self) -> dict[str, Any]:
        """Join output inherits the inputs' key order (see
        :attr:`AggResult.sorted_by`)."""
        return {
            "columns": self.by.names,
            "prefix_len": len(self.by.columns),
            "key_dtype": str(np.dtype(self.by.key_dtype)),
        }

    def _ordered_states(self) -> tuple[AggState, ...]:
        states = tuple(
            s for s in (self.left, self.right, self.products) if s is not None
        )
        if self.plan.get("mesh"):
            # identical key vectors ⇒ identical compaction ⇒ alignment
            # holds.  The compaction moves rows across shards, so the
            # key-range-sharded states are replicated over their mesh first.
            states = tuple(
                mj_mod.compact_state(jax.tree.map(_replicate, s))
                for s in states)
        return states + (None,) * (3 - len(states))

    def relation(self) -> dict[str, np.ndarray]:
        """Key columns + per-side aggregate columns (``*_left`` /
        ``*_right``) + the group-join product columns (inner joins),
        padding removed, rows in key order."""
        keys = np.asarray(self.left.keys)
        mask = keys != empty_key(keys.dtype)
        out = {n: c[mask] for n, c in self.by.unpack(keys).items()}
        for name, col in self.left_aggs.finalize(self.left).items():
            out[f"{name}_left"] = np.asarray(col)[mask]
        if self.right is not None:
            for name, col in self.right_aggs.finalize(self.right).items():
                out[f"{name}_right"] = np.asarray(col)[mask]
        if self.products is not None:
            wl = self.left.sum.shape[1]
            plane = np.asarray(self.products.sum)[mask]
            out["join_count"] = plane[:, 0]
            out["sum_left_x_count_right"] = plane[:, 1 : 1 + wl]
            out["count_left_x_sum_right"] = plane[:, 1 + wl :]
        return out

    def rollup(
        self, levels: Sequence[int] | None = None, *, backend: str = "auto"
    ) -> dict[tuple[str, ...], "JoinResult"]:
        """Prefix-level rollup OF THE JOIN — aggregate → merge join →
        rollup from the sources' single sorts.  All constituent states
        share one key vector, so each peel applies the identical
        segmented combine to every side and alignment is preserved; the
        product planes are sums over join pairs, hence roll up exactly
        (the coarse ``join_count`` is Σ over fine matched keys of
        |L|·|R|, i.e. the fine join's cardinality grouped by prefix)."""
        backend = dispatch.resolve_backend_name(backend)
        out: dict[tuple[str, ...], JoinResult] = {}
        with key_dtype_context(self.by.key_dtype):
            left0, right0, prod0 = self._ordered_states()
            peels = [_iter_prefix_levels(left0, self.by, levels, backend)]
            if right0 is not None:
                peels.append(_iter_prefix_levels(right0, self.by, levels, backend))
            if prod0 is not None:
                peels.append(_iter_prefix_levels(prod0, self.by, levels, backend))
            for tier in zip(*peels):
                names, st_l, spec = tier[0]
                st_r = tier[1][1] if right0 is not None else None
                st_p = tier[-1][1] if prod0 is not None else None
                plan = dict(self.plan)
                plan.pop("mesh", None)
                plan["rollup"] = {"level": names, "sorts": 0,
                                  "from_order": self.sorted_by}
                out[names] = JoinResult(
                    left=st_l, right=st_r, products=st_p, by=spec,
                    left_aggs=self.left_aggs, right_aggs=self.right_aggs,
                    stats=self.stats, plan=plan, how=self.how,
                )
        return out


def _mesh_merge_join(a: AggState, b: AggState, mesh, mesh_axis, *,
                     how: str, backend: str):
    """Mesh-sharded merge join: joint sampled cuts → both sides through
    the CAPACITY-BOUNDED key-range exchange → per-owner local merge join
    (see :func:`repro.distributed.groupby.sharded_merge_join_local`).
    A send segment over either side's per-peer quota retries ONCE at the
    next pow2 quotas with a loud log, then raises
    (:class:`~repro.core.types.ExchangeOverflowError`); any other row
    loss (an owner's matches over its output slice) raises immediately.
    Returns ``(left, right_or_None, exchange, axis, world)`` where
    ``exchange`` is a dict of host accounting (``rows_exchanged``,
    ``quota``, ``max_fill``, ``retries``)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.pipeline import resolve_mesh_axis
    from repro.core.types import ExchangeOverflowError
    from repro.distributed import groupby as gb_mod

    axis = resolve_mesh_axis(mesh, mesh_axis)
    world = int(mesh.shape[axis])
    dispatch.check_shardable(backend)

    def prep(st: AggState) -> AggState:
        cap = -(-st.capacity // world) * world
        if cap != st.capacity:
            st = concat_states(st, empty_like(st, cap - st.capacity))
        return st

    a, b = prep(a), prep(b)
    spec = AggState(keys=P(axis), count=P(axis), sum=P(axis, None),
                    min=P(axis, None), max=P(axis, None))
    cap_a, cap_b = a.capacity // world, b.capacity // world
    q_a = gb_mod.default_exchange_quota(cap_a, world)
    q_b = gb_mod.default_exchange_quota(cap_b, world)

    def sharded(qa, qb):
        def body(a_, b_):
            return gb_mod.sharded_merge_join_local(
                a_, b_, axis, world, how=how, backend=backend,
                quota_a=qa, quota_b=qb,
            )

        return jax.jit(jax.shard_map(
            body, mesh=mesh, check_vma=dispatch.checks_vma(backend),
            in_specs=(spec, spec),
            out_specs=(spec, spec, P(), P(), P(), P())))

    left, right, rows_sent, send_dropped, dropped, max_fill = (
        sharded(q_a, q_b)(a, b))
    retries = 0
    if bool(send_dropped):
        qa2 = min(gb_mod._pow2_ceil(q_a + 1), gb_mod._pow2_ceil(cap_a))
        qb2 = min(gb_mod._pow2_ceil(q_b + 1), gb_mod._pow2_ceil(cap_b))
        if qa2 <= q_a and qb2 <= q_b:
            raise ExchangeOverflowError(
                "mesh-sharded merge join exchange overflowed its per-peer "
                f"quotas at the lossless ceiling (fullest segment "
                f"{int(max_fill)} rows vs quotas {q_a}/{q_b})",
                quota=max(q_a, q_b), max_fill=int(max_fill),
            )
        logging.getLogger(__name__).warning(
            "mesh merge join exchange overflowed its per-peer quotas "
            "%d/%d (fullest segment %d rows); retrying once at %d/%d",
            q_a, q_b, int(max_fill), qa2, qb2,
        )
        retries = 1
        q_a, q_b = qa2, qb2
        left, right, rows_sent, send_dropped, dropped, max_fill = (
            sharded(q_a, q_b)(a, b))
        if bool(send_dropped):
            raise ExchangeOverflowError(
                "mesh-sharded merge join exchange overflowed its per-peer "
                f"quotas even after one retry at {q_a}/{q_b} (fullest "
                f"segment {int(max_fill)} rows) — results would be "
                "missing join keys",
                quota=max(q_a, q_b), max_fill=int(max_fill),
            )
    if bool(dropped):
        raise RuntimeError(
            "mesh-sharded merge join dropped rows: a key-range owner's "
            "matches exceeded its output slice (skewed cuts) — results "
            "would be missing join keys.  Widen the inputs' capacity or "
            "join without mesh="
        )
    exchange = {
        "rows_exchanged": int(rows_sent),
        "quota": max(q_a, q_b),
        "max_fill": int(max_fill),
        "retries": retries,
    }
    return left, (right if how == "inner" else None), exchange, axis, world


def pipeline(steps):
    """Run an order-preserving operator pipeline: ONE sort per source
    relation, ZERO sorts between operators.

    ``steps`` is a list; the FIRST entry is the source — an existing
    :class:`AggResult` or ``("aggregate", kwargs)`` — and each later
    entry is ``("merge_join", {"right": <AggResult | ("aggregate",
    kwargs)>, ...})`` or ``("rollup", {"levels": ...})``::

        out = repro.pipeline([
            ("aggregate", dict(columns=..., by=spec, values=v,
                               aggs=("count", "sum"))),
            ("merge_join", {"right": dim_result}),
            ("rollup", {"levels": [2, 1]}),
        ])

    Operators past the sources consume the established key order
    (:attr:`AggResult.sorted_by`): the merge join is a rank-alignment
    probe and the rollup a chain of segmented combines — neither emits a
    sort or scatter.  The returned result's ``plan["pipeline"]`` records
    the stage list, the number of source sorts paid, and the zero
    re-sort count the composition guarantees."""
    if not steps:
        raise ValueError("pipeline needs at least a source step")
    sources = 0

    def _source(spec):
        nonlocal sources
        if isinstance(spec, AggResult):
            sources += 1
            return spec
        if (isinstance(spec, tuple) and len(spec) == 2
                and spec[0] == "aggregate"):
            sources += 1
            return aggregate(**spec[1])
        raise TypeError(
            "pipeline source must be an AggResult or ('aggregate', "
            f"kwargs), got {spec!r}"
        )

    stages = ["aggregate"]
    cur = _source(steps[0])
    for step in steps[1:]:
        if not (isinstance(step, tuple) and len(step) == 2):
            raise TypeError(f"pipeline step must be (op, kwargs), got {step!r}")
        op, kw = step
        kw = dict(kw)
        if op == "merge_join":
            right = _source(kw.pop("right"))
            cur = cur.merge_join(right, **kw)
            stages.append(f"merge_join[{cur.how}]")
        elif op == "rollup":
            if isinstance(cur, dict):
                raise TypeError("cannot compose past a rollup fan-out")
            cur = cur.rollup(**kw)
            stages.append("rollup")
        else:
            raise ValueError(f"unknown pipeline op {op!r}: merge_join|rollup")
    block = {"stages": stages, "source_sorts": sources, "re_sorts": 0}
    results = cur.values() if isinstance(cur, dict) else (cur,)
    for r in results:
        r.plan = dict(r.plan)
        r.plan["pipeline"] = block
    return cur


def _iter_prefix_levels(state: AggState, by: KeySpec, levels, backend: str):
    """Peel minor key columns off a key-sorted state, yielding
    ``(prefix_names, state, prefix_spec)`` finest level first.  Dropping
    the least-significant column is a right-shift — monotone on the
    packed key — so every coarser level is ONE segmented combine of the
    already-sorted finer level: no sort, no spill (§2.2).  Caller holds
    :func:`key_dtype_context`."""
    n_cols = len(by.columns)
    if levels is None:
        levels = list(range(n_cols, -1, -1))
    requested = sorted(set(int(l) for l in levels), reverse=True)
    if requested[0] > n_cols or requested[-1] < 0:
        raise ValueError(f"rollup levels {requested} out of range [0, {n_cols}]")
    spec = by
    cur = n_cols
    for lvl in requested:
        while cur > lvl:
            # peel the minor column: shift is monotone ⇒ stays sorted
            dropped = spec.columns[-1]
            spec = KeySpec(spec.columns[:-1]) if cur > 1 else spec
            shifted = state.keys >> state.keys.dtype.type(dropped.bits)
            sentinel = empty_key(state.keys.dtype)
            if cur == 1:
                # grand total: a single all-rows group under key 0
                spec = KeySpec((KeyColumn("__all__", 1),))
                shifted = jnp.zeros_like(state.keys)
            keys2 = jnp.where(state.valid(), shifted, sentinel)
            state = sorted_ops.segmented_combine(
                AggState(keys2, state.count, state.sum, state.min, state.max),
                backend=backend,
            )
            cur -= 1
        yield by.names[:lvl], state, spec


def _resolve_order_by(order_by, by: KeySpec) -> bool:
    """order_by must be a prefix of the key columns (satisfiable from the
    one sort); returns whether sorted output is required."""
    if order_by is None or order_by is False:
        return False
    if order_by is True:
        return True
    names = (order_by,) if isinstance(order_by, str) else tuple(order_by)
    if names != by.names[: len(names)]:
        raise ValueError(
            f"order_by {names} is not a prefix of the key columns {by.names}; "
            "one sort cannot satisfy it — reorder the KeySpec"
        )
    return True


def _plan(
    n_rows: int,
    cfg: ExecConfig,
    output_estimate: int | None,
    *,
    input_sorted: bool = False,
) -> dict:
    """Optimizer-style cost comparison (paper Fig 23/24): predicted spill
    volumes for the in-sort operator and the hash baseline, plus the
    machine-calibrated decision surface (``make calibrate``).  The
    paper's point — and this function's — is that in-sort aggregation is
    never worse in *volume*, so ``algorithm="auto"`` is always in-sort;
    WHICH in-sort run-generation policy wins in *seconds* is what the
    calibrated surface (and, streamed, the runtime governor) decides.

    ``input_sorted=True`` credits a key order an upstream
    :func:`aggregate` already established: the sort term of the
    predicted cost is zero (sorting an already-sorted relation is pure
    waste — the ROADMAP's order-enforcement item)."""
    O = output_estimate or cfg.memory_rows * cfg.fanin
    insort_cb = cost_model.simulate_insort(
        n_rows, O, cfg.memory_rows, cfg.fanin,
        early_aggregation=True, wide_merge=True, replacement_selection=True,
    )
    hash_cb = cost_model.simulate_hash(
        n_rows, O, cfg.memory_rows, cfg.fanin, hybrid=True
    )
    levels = max(1, cost_model.merge_levels_insort(O, cfg.memory_rows,
                                                   cfg.fanin))
    import jax  # the constants table is keyed by device backend

    return {
        "input_rows": n_rows,
        "output_estimate": O,
        "in_memory": n_rows <= cfg.memory_rows,
        "input_sorted": input_sorted,
        "predicted_spill_insort": insort_cb.total_spill,
        "predicted_spill_hash": hash_cb.total_spill,
        "cost_model": cost_model.cost_surface(
            n_rows, O, backend=jax.default_backend(), merge_levels=levels,
            input_sorted=input_sorted,
        ),
    }


# ids of the one-shot queries, for the repro.aggregate span's query=
_query_ids = itertools.count()


def aggregate(
    columns,
    *,
    by: KeySpec,
    values=None,
    aggs: AggSpec | Sequence[str] | str = ("count",),
    order_by=None,
    algorithm: str = "auto",
    backend: str = "auto",
    cfg: ExecConfig | None = None,
    output_estimate: int | None = None,
    input_sorted: bool = False,
    pipeline: str = "device",
    mesh=None,
    mesh_axis: str | None = None,
) -> AggResult:
    """Duplicate removal / grouping / aggregation behind one front door.

    ``columns``: mapping of key-column name → integer vector (or a
    significance-ordered sequence), packed per ``by``.  ``values``: the
    optional V-wide float payload the aggregates run over.  ``aggs``
    names the requested aggregates; the accumulator carries only what
    they need.  ``order_by`` (True, or a prefix of ``by``'s column
    names) asserts the result must be key-sorted — free for the
    sort-based algorithms, an extra sort for the hash baselines.

    **Streamed input**: ``columns`` may instead be a generator/iterator
    of column-batch mappings (see
    :func:`repro.data.pipeline.iter_column_batches`); the engine then
    absorbs the input chunk by chunk through the double-buffered
    streamed pipeline (:func:`repro.core.pipeline.
    aggregate_device_stream`) — the input never needs to be resident at
    once, and the device footprint is bounded by the chunk size.  In
    this form ``values`` names a float column carried in each batch
    mapping (a string), the algorithm is in-sort on the device pipeline
    (the only external algorithm here — exactly the paper's point), and
    everything else behaves identically.

    ``algorithm``: ``"auto"`` (the paper's systems-only choice: in-sort),
    ``"insort"``, ``"hash"``, ``"f1_hash"``, ``"sort_then_stream"``, or
    ``"inmemory"``.  Streamed input additionally accepts (and defaults
    to, where the geometry allows) ``"adaptive"``: the in-sort pipeline
    with the run-generation policy re-decided mid-flight by the
    calibrated policy governor (:mod:`repro.core.adaptive`).
    ``backend``: ``"auto" | "xla" | "pallas"`` through the dispatch
    registry.

    ``input_sorted=True`` asserts the input already arrives in key
    order (e.g. the relation came out of an upstream ``aggregate`` —
    its results are key-sorted by construction); the plan's calibrated
    cost surface then credits a zero sort term.

    ``output_estimate`` sizes the result buffers; if the output
    overruns them anyway, finalize retries ONCE at the next power of
    two (with one more pre-merge level) before raising.

    With the default ``pipeline="device"``, the in-sort algorithms
    compile to ONE device program — run generation as a ``lax.scan``
    fused with the wide merge (:mod:`repro.core.pipeline`), with a single
    host readback for the stats.  ``pipeline="host"`` selects the
    host-orchestrated reference loop (exact per-merge-level accounting).

    ``mesh`` (a :class:`jax.sharding.Mesh`) shards that one device
    program over ``mesh_axis`` (default: the mesh's first axis): each
    device runs run generation over its shard, then a key-range
    ``all_to_all`` exchanges the locally aggregated sorted fragments and
    each range owner merges them — the relation stays globally sorted by
    the composite key, and ``stats.rows_exchanged`` records the shuffle
    volume (valid rows on the wire, which local early aggregation keeps
    below the input row count on duplicate-heavy data).  In-sort +
    ``pipeline="device"`` only; ``mesh=None`` is today's single-device
    program, bit for bit.
    """
    cfg = cfg or ExecConfig()
    if mesh is not None and algorithm not in ("auto", "insort"):
        raise ValueError(
            f"mesh-sharded aggregation is in-sort only, got algorithm="
            f"{algorithm!r}"
        )
    if not isinstance(aggs, AggSpec):
        aggs = AggSpec(aggs) if isinstance(aggs, str) else AggSpec(*aggs)
    if isinstance(columns, Iterator):
        return _aggregate_stream(
            columns, by=by, values=values, aggs=aggs, order_by=order_by,
            algorithm=algorithm, backend=backend, cfg=cfg,
            output_estimate=output_estimate, input_sorted=input_sorted,
            pipeline=pipeline, mesh=mesh, mesh_axis=mesh_axis,
        )
    if algorithm == "adaptive":
        raise ValueError(
            "algorithm='adaptive' adapts mid-stream — it needs streamed "
            "input (pass an iterator of column batches); one-shot input "
            "is planned up front with algorithm='auto'"
        )
    with span("repro.aggregate", query=next(_query_ids)):
        packed = by.pack(columns)
        want_sorted = _resolve_order_by(order_by, by)
        if values is not None:
            values = np.asarray(values, dtype=np.float32)
            if values.ndim == 1:
                values = values[:, None]
            widths = aggs.plane_widths(values.shape[1])
            if not any(widths):
                values = None  # nothing requested needs the payload
                widths = (0, 0, 0)
        else:
            widths = (0, 0, 0)
            if aggs.needs_payload():
                raise ValueError(
                    f"aggregates {aggs.names} need a payload; pass values=..."
                )
        plan = _plan(len(packed), cfg, output_estimate,
                     input_sorted=input_sorted)
        backend = dispatch.resolve_backend_name(backend)
        plan["backend"] = backend

        sort_based = algorithm in ("auto", "insort", "sort_then_stream",
                                   "inmemory")
        plan["algorithm"] = "insort" if algorithm == "auto" else algorithm
        plan["pipeline"] = (pipeline if algorithm in ("auto", "insort")
                            else "host")
        if mesh is not None:
            from repro.core.pipeline import resolve_mesh_axis

            axis = resolve_mesh_axis(mesh, mesh_axis)
            plan["mesh"] = {"axis": axis, "world": int(mesh.shape[axis])}
        with key_dtype_context(by.key_dtype):
            if algorithm in ("auto", "insort"):
                state, stats = insort_mod.insort_aggregate(
                    packed, values, cfg, output_estimate=output_estimate,
                    backend=backend, widths=widths, pipeline=pipeline,
                    mesh=mesh, mesh_axis=mesh_axis,
                )
            elif algorithm == "sort_then_stream":
                state, stats = insort_mod.sort_then_stream_aggregate(
                    packed, values, cfg, backend=backend
                )
            elif algorithm == "hash":
                state, stats = hash_mod.hash_aggregate(
                    packed, values, cfg, output_estimate=output_estimate,
                    backend=backend, widths=widths,
                )
            elif algorithm == "f1_hash":
                state, stats = hash_mod.f1_hash_aggregate(
                    packed, values, cfg, backend=backend, widths=widths
                )
            elif algorithm == "inmemory":
                state = sorted_ops.sorted_groupby(
                    packed, values, backend=backend, widths=widths
                )
                stats = SpillStats()
            else:
                raise ValueError(f"unknown algorithm {algorithm!r}")
            if want_sorted and not sort_based:
                # hash order → key order: the extra sort the paper's operator
                # never pays (Fig 19)
                state = sorted_ops.sort_state(state, backend=backend)
        return AggResult(state=state, stats=stats, by=by, aggs=aggs, plan=plan)


def _aggregate_stream(
    batches,
    *,
    by: KeySpec,
    values,
    aggs: AggSpec,
    order_by,
    algorithm: str,
    backend: str,
    cfg: ExecConfig,
    output_estimate: int | None,
    input_sorted: bool,
    pipeline: str,
    mesh,
    mesh_axis: str | None,
) -> AggResult:
    """:func:`aggregate` over an iterator of column-batch mappings.

    Each batch mapping carries the key columns named by ``by`` plus (when
    ``values`` is a column name) one float value column.  Batches are
    packed host-side one at a time and fed to the double-buffered
    streamed device pipeline — host→device transfer of batch k+1 overlaps
    the device aggregating batch k, and only the finalize syncs.

    ``algorithm="auto"`` runs ``"adaptive"`` where the geometry allows
    (single device, ``memory_rows`` divisible by ``batch_rows``): the
    run-generation policy is re-decided mid-flight by the calibrated
    governor, so a wrong up-front estimate costs one observation window,
    not the stream.  ``"insort"`` keeps the fixed default policy."""
    if algorithm not in ("auto", "insort", "adaptive"):
        raise ValueError(
            f"streamed input runs the in-sort device pipeline only, got "
            f"algorithm={algorithm!r}"
        )
    adaptive_ok = mesh is None and cfg.memory_rows % cfg.batch_rows == 0
    if algorithm == "adaptive" and not adaptive_ok:
        raise ValueError(
            "algorithm='adaptive' needs a single-device stream with "
            "memory_rows divisible by batch_rows, got "
            f"mesh={'set' if mesh is not None else None}, "
            f"memory_rows={cfg.memory_rows}, batch_rows={cfg.batch_rows}"
        )
    adaptive = algorithm == "adaptive" or (algorithm == "auto" and adaptive_ok)
    policy = "adaptive" if adaptive else "rs"
    if pipeline != "device":
        raise ValueError(
            f"streamed input requires pipeline='device', got {pipeline!r}"
        )
    if values is not None and not isinstance(values, str):
        raise TypeError(
            "with streamed input, values must name a column carried in "
            f"each batch mapping (a str), got {type(values).__name__}"
        )
    _resolve_order_by(order_by, by)  # sort-based: always satisfiable

    backend = dispatch.resolve_backend_name(backend)
    rows_seen = 0

    def _prep(batch):
        nonlocal rows_seen
        packed = by.pack(batch)
        rows_seen += len(packed)
        if values is None:
            return packed, None
        if values not in batch:
            raise KeyError(f"values column {values!r} missing from batch")
        vals = np.asarray(batch[values], dtype=np.float32)
        if vals.ndim == 1:
            vals = vals[:, None]
        if len(vals) != len(packed):
            raise ValueError(
                f"values column {values!r} has {len(vals)} rows, key "
                f"columns have {len(packed)}"
            )
        return packed, vals

    from repro.core import pipeline as pipeline_mod

    # Peek one batch to fix the payload width (plane widths are static).
    it = iter(batches)
    first = next(it, None)
    if first is None:
        with key_dtype_context(by.key_dtype):
            state, stats = pipeline_mod.insort_aggregate_device_stream(
                iter(()), cfg, policy=policy, backend=backend,
                widths=(0, 0, 0), width=0, key_dtype=by.key_dtype,
                output_estimate=output_estimate, mesh=mesh,
                mesh_axis=mesh_axis,
            )
        plan = _plan(0, cfg, output_estimate, input_sorted=input_sorted)
        plan.update(algorithm="adaptive" if adaptive else "insort",
                    policy=policy, pipeline="device", backend=backend,
                    streamed=True)
        return AggResult(state=state, stats=stats, by=by, aggs=aggs, plan=plan)

    first_prepped = _prep(first)
    V = 0 if first_prepped[1] is None else first_prepped[1].shape[1]
    widths = aggs.plane_widths(V)
    if values is not None and not any(widths):
        # nothing requested needs the payload — drop the value column
        values = None
        rows_seen = 0
        first_prepped = _prep(first)
        V, widths = 0, (0, 0, 0)
    elif values is None and aggs.needs_payload():
        raise ValueError(
            f"aggregates {aggs.names} need a payload; pass values=<column "
            "name>"
        )

    chunks = itertools.chain([first_prepped], (_prep(b) for b in it))
    with key_dtype_context(by.key_dtype):
        state, stats = pipeline_mod.insort_aggregate_device_stream(
            chunks, cfg, policy=policy, backend=backend, widths=widths,
            width=V, key_dtype=by.key_dtype, output_estimate=output_estimate,
            mesh=mesh, mesh_axis=mesh_axis,
        )
    plan = _plan(rows_seen, cfg, output_estimate, input_sorted=input_sorted)
    plan.update(algorithm="adaptive" if adaptive else "insort",
                policy=policy, pipeline="device", backend=backend,
                streamed=True)
    if adaptive:
        plan["policy_switches"] = stats.policy_switches
        plan["readbacks_paid"] = stats.readbacks_paid
    if mesh is not None:
        axis = pipeline_mod.resolve_mesh_axis(mesh, mesh_axis)
        plan["mesh"] = {"axis": axis, "world": int(mesh.shape[axis])}
    return AggResult(state=state, stats=stats, by=by, aggs=aggs, plan=plan)


def serve_aggregate(**kwargs):
    """Open a long-lived aggregation session — the serving twin of
    :func:`aggregate` for continuously arriving input.

    Same schema arguments (``by=``, ``values=``, ``aggs=``) plus
    ``watermark=<major key column>`` for TTL expiry and the streaming
    engine's knobs (``policy=``, ``cfg=``, ``mesh=``, …).  The session
    ingests column batches with zero host readbacks and answers
    **merge-on-read snapshots**: sorted :class:`AggResult` relations
    computed without consuming the live engine state, so ingest
    continues uninterrupted.  See
    :class:`repro.service.AggregationSession`."""
    from repro.service import AggregationSession  # lazy: optional layer

    return AggregationSession(**kwargs)


# ---------------------------------------------------------------------------
# generic rollup: any prefix hierarchy, all levels from ONE sort
# ---------------------------------------------------------------------------


def rollup(
    columns,
    *,
    by: KeySpec,
    values=None,
    aggs: AggSpec | Sequence[str] | str = ("count", "sum"),
    levels: Sequence[int] | None = None,
    algorithm: str = "auto",
    backend: str = "auto",
    cfg: ExecConfig | None = None,
    output_estimate: int | None = None,
    pipeline: str = "device",
) -> tuple[dict[tuple[str, ...], AggResult], SpillStats]:
    """``GROUP BY ROLLUP(...)`` over any key hierarchy from ONE sort (§2.2).

    Aggregates at the full key, then peels minor columns off the sorted
    output: dropping the least-significant column is a right-shift, which
    is monotone on the packed key, so every coarser level is a
    segmented combine of the (already sorted) finer level — no further
    sort, no extra spill.  ``levels`` selects prefix lengths (default:
    every prefix plus the grand total, which reports as ``()``).

    Returns ({prefix column names: AggResult}, stats of the one sort).
    """
    cfg = cfg or ExecConfig()
    if not isinstance(aggs, AggSpec):
        aggs = AggSpec(aggs) if isinstance(aggs, str) else AggSpec(*aggs)
    fine = aggregate(
        columns, by=by, values=values, aggs=aggs, algorithm=algorithm,
        backend=backend, cfg=cfg, output_estimate=output_estimate,
        pipeline=pipeline,
        order_by=True,  # the peel below requires key-sorted input (hash
        # algorithms pay their post-sort here, Fig 19 style)
    )
    backend = dispatch.resolve_backend_name(backend)
    out: dict[tuple[str, ...], AggResult] = {}
    with key_dtype_context(by.key_dtype):
        for names, state, spec in _iter_prefix_levels(
            fine.state, by, levels, backend
        ):
            out[names] = AggResult(
                state=state, stats=fine.stats, by=spec, aggs=aggs,
                plan=fine.plan,
            )
    return out, fine.stats
