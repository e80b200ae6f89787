"""Core row/aggregate-state types shared by every grouping algorithm.

The paper's operators consume streams of (key, payload) rows and produce
(key, aggregate) rows.  All algorithms in :mod:`repro.core` share one
fixed-shape representation so that sort-based, hash-based, and in-stream
aggregation are interchangeable and bit-comparable:

* keys are ``uint32`` or ``uint64`` (the *key dtype* travels with the
  arrays); the per-dtype sentinel ``EMPTY`` (the dtype's maximum) marks
  unused slots and conveniently sorts to the end, which is how
  fixed-capacity "memory" tiles model the paper's variable-occupancy
  b-tree.  64-bit keys exist so composite grouping keys (see
  :mod:`repro.core.schema`) stop competing for 32 bits; on the host they
  are plain NumPy ``uint64``, and any jnp computation over them must run
  inside :func:`key_dtype_context` (which enables JAX x64 only for that
  scope — the Pallas kernels instead compare 64-bit keys as a (hi, lo)
  pair of uint32 lanes and never need native 64-bit ops).
* the aggregate state is a struct-of-arrays ``AggState`` carrying
  count / sum / min / max over a ``V``-wide float payload (``V = 0`` for
  pure duplicate removal).  Each value plane may independently be absent
  (width 0) so an :class:`repro.core.schema.AggSpec` can request e.g.
  count+sum without paying for min/max.  ``avg`` etc. are finalizers over
  this state, matching the paper's note (§3.3) that the in-memory row
  format differs from both input and output formats.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

EMPTY = np.uint32(0xFFFFFFFF)
# Largest key a user may supply (EMPTY is reserved).
MAX_KEY = np.uint32(0xFFFFFFFE)

# 64-bit twins of the sentinels (composite keys wider than 32 bits).
EMPTY64 = np.uint64(0xFFFFFFFFFFFFFFFF)
MAX_KEY64 = np.uint64(0xFFFFFFFFFFFFFFFE)

KEY_DTYPES = (np.dtype(np.uint32), np.dtype(np.uint64))

_F32_INF = np.float32(np.inf)


def empty_key(dtype) -> np.unsignedinteger:
    """The EMPTY sentinel for a key dtype (its maximum value)."""
    dtype = np.dtype(dtype)
    if dtype == np.uint32:
        return EMPTY
    if dtype == np.uint64:
        return EMPTY64
    raise TypeError(f"unsupported key dtype {dtype}; expected one of {KEY_DTYPES}")


def max_key(dtype) -> np.unsignedinteger:
    """Largest user-suppliable key for a key dtype (EMPTY is reserved)."""
    dtype = np.dtype(dtype)
    if dtype == np.uint32:
        return MAX_KEY
    if dtype == np.uint64:
        return MAX_KEY64
    raise TypeError(f"unsupported key dtype {dtype}; expected one of {KEY_DTYPES}")


def key_dtype_for_bits(bits: int):
    """Smallest supported key dtype holding ``bits`` key bits."""
    if bits <= 32:
        return np.dtype(np.uint32)
    if bits <= 64:
        return np.dtype(np.uint64)
    raise ValueError(f"composite keys are limited to 64 bits, got {bits}")


def _dtype_of(x) -> np.dtype:
    if hasattr(x, "keys"):  # AggState / OrderedIndex
        x = x.keys
    try:
        return np.dtype(x)  # dtype objects, scalar types, dtype names
    except TypeError:
        return np.dtype(x.dtype)  # arrays / scalars


def key_dtype_context(x):
    """Context manager required around jnp computation on 64-bit keys.

    JAX canonicalizes 64-bit types away unless x64 is enabled; enabling it
    globally would change dtype semantics for the whole process (models,
    optimizers, …).  This scopes ``jax.enable_x64(True)`` to the engine
    call operating on uint64 keys and is a no-op for uint32.
    Accepts an array, an AggState, or a dtype.
    """
    if _dtype_of(x) == np.uint64:
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def match_vma(tree, ref):
    """Cast every leaf of ``tree`` to vary over the manual mesh axes that
    any leaf of ``ref`` varies over.

    Inside ``shard_map`` a fresh constant (an initial scan carry, a
    reset counter in one ``cond`` branch) is invariant across the mesh
    axes while the values it meets there are per-shard, and the
    varying-manual-axes check refuses the mismatch.  Outside
    ``shard_map`` nothing varies and this is the identity."""
    axes = frozenset().union(
        *(jax.typeof(x).vma for x in jax.tree.leaves(ref)))

    def cast(x):
        missing = tuple(sorted(axes - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(cast, tree) if axes else tree


def as_key_array(keys) -> jax.Array:
    """Lift user keys to a jnp key vector, preserving uint64, casting
    everything else to the legacy uint32."""
    dtype = _dtype_of(keys)
    if dtype == np.uint64:
        return jnp.asarray(keys, dtype=jnp.uint64)  # caller holds the context
    return jnp.asarray(keys).astype(jnp.uint32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AggState:
    """Struct-of-arrays aggregate accumulator.

    ``keys``   (N,)    uint32 or uint64, EMPTY (dtype max) marks invalid rows.
    ``count``  (N,)    int64-safe int32 group cardinalities.
    ``sum``    (N, Vs) float32 running sums.
    ``min``    (N, Vm) float32 running minima (+inf for invalid).
    ``max``    (N, Vx) float32 running maxima (-inf for invalid).

    The value planes usually share one width V, but any of them may be
    width 0 when the requested aggregates don't need it (see
    :class:`repro.core.schema.AggSpec`).
    """

    keys: jax.Array
    count: jax.Array
    sum: jax.Array
    min: jax.Array
    max: jax.Array

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def width(self) -> int:
        """The payload width V (max over the carried value planes)."""
        return max(self.widths)

    @property
    def widths(self) -> tuple[int, int, int]:
        """Per-plane widths (sum, min, max)."""
        return (self.sum.shape[1], self.min.shape[1], self.max.shape[1])

    @property
    def key_dtype(self) -> np.dtype:
        return np.dtype(self.keys.dtype)

    def valid(self) -> jax.Array:
        return self.keys != empty_key(self.keys.dtype)

    def occupancy(self) -> jax.Array:
        # dtype pinned: x64 mode would promote a plain sum to int64 and
        # break scan/while_loop carries built around occupancy counters
        return jnp.sum(self.valid(), dtype=jnp.int32)


def empty_state(
    capacity: int,
    width: int,
    *,
    key_dtype=np.uint32,
    widths: tuple[int, int, int] | None = None,
) -> AggState:
    """A fresh, all-invalid accumulator of fixed capacity.

    ``widths`` overrides the per-plane (sum, min, max) widths; by default
    all three carry ``width`` columns.
    """
    ws, wm, wx = widths if widths is not None else (width, width, width)
    key_dtype = np.dtype(key_dtype)
    return AggState(
        keys=jnp.full((capacity,), empty_key(key_dtype), dtype=key_dtype),
        count=jnp.zeros((capacity,), dtype=jnp.int32),
        sum=jnp.zeros((capacity, ws), dtype=jnp.float32),
        min=jnp.full((capacity, wm), _F32_INF, dtype=jnp.float32),
        max=jnp.full((capacity, wx), -_F32_INF, dtype=jnp.float32),
    )


def empty_like(state: AggState, capacity: int) -> AggState:
    """An all-invalid state matching ``state``'s key dtype and plane widths."""
    return empty_state(
        capacity, state.width, key_dtype=state.key_dtype, widths=state.widths
    )


def rows_to_state(
    keys: jax.Array,
    payload: jax.Array | None,
    *,
    widths: tuple[int, int, int] | None = None,
) -> AggState:
    """Lift raw input rows into aggregate states (count=1, sum=min=max=v).

    ``widths`` selects which value planes to materialize: each entry is
    either the payload width V or 0 (plane not requested).
    """
    keys = as_key_array(keys)
    n = keys.shape[0]
    if payload is None:
        payload = jnp.zeros((n, 0), dtype=jnp.float32)
    if payload.ndim == 1:
        payload = payload[:, None]
    payload = payload.astype(jnp.float32)
    v = payload.shape[1]
    ws, wm, wx = widths if widths is not None else (v, v, v)
    for w in (ws, wm, wx):
        assert w in (0, v), f"plane width {w} must be 0 or the payload width {v}"
    valid = keys != empty_key(keys.dtype)
    vcol = valid[:, None]
    return AggState(
        keys=keys,
        count=valid.astype(jnp.int32),
        sum=jnp.where(vcol, payload, 0.0) if ws else jnp.zeros((n, 0), jnp.float32),
        min=jnp.where(vcol, payload, _F32_INF) if wm else jnp.zeros((n, 0), jnp.float32),
        max=jnp.where(vcol, payload, -_F32_INF) if wx else jnp.zeros((n, 0), jnp.float32),
    )


def concat_states(a: AggState, b: AggState) -> AggState:
    return jax.tree.map(lambda x, y: jnp.concatenate([x, y], axis=0), a, b)


def take(state: AggState, idx: jax.Array) -> AggState:
    """Row-gather a state (used to apply sort permutations)."""
    return jax.tree.map(lambda x: jnp.take(x, idx, axis=0), state)


def slice_rows(state: AggState, start, size: int) -> AggState:
    def f(x):
        return jax.lax.dynamic_slice_in_dim(x, start, size, axis=0)

    return jax.tree.map(f, state)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StreamEngineState:
    """The device-resident carry of the external-aggregation scan, as an
    explicit, reusable pytree.

    The fused pipeline (:mod:`repro.core.pipeline`) advances this state
    one input batch at a time; making the carry a first-class value is
    what lets a host loop feed the engine **super-batches** (chunks of
    the input stream) through a jitted ``absorb_chunk`` step, double-
    buffering host→device transfer behind compute, instead of requiring
    the whole input resident as one ``(T, B)`` stack.

    Field usage varies by run-generation policy (unused tables carry
    capacity 0 so the pytree structure stays uniform per policy):

    ``table``     early-agg ordered in-memory index (capacity M), or the
                  replacement-selection run partition (capacity M + 2B).
    ``table2``    replacement selection's next-run partition.
    ``frontier``  replacement selection's eviction frontier key (scalar).
    ``store``     the stacked run buffer — leading dims ``(R, C)``:
                  R page-aligned run slots of C rows each.
    ``lens``      ``(R,)`` int32 per-slot run lengths.
    ``cursor``    replacement selection's write cursor within the open
                  run slot.
    ``ridx``      the next free run slot.
    ``spilled``   rows spilled by run generation so far.
    ``absorbed``  valid input rows the engine has consumed so far (the
                  observation block's denominator).
    ``dups``      duplicate-key encounters observed while absorbing:
                  rows that combined into an existing group (absorbing
                  policies) or adjacent equal-key pairs within a sorted
                  batch (non-deduping ``traditional``).  ``dups /
                  absorbed`` is the running duplicate-rate estimate the
                  adaptive policy governor steers on.

    All counters are device scalars: absorbing a chunk performs **zero**
    host synchronizations, and the spill accounting becomes a
    :class:`DeviceSpillStats` only at the single finalize readback.  The
    observation block (``absorbed``, ``dups``, plus occupancy/``ridx``)
    is read back *explicitly* — and only every k-th chunk — by the
    adaptive streaming mode (:mod:`repro.core.adaptive`).
    """

    table: AggState
    table2: AggState
    frontier: jax.Array
    store: AggState
    lens: jax.Array
    cursor: jax.Array
    ridx: jax.Array
    spilled: jax.Array
    absorbed: jax.Array
    dups: jax.Array

    @property
    def run_slots(self) -> int:
        """R — preallocated run slots in the stacked store."""
        return self.lens.shape[-1]

    @property
    def slot_rows(self) -> int:
        """C — page-aligned capacity of one run slot."""
        return self.store.keys.shape[-1]

    @property
    def key_dtype(self) -> np.dtype:
        return np.dtype(self.store.keys.dtype)


# scalar leaves of StreamEngineState (everything else has a leading row or
# slot dim).  The mesh-sharded stream keeps these as (1,)-shaped per-shard
# arrays so every leaf can carry a sharded leading axis; these helpers
# convert at the shard_map boundary.
_SES_SCALARS = ("frontier", "cursor", "ridx", "spilled", "absorbed", "dups")


def expand_engine_scalars(es: StreamEngineState) -> StreamEngineState:
    """() scalar leaves → (1,) so each leaf has a shardable leading dim."""
    return dataclasses.replace(
        es, **{f: getattr(es, f)[None] for f in _SES_SCALARS}
    )


def squeeze_engine_scalars(es: StreamEngineState) -> StreamEngineState:
    """(1,) scalar leaves → () (inverse of :func:`expand_engine_scalars`)."""
    return dataclasses.replace(
        es, **{f: getattr(es, f)[0] for f in _SES_SCALARS}
    )


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """External-algorithm knobs, mirroring the paper's experiment parameters.

    memory_rows  M — the fixed "memory allocation" in rows.
    page_rows    P — unit of temporary-storage I/O in rows.
    fanin        F — traditional merge fan-in / hash partitioning fan-out.
    batch_rows     — input consumption granularity (paper §5 sorts small
                     input batches before probing the index).
    """

    memory_rows: int = 1 << 12
    page_rows: int = 1 << 8
    fanin: int = 8
    batch_rows: int = 1 << 10

    def __post_init__(self):
        assert self.page_rows <= self.memory_rows
        assert self.batch_rows <= self.memory_rows
        assert self.fanin >= 2


@dataclasses.dataclass
class SpillStats:
    """Exact temporary-storage accounting (rows, the paper's unit)."""

    rows_spilled_run_generation: int = 0
    rows_spilled_merge: int = 0
    runs_generated: int = 0
    merge_steps: int = 0
    merge_levels: int = 0
    pages_read: int = 0
    rows_emitted: int = 0  # rows streamed out of the wide merge's left edge
    index_overflowed: bool = False
    max_index_occupancy: int = 0
    # shuffle-volume accounting (mesh-sharded pipeline): valid rows that
    # entered the cross-shard all_to_all exchange, summed over shards.
    # 0 for every single-device plan.
    rows_exchanged: int = 0
    # eviction accounting (streaming service TTL/key retirement): state
    # rows retired from the live engine — nothing leaves the engine
    # without being counted here or emitted.  0 for every one-shot plan.
    rows_retired: int = 0
    # adaptive-streaming observation block (defaults for every fixed-policy
    # or one-shot plan, so device-vs-host stats parity is unaffected):
    # the engine's final duplicate-rate estimate, how often the governor
    # switched run-generation policy mid-stream, and how many decision
    # scalar readbacks the host paid for them (the O(stream/k) budget).
    duplicate_rate: float = 0.0
    policy_switches: int = 0
    readbacks_paid: int = 0
    # capacity-bounded exchange accounting (mesh-sharded pipeline; all 0
    # for single-device plans): the per-peer send quota the exchange ran
    # at (rows per shard->owner fragment), the fullest send segment
    # actually observed (max over peers and shards — `max_fill / quota`
    # is the sampled cuts' balance signal), and how many times a host
    # entry point had to retry the exchange at a wider quota.
    exchange_quota: int = 0
    exchange_max_fill: int = 0
    exchange_retries: int = 0

    @property
    def total_spill_rows(self) -> int:
        return self.rows_spilled_run_generation + self.rows_spilled_merge

    def as_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["total_spill_rows"] = self.total_spill_rows
        return d

    @classmethod
    def reduce_shards(cls, shards: "list[SpillStats]") -> "SpillStats":
        """Host twin of :meth:`DeviceSpillStats.cross_shard`: combine
        per-shard accounting into the global view — counters add, depth
        and peak-occupancy take the max, flags OR.  Used by tests to
        predict the sharded pipeline's stats from per-shard references."""
        assert shards, "reduce_shards needs at least one shard"
        return cls(
            rows_spilled_run_generation=sum(
                s.rows_spilled_run_generation for s in shards
            ),
            rows_spilled_merge=sum(s.rows_spilled_merge for s in shards),
            runs_generated=sum(s.runs_generated for s in shards),
            merge_steps=sum(s.merge_steps for s in shards),
            merge_levels=max(s.merge_levels for s in shards),
            pages_read=sum(s.pages_read for s in shards),
            rows_emitted=sum(s.rows_emitted for s in shards),
            index_overflowed=any(s.index_overflowed for s in shards),
            max_index_occupancy=max(s.max_index_occupancy for s in shards),
            rows_exchanged=sum(s.rows_exchanged for s in shards),
            rows_retired=sum(s.rows_retired for s in shards),
            duplicate_rate=max(s.duplicate_rate for s in shards),
            policy_switches=sum(s.policy_switches for s in shards),
            readbacks_paid=sum(s.readbacks_paid for s in shards),
            exchange_quota=max(s.exchange_quota for s in shards),
            exchange_max_fill=max(s.exchange_max_fill for s in shards),
            exchange_retries=sum(s.exchange_retries for s in shards),
        )


class MergeOverflowError(RuntimeError):
    """The wide merge dropped rows (``merge_dropped_rows`` tripped):
    either its index outgrew its capacity or the output overran its
    buffer.  Subclasses :class:`RuntimeError` so existing callers that
    catch broadly keep working; the streaming finalize/snapshot path
    catches *this* type specifically to auto-retry once at the next
    pow2 output capacity."""


class ExchangeOverflowError(RuntimeError):
    """The cross-shard exchange's per-peer send quota was too small for
    at least one send segment (``exchange_dropped`` tripped): rows would
    have been silently left behind on the sending shard.  The host entry
    points (one-shot mesh aggregate, streaming finalize/snapshot, the
    mesh merge join, and the distributed group-by) catch *this* type
    specifically to retry ONCE at the next pow2 quota — a second
    overflow propagates.  Carries the static ``quota`` the exchange ran
    at and the observed ``max_fill`` so the retry can size itself."""

    def __init__(self, message: str, *, quota: int, max_fill: int):
        super().__init__(message)
        self.quota = quota
        self.max_fill = max_fill


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DeviceSpillStats:
    """:class:`SpillStats` as a device pytree of int32/bool scalars.

    The device-resident pipeline (:mod:`repro.core.pipeline`) accumulates
    spill accounting in scan/while carries instead of host counters, so an
    entire run-generation + wide-merge program needs **zero** host syncs
    until the caller asks for numbers.  :meth:`finalize` performs that one
    readback and returns the plain host :class:`SpillStats`.

    Three device-side safety flags have no host twin — each means rows
    were (or would have been) silently lost, so ``finalize`` raises
    instead of returning corrupt accounting: ``run_buffer_overflowed``
    trips if run generation needed more run slots than the preallocated
    stacked buffer holds; ``merge_dropped_rows`` trips if the wide-merge
    index exceeded its hard capacity (resident > index_rows + page_rows)
    and live rows were trimmed; ``exchange_dropped`` trips if a
    cross-shard send segment exceeded the per-peer exchange quota
    (raised as the retryable :class:`ExchangeOverflowError`).
    """

    rows_spilled_run_generation: jax.Array
    rows_spilled_merge: jax.Array
    runs_generated: jax.Array
    merge_steps: jax.Array
    merge_levels: jax.Array
    pages_read: jax.Array
    rows_emitted: jax.Array
    index_overflowed: jax.Array
    max_index_occupancy: jax.Array
    run_buffer_overflowed: jax.Array
    merge_dropped_rows: jax.Array
    rows_exchanged: jax.Array
    rows_retired: jax.Array
    # capacity-bounded exchange block: exchange_dropped is the third
    # loud-failure flag (a send segment exceeded the per-peer quota);
    # finalize raises ExchangeOverflowError on it so host entry points
    # can retry once at a wider quota.
    exchange_dropped: jax.Array
    exchange_quota: jax.Array
    exchange_max_fill: jax.Array

    @classmethod
    def zeros(cls) -> "DeviceSpillStats":
        z = jnp.int32(0)
        f = jnp.bool_(False)
        return cls(z, z, z, z, z, z, z, f, z, f, f, z, z, f, z, z)

    def cross_shard(self, axis_name: str) -> "DeviceSpillStats":
        """Reduce per-shard accounting to the global view inside a
        ``shard_map`` region: row/step counters ``psum``, merge depth and
        peak index occupancy ``pmax``, and the loud-failure flags OR
        (``pmax`` over their int casts) — so a single shard's overflow
        trips :meth:`finalize` globally.  The result is replicated; the
        sharded pipeline's stats output therefore still needs only ONE
        host readback."""
        ps = lambda x: jax.lax.psum(x, axis_name)
        pm = lambda x: jax.lax.pmax(x, axis_name)
        por = lambda x: pm(x.astype(jnp.int32)) > 0
        return DeviceSpillStats(
            rows_spilled_run_generation=ps(self.rows_spilled_run_generation),
            rows_spilled_merge=ps(self.rows_spilled_merge),
            runs_generated=ps(self.runs_generated),
            merge_steps=ps(self.merge_steps),
            merge_levels=pm(self.merge_levels),
            pages_read=ps(self.pages_read),
            rows_emitted=ps(self.rows_emitted),
            index_overflowed=por(self.index_overflowed),
            max_index_occupancy=pm(self.max_index_occupancy),
            run_buffer_overflowed=por(self.run_buffer_overflowed),
            merge_dropped_rows=por(self.merge_dropped_rows),
            rows_exchanged=ps(self.rows_exchanged),
            rows_retired=ps(self.rows_retired),
            exchange_dropped=por(self.exchange_dropped),
            exchange_quota=pm(self.exchange_quota),
            exchange_max_fill=pm(self.exchange_max_fill),
        )

    def finalize(self, *, entry_point: str = "finalize") -> SpillStats:
        """One host readback → plain :class:`SpillStats` (the pipeline's
        only device→host synchronization point).

        ``entry_point`` names the merge program that produced these stats
        ("finalize" for the destructive drain, "snapshot" for the
        merge-on-read service query) so an overflow raised here tells the
        caller which knob to turn.
        """
        if bool(self.run_buffer_overflowed):
            raise RuntimeError(
                f"device run buffer overflowed its preallocated run slots "
                f"during {entry_point}; results would be missing rows "
                "(this is a bug in the slot bound — please report input "
                "sizes and ExecConfig)"
            )
        if bool(self.exchange_dropped):
            raise ExchangeOverflowError(
                f"the cross-shard exchange during {entry_point} overflowed "
                f"its per-peer send quota ({int(self.exchange_max_fill)} "
                f"rows in the fullest segment vs quota "
                f"{int(self.exchange_quota)}); rows would have been left "
                "behind — pass a larger exchange_quota (host entry points "
                "retry once at the next pow2 automatically)",
                quota=int(self.exchange_quota),
                max_fill=int(self.exchange_max_fill),
            )
        if bool(self.merge_dropped_rows):
            if entry_point == "snapshot":
                hint = (
                    "raise output_rows (the snapshot output capacity) or "
                    "pass a larger output_estimate (more pre-merge levels)"
                )
            else:
                hint = (
                    "pass a larger output_estimate (more pre-merge levels) "
                    "or raise index_rows"
                )
            raise MergeOverflowError(
                f"the wide merge during {entry_point} dropped rows: either "
                "its index overflowed its capacity (max resident "
                f"{int(self.max_index_occupancy)} rows) or the output "
                f"overran its buffer — {hint}"
            )
        return SpillStats(
            rows_spilled_run_generation=int(self.rows_spilled_run_generation),
            rows_spilled_merge=int(self.rows_spilled_merge),
            runs_generated=int(self.runs_generated),
            merge_steps=int(self.merge_steps),
            merge_levels=int(self.merge_levels),
            pages_read=int(self.pages_read),
            rows_emitted=int(self.rows_emitted),
            index_overflowed=bool(self.index_overflowed),
            max_index_occupancy=int(self.max_index_occupancy),
            rows_exchanged=int(self.rows_exchanged),
            rows_retired=int(self.rows_retired),
            exchange_quota=int(self.exchange_quota),
            exchange_max_fill=int(self.exchange_max_fill),
        )
