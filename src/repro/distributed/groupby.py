"""Distributed duplicate removal / grouping / aggregation (shard_map).

The cluster-scale form of the paper's operator, using its own §2.1
observation that *sorting and partitioning are the same physical
property*:

  1. local early aggregation (§3): each device absorbs its shard's
     duplicates with the in-memory ordered index — this is the paper's
     intro note that best-effort aggregation **before** re-partitioning
     reduces the shuffle volume;
  2. key-range exchange: the key space splits into `world` contiguous
     ranges; because local outputs are sorted, the send buffer is built
     with two searchsorted cuts, and the all_to_all is the paper's
     "partitioning enforced together with sorting";
  3. local wide merge (§4): each device merges the `world` sorted
     fragments it received — output is locally sorted, and globally
     sorted by (range owner, key): a distributed ORDER BY for free.

The exchange core (:func:`exchange_sorted_fragments`) is shared with the
mesh-sharded device-resident pipeline (:mod:`repro.core.pipeline`), which
runs full external run generation per shard before the same key-range
all_to_all.

Overflow is LOUD: every place a fixed-capacity buffer can cut live rows —
the local-aggregation trim to ``capacity``, the per-peer send quota, and
the post-merge trim back to ``capacity`` — returns a device flag instead
of silently dropping, and :func:`make_distributed_groupby` raises on it
(matching the PR 3 wide merge's ``merge_dropped_rows`` contract).

``sparse_embedding_grad`` applies the same pipeline to embedding-table
gradients: (token, grad) pairs dedup-aggregate locally, then only unique
rows travel.
"""
from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import merge as merge_mod
from repro.core import sorted_ops
from repro.core.types import (
    AggState, empty_key, match_vma, max_key, rows_to_state,
)

_log = logging.getLogger(__name__)

# default merge page for the post-exchange fragment merge when the caller
# has no ExecConfig to thread through (the distributed group-by front door)
_DEFAULT_EXCHANGE_PAGE = 256


def _pow2_ceil(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def default_exchange_quota(capacity: int, world: int, *, headroom: int = 2,
                           floor: int = 64) -> int:
    """Per-peer send quota for a capacity-bounded exchange: the expected
    rows per owner under the sampled cuts (``capacity / world``) times a
    pow2 ``headroom`` for sampling error, never above ``pow2(capacity)``
    (a quota >= capacity is statically lossless, so the retry ladder
    terminates there).  This is what keeps the exchange's receive buffer
    at ``world * quota ~= headroom * capacity`` rows — constant in world
    at fixed rows-per-shard — instead of the old ``world * capacity``.

    ``floor`` guards the SMALL end: when expected rows per owner is a
    handful, sample-quantile noise is additive, not proportional (a
    9-row segment against an expected 4 is routine at 64 samples/shard),
    so multiplicative headroom alone would trip the retry ladder — and a
    retry re-dispatches the whole sharded program.  The floor costs at
    most ``world * floor`` receive rows, noise at the scale where
    ``headroom * expected`` dominates anyway."""
    expected = -(-capacity // world)
    want = max(headroom * expected, floor)
    return max(1, min(_pow2_ceil(want), _pow2_ceil(capacity)))


def exchange_page_rows(quota: int, page_rows: int | None = None) -> int:
    """Merge page size for the fragment merge: the caller's page size,
    shrunk so it divides ``quota`` exactly (a clamped last page would
    double-read rows through :func:`repro.core.merge._page_of`).  Quotas
    from :func:`default_exchange_quota` are pow2, so any pow2 page size
    passes through unchanged."""
    p = max(1, min(page_rows or _DEFAULT_EXCHANGE_PAGE, quota))
    return math.gcd(quota, p)


def exchange_footprint_rows(world: int, quota: int,
                            page_rows: int | None = None) -> int:
    """Analytic per-shard resident footprint of one exchange + fragment
    merge, in rows: the receive buffer (``world * quota``), the wide
    merge's working set (index tile ``world * P`` + one incoming page +
    merge headroom = ``(world + 2) * P``), and the merged output buffer
    (``world * quota``).  O(quota_bound + merge_page); the old scheme was
    ``world * capacity`` on the wire alone."""
    p = exchange_page_rows(quota, page_rows)
    return 2 * world * quota + (world + 2) * p


def _range_of(keys, world):
    """Owner of each key under contiguous range partitioning of the key
    dtype's domain (uint32 or uint64)."""
    bits = np.dtype(keys.dtype).itemsize * 8
    span = keys.dtype.type((1 << bits) // world)
    return jnp.minimum(keys // span, world - 1).astype(jnp.int32)


def _local_group_sorted(keys, payload, capacity):
    """Local early aggregation trimmed to ``capacity`` — returns the
    trimmed state plus the live-rows-cut flag (more unique keys in this
    shard's slice than ``capacity`` is row loss, the same as the other
    two overflow sites)."""
    st = sorted_ops.sorted_groupby(keys, payload)
    return merge_mod.trim_to_capacity(st, capacity)


def _fill_like(x):
    if x.dtype in (jnp.uint32, jnp.uint64):
        return empty_key(x.dtype)
    return jnp.zeros((), x.dtype)


def _sample_local_keys(st: AggState, nsamp: int):
    """``nsamp`` evenly spaced keys from a sorted local state's valid
    prefix (all-EMPTY shards contribute EMPTY samples, which rank last)."""
    occ = jnp.maximum(st.occupancy(), 1)
    pos = jnp.minimum((jnp.arange(nsamp) * occ) // nsamp, st.capacity - 1)
    return jnp.take(st.keys, pos)


def strictify_cuts(cuts):
    """Make sampled inner cut values strictly increasing (and clamped to
    the key domain, below the EMPTY sentinel).  Under heavy skew — a hot
    key holding most rows, or fewer distinct keys than shards — the raw
    sample quantiles repeat, which leaves owner ranges empty and piles
    several ranges' keys onto one peer.  The recurrence

        c'_i = min(max(c_i, min(c'_{i-1}, top - 1) + 1), top)

    (a ``lax.scan`` over the ``world - 1`` scalars; the inner ``min``
    saturates instead of overflowing unsigned arithmetic at ``top``)
    bumps each duplicate one key above its predecessor, so cuts stay
    distinct wherever the domain allows and collapse onto ``top`` only
    when it doesn't — identical and deterministic on every shard."""
    kd = cuts.dtype
    top = jnp.asarray(max_key(kd), kd)
    one = jnp.asarray(1, kd)

    def step(carry, ci):
        prev, started = carry
        lo = jnp.where(started, jnp.minimum(prev, top - one) + one,
                       jnp.zeros((), kd))
        nxt = jnp.minimum(jnp.maximum(ci, lo), top)
        return (nxt, jnp.bool_(True)), nxt

    (_, _), out = jax.lax.scan(
        step, (match_vma(jnp.zeros((), kd), cuts), jnp.bool_(False)),
        jnp.minimum(cuts, top),
    )
    return out


def sample_range_cuts(states, axis: str, world: int, *, nsamp: int = 64):
    """Sampled key-range partition edges over one or MORE sorted local
    states (sample-sort style).  Each shard contributes a sorted sample
    per state; the gathered sample's quantiles give identical,
    data-driven inner edges — shape ``(world - 1,)`` — on every shard.
    Passing both sides of a join here partitions both relations by the
    SAME cuts, which is what makes the post-exchange per-owner join a
    purely local merge join.  Edges are deduped/clamped
    (:func:`strictify_cuts`) so skewed samples cannot produce empty
    owner ranges from repeated quantile values."""
    sample = jnp.concatenate([_sample_local_keys(st, nsamp) for st in states])
    all_samp = jnp.sort(jax.lax.all_gather(sample, axis).reshape(-1))
    eidx = (jnp.arange(1, world) * all_samp.shape[0]) // world
    return strictify_cuts(jnp.take(all_samp, eidx))


def exchange_sorted_fragments(st: AggState, axis: str, world: int, *, quota: int,
                              nsamp: int = 64, inner_cuts=None):
    """Key-range ``all_to_all`` of a *sorted, duplicate-free* local state.

    Range boundaries are SAMPLED (sample-sort style): fixed uniform ranges
    collapse under key skew, so each shard contributes a sorted sample of
    its keys; the gathered sample's quantiles give identical, data-driven
    edges on every shard.  Sorted local output ⇒ the per-peer send
    segments are two searchsorted cuts, "partitioning enforced together
    with sorting" (§2.1).  Each peer receives a sorted, EMPTY-padded
    fragment of exactly ``quota`` rows.

    ``inner_cuts`` overrides the sampled edges with precomputed ones
    (shape ``(world - 1,)``, identical on every shard — see
    :func:`sample_range_cuts`): the sharded merge join exchanges BOTH
    sides under one shared cut vector so the two partitionings align.

    Returns ``(recv, rows_sent, send_dropped, max_fill)``:

    * ``recv`` — AggState of ``world * quota`` rows; rows
      ``[i*quota, (i+1)*quota)`` are peer ``i``'s sorted fragment, and
      fragment key ranges ascend with ``i`` (global order = (owner, key));
    * ``rows_sent`` — valid rows this shard put on the wire (shuffle
      volume; ``psum`` it for the global count);
    * ``send_dropped`` — True iff some send segment exceeded ``quota``
      and live rows were cut.  Callers must surface this loudly; with
      ``quota >= st.capacity`` it is statically impossible.
    * ``max_fill`` — this shard's fullest send segment in rows (``pmax``
      it for the global view); ``max_fill / quota`` is how close the
      sampled cuts came to overflowing the capacity bound.
    """
    capacity = st.capacity
    inner = (sample_range_cuts((st,), axis, world, nsamp=nsamp)
             if inner_cuts is None else inner_cuts)
    cuts = jnp.searchsorted(st.keys, inner, side="left").astype(jnp.int32)
    ends = jnp.concatenate([cuts, jnp.asarray([capacity], jnp.int32)])
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), cuts])
    # segment i = rows [starts[i], ends[i]) of the sorted local state; the
    # EMPTY tail beyond occupancy lands in the last segment and pads it.
    seg_valid = jnp.minimum(ends, st.occupancy()) - jnp.minimum(
        starts, st.occupancy()
    )
    rows_sent = jnp.sum(seg_valid, dtype=jnp.int32)
    max_fill = jnp.max(seg_valid).astype(jnp.int32)
    send_dropped = jnp.any(seg_valid > quota)
    idx = starts[:, None] + jnp.arange(quota, dtype=jnp.int32)[None, :]
    valid_send = idx < ends[:, None]
    idx = jnp.minimum(idx, capacity - 1)

    def gather_rows(x):
        g = jnp.take(x, idx.reshape(-1), axis=0)
        mask = valid_send.reshape(-1)
        return jnp.where(mask.reshape((-1,) + (1,) * (g.ndim - 1)),
                         g, _fill_like(x))

    send = jax.tree.map(gather_rows, st)
    recv = jax.tree.map(
        lambda x: jax.lax.all_to_all(
            x.reshape((world, quota) + x.shape[1:]), axis, 0, 0,
            tiled=False,
        ).reshape((world * quota,) + x.shape[1:]),
        send,
    )
    return recv, rows_sent, send_dropped, max_fill


class ExchangeInfo(NamedTuple):
    """Accounting from one :func:`exchange_and_merge` (device scalars
    except the static ``quota``), already cross-shard reduced where
    noted by the caller's contract."""

    rows_sent: jax.Array  # valid rows this shard put on the wire
    send_dropped: jax.Array  # a send segment exceeded `quota` (retryable)
    max_fill: jax.Array  # fullest send segment observed on this shard
    merge_dropped: jax.Array  # fragment merge lost rows (statically ~impossible)
    quota: int  # the static per-peer quota the exchange ran at


def exchange_and_merge(st: AggState, axis: str, world: int, *,
                       backend: str = "auto", quota: int | None = None,
                       page_rows: int | None = None):
    """Key-range exchange + per-owner merge of a sorted, duplicate-free
    local state — the shared tail of the mesh-sharded pipelines: the
    one-shot finalize, the streamed finalize, AND the service's
    merge-on-read snapshot all run this same program over their
    per-shard merge output (the snapshot feeds it a fresh buffer, so
    exchanging never perturbs the live per-shard engine states).

    The per-peer quota is CAPACITY-BOUNDED (:func:`default_exchange_quota`
    unless overridden): expected rows per owner under the sampled cuts
    times a pow2 headroom, so the wire + merge footprint is
    O(quota_bound + merge_page) per shard instead of the old
    ``world * capacity``.  A segment over quota sets
    ``info.send_dropped`` — host entry points surface it as
    :class:`repro.core.types.ExchangeOverflowError` and retry once at
    the next pow2 quota.

    Returns ``(merged, info)``: the merged state at capacity
    ``world * quota`` and an :class:`ExchangeInfo`."""
    if quota is None:
        quota = default_exchange_quota(st.capacity, world)
    recv, rows_sent, send_dropped, max_fill = exchange_sorted_fragments(
        st, axis, world, quota=quota
    )
    merged, merge_dropped = merge_received_fragments(
        recv, world, quota, backend=backend, page_rows=page_rows
    )
    return merged, ExchangeInfo(rows_sent, send_dropped, max_fill,
                                merge_dropped, quota)


def merge_received_fragments(recv: AggState, world: int, quota: int, *,
                             backend: str = "auto",
                             page_rows: int | None = None):
    """Local PAGE-STREAMED wide merge (§4) of the ``world`` sorted
    fragments an :func:`exchange_sorted_fragments` shard received: the
    fragments are exactly §4 runs (sorted, duplicate-free,
    EMPTY-padded), so they stream page-wise through
    :func:`repro.core.merge.wide_merge_device` — resident working set
    ``(world + 2) * page`` rows instead of the former full-width
    ``world * quota`` merge tree.  The index bound is exact: the merge
    frontier is at least every read page's low key, so at most one page
    per fragment is ever resident (``index_rows = world * page``).

    Returns ``(merged, dropped)``: the merged state at capacity
    ``world * quota`` (trim + loud-overflow is the caller's policy, see
    :func:`repro.core.merge.trim_to_capacity`) and the wide merge's
    hard row-loss flag, statically impossible here because the output
    buffer holds every input row — surfaced defensively anyway."""
    p = exchange_page_rows(quota, page_rows)
    store, lens = merge_mod.fragments_to_store(recv, world, quota)
    merged, _out_cur, _pages, _max_occ, _overflow, dropped = (
        merge_mod.wide_merge_device(
            store, lens, page_rows=p, index_rows=world * p,
            out_capacity=world * quota, backend=backend,
        )
    )
    return merged, dropped


def sharded_merge_join_local(a: AggState, b: AggState, axis: str, world: int,
                             *, how: str = "inner", backend: str = "xla",
                             nsamp: int = 64, quota_a: int | None = None,
                             quota_b: int | None = None,
                             page_rows: int | None = None):
    """Per-shard body of the mesh-sharded merge join (call inside
    ``shard_map``; both inputs are this shard's sorted, duplicate-free,
    EMPTY-tailed slices of globally sorted relations).

    Sharded join = the existing key-range machinery, run twice under ONE
    shared cut vector: sample BOTH sides jointly
    (:func:`sample_range_cuts`), exchange each side by those cuts
    (:func:`exchange_sorted_fragments`), per-owner merge of each side's
    received fragments — and then the join is purely local, because
    owner ``i`` now holds *all* rows of *both* relations in key range
    ``i``.  No global sort anywhere: established order survives the
    shuffle, exactly as in the aggregation exchange.

    Both exchanges are capacity-bounded (:func:`default_exchange_quota`
    per side unless ``quota_a``/``quota_b`` override) and both fragment
    merges page-stream (:func:`merge_received_fragments`), so the join's
    shuffle footprint follows the same O(quota_bound + merge_page)
    discipline as the aggregation exchange.

    Returns ``(left, right_or_left, rows_sent, send_dropped, dropped,
    max_fill)``: the local join output trimmed back to this shard's
    slice of the global output capacity (``|a|`` rows — loud flag if a
    skewed owner's matches exceed its slice), the aligned right side
    (inner; the left state again for semi/anti so the shape structure is
    static), the global shuffle volume (both sides, psum'd), the pmax'd
    RETRYABLE quota-overflow flag (either side's send segment over its
    quota — the mesh join front door retries once at wider quotas), the
    pmax'd non-retryable row-loss flag (merge/trim), and the pmax'd
    fullest send segment across both sides.
    """
    from repro.core.merge_join import merge_join

    qa = default_exchange_quota(a.capacity, world) if quota_a is None else quota_a
    qb = default_exchange_quota(b.capacity, world) if quota_b is None else quota_b
    cuts = sample_range_cuts((a, b), axis, world, nsamp=nsamp)
    recv_a, sent_a, drop_a, fill_a = exchange_sorted_fragments(
        a, axis, world, quota=qa, inner_cuts=cuts)
    recv_b, sent_b, drop_b, fill_b = exchange_sorted_fragments(
        b, axis, world, quota=qb, inner_cuts=cuts)
    ma, mdrop_a = merge_received_fragments(
        recv_a, world, qa, backend=backend, page_rows=page_rows)
    mb, mdrop_b = merge_received_fragments(
        recv_b, world, qb, backend=backend, page_rows=page_rows)
    left, right = merge_join(ma, mb, how=how, backend=backend)
    left, trim_l = merge_mod.trim_to_capacity(left, a.capacity)
    if right is not None:
        right, trim_r = merge_mod.trim_to_capacity(right, a.capacity)
    else:
        right, trim_r = left, jnp.bool_(False)
    rows_sent = jax.lax.psum(sent_a + sent_b, axis)
    send_dropped = jax.lax.pmax(
        (drop_a | drop_b).astype(jnp.int32), axis) > 0
    dropped = jax.lax.pmax(
        (mdrop_a | mdrop_b | trim_l | trim_r).astype(jnp.int32), axis) > 0
    max_fill = jax.lax.pmax(jnp.maximum(fill_a, fill_b), axis)
    return left, right, rows_sent, send_dropped, dropped, max_fill


def make_distributed_groupby(mesh, axis: str = "data", *, capacity: int,
                             on_overflow: str = "raise",
                             exchange_quota: int | None = None,
                             page_rows: int | None = None):
    """Returns fn(keys (n_loc,), payload (n_loc, V)) → AggState per device,
    covering this device's key range (globally sorted across devices).

    The exchange runs at a capacity-bounded per-peer quota
    (:func:`default_exchange_quota` unless ``exchange_quota`` overrides)
    and the fragment merge page-streams, so per-shard memory is
    O(quota_bound + merge_page), not O(world × capacity).

    ``on_overflow`` controls what happens when fixed capacities would cut
    live rows: ``"raise"`` (default) reads the flags back after the
    exchange; a send segment over quota RETRIES ONCE at the next pow2
    quota with a loud log (the PR 8 retry-once pattern), then raises —
    any other loss site (local trim, post-merge trim) raises
    immediately; ``"flag"`` returns ``(state, dropped)`` with the
    combined device flag for callers embedding the exchange in a larger
    jitted program (NO retry: the flag read would cost the readback the
    mode exists to avoid).
    """
    if on_overflow not in ("raise", "flag"):
        raise ValueError(f"unknown on_overflow {on_overflow!r}: raise|flag")
    world = mesh.shape[axis]

    def local_fn(quota, keys, payload):
        keys = keys.reshape(-1)
        payload = payload.reshape(keys.shape[0], -1)
        # 1. local early aggregation — the paper's §3 on-device
        st, local_dropped = _local_group_sorted(keys, payload, capacity)
        # 2. capacity-bounded sampled key-range exchange (shared with the
        #    sharded pipeline)
        recv, _sent, send_dropped, _fill = exchange_sorted_fragments(
            st, axis, world, quota=quota
        )
        # 3. local page-streamed wide merge of the received fragments
        merged, merge_dropped = merge_received_fragments(
            recv, world, quota, page_rows=page_rows
        )
        merged, recv_dropped = merge_mod.trim_to_capacity(merged, capacity)
        pflag = lambda f: jax.lax.pmax(f.astype(jnp.int32), axis) > 0
        return merged, pflag(send_dropped), pflag(
            local_dropped | merge_dropped | recv_dropped
        )

    @functools.cache
    def sharded(quota):
        return jax.jit(jax.shard_map(
            functools.partial(local_fn, quota), mesh=mesh,
            in_specs=(P(axis), P(axis, None)),
            out_specs=(
                AggState(
                    keys=P(axis), count=P(axis), sum=P(axis, None),
                    min=P(axis, None), max=P(axis, None),
                ),
                P(),
                P(),
            ),
        ))

    q0 = (default_exchange_quota(capacity, world) if exchange_quota is None
          else exchange_quota)
    q_max = _pow2_ceil(capacity)

    def run(keys, payload):
        state, send_dropped, dropped = sharded(q0)(keys, payload)
        if on_overflow == "flag":
            return state, send_dropped | dropped
        # one replicated-scalar readback, eager callers only
        if bool(send_dropped) and q0 < q_max:
            quota2 = min(_pow2_ceil(q0 + 1), q_max)
            _log.warning(
                "distributed group-by exchange overflowed its per-peer "
                "quota=%d; retrying once at quota=%d", q0, quota2,
            )
            state, send_dropped, dropped = sharded(quota2)(keys, payload)
        if bool(send_dropped) or bool(dropped):
            raise RuntimeError(
                "distributed group-by dropped rows: a send segment "
                "exceeded the per-peer exchange quota even after one "
                "retry, or received fragments exceeded "
                f"capacity={capacity} on at least one shard — raise "
                "`capacity` (results would be missing keys/counts)"
            )
        return state

    return run


def sparse_embedding_grad(tokens, grads, vocab: int, mesh, axis="data",
                          capacity: int | None = None,
                          on_overflow: str = "raise"):
    """Aggregate (token, grad_row) pairs across devices sort-based, then
    scatter into the dense (V, D) gradient.  Wire volume: unique rows per
    range shard instead of the full dense table all-reduce.

    The default ``on_overflow="raise"`` reads one replicated flag back
    per call and raises on row loss — eager (host-driver) use only.
    Inside ``jit``/``grad`` pass ``on_overflow="flag"``: the result is
    ``(state, dropped)`` with the device flag for the caller to surface.
    """
    d = grads.shape[-1]
    capacity = capacity or tokens.size
    gb = make_distributed_groupby(mesh, axis, capacity=capacity,
                                  on_overflow=on_overflow)
    return gb(tokens.reshape(-1).astype(jnp.uint32), grads.reshape(-1, d))
