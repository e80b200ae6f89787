"""Engine-level aggregation service: persistent ingest + merge-on-read.

:class:`AggregationService` owns one long-lived
:class:`~repro.core.pipeline.StreamingAggregator` and turns its staged
absorb protocol into a serving loop:

* :meth:`ingest` — double-buffered by default: the chunk is staged
  (async host→device transfer) and the *previous* chunk's absorb is
  dispatched, so transfer overlaps compute exactly as in
  :func:`~repro.core.pipeline.aggregate_device_stream`.
* :meth:`snapshot` — merge-on-read: the engine's statically planned
  drain + pre-merge + wide merge runs as a NON-donating program into a
  fresh output buffer.  The live engine state is byte-for-byte
  untouched, so ingest continues afterwards; repeated snapshots hit a
  pow2-bucketed set of compiled programs.
* :meth:`retire_below` — watermark eviction: resident rows with keys
  below a threshold are retired from the run store and tables, counted
  into ``SpillStats.rows_retired`` (surfaced by every later snapshot).
* :meth:`close` — the destructive finalize of the plain streaming
  protocol, ending the session.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.pipeline import StreamingAggregator
from repro.core.trace import span
from repro.core.types import (
    AggState,
    DeviceSpillStats,
    ExecConfig,
    SpillStats,
)
from repro.service.metrics import ServiceMetrics


class AggregationService:
    """A persistent device-resident aggregation engine behind a serving
    protocol: ingest packed-key micro-batches, answer snapshot queries
    mid-flight, retire expired key ranges, finalize on close.

    Constructor arguments mirror
    :class:`~repro.core.pipeline.StreamingAggregator` (``mesh=`` keeps a
    per-shard engine under ``shard_map``); ``overlap=False`` disables
    the ingest double buffer (each chunk is absorbed synchronously with
    its staging — useful for latency-vs-throughput comparisons, see
    ``benchmarks/bench_service.py``).
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
        overlap: bool = True,
        governor=None,
    ):
        self._agg = StreamingAggregator(
            cfg, policy=policy, key_dtype=key_dtype, width=width,
            widths=widths, backend=backend, index_rows=index_rows,
            output_estimate=output_estimate, output_rows=output_rows,
            mesh=mesh, mesh_axis=mesh_axis, governor=governor,
        )
        self.overlap = bool(overlap)
        self.metrics = ServiceMetrics()
        self._pending = None  # staged-but-not-absorbed chunk (overlap)
        self._closed = False

    # -- introspection ---------------------------------------------------

    @property
    def cfg(self) -> ExecConfig:
        return self._agg.cfg

    @property
    def policy(self) -> str:
        return self._agg.policy

    @property
    def current_policy(self) -> str:
        """The run-generation policy the next ingest will use — under
        ``policy="adaptive"`` this is the governor's current arm."""
        return self._agg.arm

    @property
    def key_dtype(self) -> np.dtype:
        return self._agg.key_dtype

    @property
    def rows_ingested(self) -> int:
        return self.metrics.rows_ingested

    @property
    def closed(self) -> bool:
        return self._closed

    def _check_open(self):
        if self._closed:
            raise RuntimeError("AggregationService is closed")

    # -- ingest ----------------------------------------------------------

    def ingest(self, keys, payload=None) -> None:
        """Absorb one micro-batch (host NumPy keys + optional payload).

        Zero host syncs: the chunk is staged with an explicit async
        ``device_put`` and (with ``overlap``) the previous chunk's
        absorb is dispatched behind it, hiding the transfer."""
        self._check_open()
        staged = self._agg.stage(keys, payload)
        if staged is None:
            return
        if self.overlap:
            pending, self._pending = self._pending, staged
            if pending is not None:
                self._agg.absorb_staged(pending)
        else:
            self._agg.absorb_staged(staged)
        self.metrics.observe_ingest(staged.rows)

    def flush(self) -> None:
        """Dispatch the absorb of any chunk still held by the double
        buffer (query/evict/close boundaries call this implicitly so
        answers always cover every ingested row)."""
        if self._pending is not None:
            pending, self._pending = self._pending, None
            self._agg.absorb_staged(pending)

    # -- merge-on-read ---------------------------------------------------

    def snapshot_device(self) -> tuple[AggState, DeviceSpillStats]:
        """:meth:`snapshot` without the host sync: device values only,
        no latency metric (compose with other device programs)."""
        self._check_open()
        self.flush()
        return self._agg.snapshot_device()

    def snapshot(self) -> tuple[AggState, SpillStats]:
        """Answer the current aggregate without consuming the engine.

        Returns ``(state, stats)`` like a finalize — keys sorted,
        EMPTY-padded tail, ``stats.rows_retired`` carrying the eviction
        account — but the live engine state is untouched and ingest
        continues.  The blocking readback is timed into the service's
        snapshot latency quantiles."""
        self._check_open()
        self.flush()
        t0 = time.perf_counter()
        # the aggregator-level snapshot retries ONCE at the next pow2
        # out_capacity if the wide merge overflows (loud log), so a
        # slightly-low output_estimate degrades to a slow snapshot
        # instead of a dead session
        state, stats = self._agg.snapshot()
        jax.block_until_ready(state.keys)
        seconds = time.perf_counter() - t0
        with span("repro.readback", what="occupancy"):
            groups = int(state.occupancy())
        self.metrics.observe_snapshot(stats, groups=groups, seconds=seconds)
        self.metrics.observe_policy(
            self._agg.policy_events, readbacks=self._agg.readbacks_paid,
            current=self._agg.arm)
        return state, stats

    # -- eviction --------------------------------------------------------

    def retire_below(self, threshold) -> int:
        """Retire every resident row with key ``< threshold`` (watermark
        TTL).  One scalar host sync; returns the cumulative retired-row
        count, which every later snapshot also reports as
        ``stats.rows_retired``."""
        self._check_open()
        self.flush()
        total = self._agg.evict_below(threshold)
        self.metrics.rows_retired = total
        return total

    # -- teardown --------------------------------------------------------

    def close(self) -> tuple[AggState, SpillStats]:
        """Final destructive drain (the plain streaming ``finalize``);
        the service accepts no further ingest."""
        self._check_open()
        self.flush()
        self._closed = True
        out = self._agg.finalize()
        self.metrics.observe_policy(
            self._agg.policy_events, readbacks=self._agg.readbacks_paid,
            current=self._agg.arm)
        return out
