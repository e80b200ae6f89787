"""Schema-level sessions: composite keys, declarative aggregates, TTL.

:class:`AggregationSession` is the :func:`repro.aggregate` rendering of
the service — batches are column mappings packed through a
:class:`~repro.core.schema.KeySpec`, snapshots come back as
:class:`~repro.core.schema.AggResult`, and sessionization-style expiry
is keyed on the **watermark column**: the major (most significant)
column of the composite key.  Because the KeySpec packs major-first,
"watermark below the cutoff" is ONE contiguous packed-key range
``[0, cutoff << shift)`` — TTL expiry reduces to the engine's sorted
prefix retirement, no per-row predicate anywhere.
"""
from __future__ import annotations

import numpy as np

from repro.core import dispatch
from repro.core import schema as schema_mod
from repro.core.schema import AggResult, AggSpec, KeySpec
from repro.core.trace import span
from repro.core.types import ExecConfig, SpillStats, empty_state, key_dtype_context
from repro.service.metrics import ServiceMetrics
from repro.service.service import AggregationService


class AggregationSession:
    """A long-lived grouped-aggregation session over column batches.

    ::

        sess = repro.serve_aggregate(
            by=KeySpec.of(minute=22, user=10), values="amount",
            aggs=("count", "sum"), watermark="minute")
        for batch in source:
            sess.ingest(batch)           # zero-readback ingest
            if query_due:
                res = sess.snapshot()    # merge-on-read AggResult
        sess.expire_below(minute=now - ttl)   # retire closed sessions
        final = sess.close()

    ``watermark`` names the major key column used by
    :meth:`expire_below`; it must be the FIRST KeySpec column so expiry
    is a single packed-key range.  The payload width is fixed by the
    first ingested batch (the engine's plane widths are static).
    """

    def __init__(
        self,
        *,
        by: KeySpec,
        values: str | None = None,
        aggs=("count",),
        watermark: str | None = None,
        cfg: ExecConfig | None = None,
        policy: str = "rs",
        backend: str = "auto",
        index_rows: int | None = None,
        output_estimate: int | None = None,
        output_rows: int | None = None,
        mesh=None,
        mesh_axis: str | None = None,
        overlap: bool = True,
        governor=None,
    ):
        if not isinstance(aggs, AggSpec):
            aggs = AggSpec(aggs) if isinstance(aggs, str) else AggSpec(*aggs)
        if values is not None and not isinstance(values, str):
            raise TypeError(
                "session batches are column mappings: values must name a "
                f"column (a str), got {type(values).__name__}"
            )
        if values is None and aggs.needs_payload():
            raise ValueError(
                f"aggregates {aggs.names} need a payload; pass "
                "values=<column name>"
            )
        if watermark is not None and watermark != by.names[0]:
            raise ValueError(
                f"watermark column {watermark!r} must be the major (first) "
                f"key column {by.names[0]!r}: the KeySpec packs major-first, "
                "so only the major column maps TTL expiry onto one "
                "contiguous packed-key range"
            )
        self.by = by
        self.aggs = aggs
        self.values = values
        self.watermark = watermark
        self.cfg = cfg or ExecConfig()
        self._engine_kw = dict(
            policy=policy, backend=backend, index_rows=index_rows,
            output_estimate=output_estimate, output_rows=output_rows,
            mesh=mesh, mesh_axis=mesh_axis, overlap=overlap,
            governor=governor,
        )
        self._svc: AggregationService | None = None
        self._closed = False

    # -- plumbing --------------------------------------------------------

    def _prep(self, batch) -> tuple[np.ndarray, np.ndarray | None]:
        packed = self.by.pack(batch)
        if self.values is None:
            return packed, None
        if self.values not in batch:
            raise KeyError(
                f"values column {self.values!r} missing from batch")
        vals = np.asarray(batch[self.values], dtype=np.float32)
        if vals.ndim == 1:
            vals = vals[:, None]
        if len(vals) != len(packed):
            raise ValueError(
                f"values column {self.values!r} has {len(vals)} rows, key "
                f"columns have {len(packed)}"
            )
        return packed, vals

    def _ensure_service(self, payload_width: int) -> AggregationService:
        if self._svc is None:
            self._svc = AggregationService(
                self.cfg, key_dtype=self.by.key_dtype, width=payload_width,
                widths=self.aggs.plane_widths(payload_width),
                **self._engine_kw,
            )
        return self._svc

    def _result(self, state, stats: SpillStats) -> AggResult:
        plan = schema_mod._plan(
            self.metrics.rows_ingested, self.cfg,
            self._engine_kw["output_estimate"])
        plan.update(
            algorithm="insort", pipeline="device", streamed=True,
            service=True,
            backend=(self._svc._agg.backend if self._svc is not None
                     else dispatch.resolve_backend_name(
                         self._engine_kw["backend"])),
            snapshots=self.metrics.snapshots_taken,
        )
        return AggResult(state=state, stats=stats, by=self.by,
                         aggs=self.aggs, plan=plan)

    @property
    def metrics(self) -> ServiceMetrics:
        return (self._svc.metrics if self._svc is not None
                else ServiceMetrics())

    @property
    def closed(self) -> bool:
        return self._closed

    def _check_open(self):
        if self._closed:
            raise RuntimeError("AggregationSession is closed")

    # -- the session protocol --------------------------------------------

    def ingest(self, batch) -> None:
        """Absorb one column-batch mapping (key columns named by the
        KeySpec, plus the values column when requested)."""
        self._check_open()
        with span("repro.ingest", chunk=self.metrics.chunks_ingested):
            packed, vals = self._prep(batch)
            if not len(packed):
                return
            svc = self._ensure_service(0 if vals is None else vals.shape[1])
            svc.ingest(packed, vals)

    def snapshot(self) -> AggResult:
        """Merge-on-read snapshot as a sorted :class:`AggResult`.

        Non-destructive — ingest continues afterwards.  A session that
        never ingested (or whose rows were all retired) answers a valid
        EMPTY relation, not an error: the result keeps the declared
        key columns and aggregate planes at width 0 rows."""
        self._check_open()
        if self._svc is None:  # nothing ever ingested
            with key_dtype_context(self.by.key_dtype):
                state = empty_state(
                    0, 0, key_dtype=self.by.key_dtype,
                    widths=self.aggs.plane_widths(0))
            return self._result(state, SpillStats())
        with span("repro.snapshot", snapshot=self.metrics.snapshots_taken):
            state, stats = self._svc.snapshot()
        return self._result(state, stats)

    def expire_below(self, cutoff=None, **by_name) -> int:
        """Retire every group whose watermark column is ``< cutoff``
        (TTL expiry).  Accepts the cutoff positionally or by column name
        (``sess.expire_below(minute=120)``).  Returns the cumulative
        retired-row count; later snapshots report it as
        ``stats.rows_retired``."""
        self._check_open()
        if self.watermark is None:
            raise RuntimeError(
                "session has no watermark column; construct with "
                "watermark=<major key column> to enable TTL expiry"
            )
        if by_name:
            if cutoff is not None or set(by_name) != {self.watermark}:
                raise ValueError(
                    f"pass ONE cutoff for the watermark column "
                    f"{self.watermark!r}, got cutoff={cutoff!r}, {by_name}"
                )
            cutoff = by_name[self.watermark]
        if cutoff is None:
            raise ValueError("expire_below needs a cutoff")
        col = self.by.columns[0]
        cutoff = int(cutoff)
        if not 0 <= cutoff <= col.max_value + 1:
            raise ValueError(
                f"cutoff {cutoff} out of range for {col.bits}-bit column "
                f"{col.name!r}"
            )
        if self._svc is None:
            return 0
        threshold = cutoff << self.by.shift_of(self.watermark)
        return self._svc.retire_below(threshold)

    def close(self) -> AggResult:
        """Destructive final drain; the session accepts no further
        ingest.  An empty session closes to the same valid empty
        relation a snapshot would report."""
        self._check_open()
        self._closed = True
        if self._svc is None:
            with key_dtype_context(self.by.key_dtype):
                state = empty_state(
                    0, 0, key_dtype=self.by.key_dtype,
                    widths=self.aggs.plane_widths(0))
            return self._result(state, SpillStats())
        with span("repro.close"):
            state, stats = self._svc.close()
        return self._result(state, stats)


def serve_aggregate(**kwargs) -> AggregationSession:
    """Open a long-lived aggregation session — the serving twin of
    :func:`repro.aggregate` (same ``by=``/``values=``/``aggs=`` schema
    arguments, plus ``watermark=`` for TTL expiry and the streaming
    engine's knobs).  See :class:`AggregationSession`."""
    return AggregationSession(**kwargs)
