"""Stream traffic: serving sessions through ``repro.serve_aggregate``,
one after another.  Each session ingests the whole table in
``chunk_rows`` chunks, takes a merge-on-read ``snapshot()`` after every
``snapshot_every``-th chunk, then calls ``close()``.  The window ends
when the first session that finishes after ``seconds`` finishes.  Every
snapshot and every close is an answer, compared with the reference over
the rows ingested before it."""
from __future__ import annotations

import time

import jax

import repro
from chipbench.traffic_common import Answer, Window, relation_of, span
from repro.core.types import ExecConfig


class Traffic:
    def __init__(self, query, params: dict, *, mesh=None):
        if mesh is not None:
            raise ValueError("stream traffic runs on one chip")
        self.q = query
        self.chunk = int(params["chunk_rows"])
        self.every = int(params["snapshot_every"])
        cfg = query.config
        self._kw = dict(
            by=repro.KeySpec.of(**query.bits),
            values=None if query.values is None else "values",
            aggs=tuple(cfg["aggs"]), cfg=ExecConfig(**cfg["exec_config"]),
            output_estimate=cfg["output_estimate"])
        self.results = []  # per session: (rows ingested, AggResult) pairs
        self.ingest_s = 0.0
        self.chunks = 0
        self.answer_s = 0.0
        self.answers_taken = 0

    def _batch(self, lo):
        hi = lo + self.chunk
        batch = {k: v[lo:hi] for k, v in self.q.keys.items()}
        if self.q.values is not None:
            batch["values"] = self.q.values[lo:hi]
        return batch

    def session(self, keep=None):
        """One whole session; ``keep`` collects (rows, result) pairs."""
        sess = repro.serve_aggregate(**self._kw)
        n = self.q.rows
        for i, lo in enumerate(range(0, n, self.chunk)):
            batch = self._batch(lo)
            with span("chipbench.ingest"):
                t = time.perf_counter()
                sess.ingest(batch)
                self.ingest_s += time.perf_counter() - t
            self.chunks += 1
            if (i + 1) % self.every == 0 and lo + self.chunk < n:
                with span("chipbench.snapshot"):
                    t = time.perf_counter()
                    snap = sess.snapshot()
                    jax.block_until_ready(snap.state)
                    self.answer_s += time.perf_counter() - t
                self.answers_taken += 1
                if keep is not None:
                    keep.append((lo + self.chunk, snap))
        with span("chipbench.close"):
            t = time.perf_counter()
            final = sess.close()
            jax.block_until_ready(final.state)
            self.answer_s += time.perf_counter() - t
        self.answers_taken += 1
        if keep is not None:
            keep.append((n, final))

    def warm(self) -> None:
        self.session()
        self.ingest_s = self.answer_s = 0.0
        self.chunks = self.answers_taken = 0

    def window(self, seconds: float) -> Window:
        t0 = time.perf_counter()
        while True:
            keep = []
            self.session(keep)
            self.results.append(keep)
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        sessions = len(self.results)
        return Window(
            seconds=elapsed, attempted=sessions,
            metrics={"ingest_rows_per_s": sessions * self.q.rows / elapsed,
                     "snapshot_s": self.answer_s / self.answers_taken},
            counters={"sessions": sessions, "rows_ingested": sessions * self.q.rows,
                      "chunks": self.chunks, "answers": self.answers_taken,
                      "ingest_host_s": self.ingest_s})

    def answers(self):
        for s, keep in enumerate(self.results):
            for j, (rows, result) in enumerate(keep):
                keep[j] = (rows, None)
                last = j == len(keep) - 1
                yield Answer(attempt=s,
                             label=f"session {s} {'close' if last else f'snapshot {j}'}",
                             rows=rows, relation=relation_of(result, self.q))
