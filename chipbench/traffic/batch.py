"""Batch traffic: one-shot queries through ``repro.aggregate``, one at a
time (a closed loop), each from host columns to a result whose state is
waited for.  The window ends when the first query that finishes after
``seconds`` finishes; every query is one answer, compared in full."""
from __future__ import annotations

import time

import jax

import repro
from chipbench.traffic_common import Answer, Window, relation_of, span
from repro.core.types import ExecConfig


class Traffic:
    def __init__(self, query, params: dict, *, mesh=None):
        self.q = query
        self.mesh = mesh
        cfg = query.config
        self._kw = dict(
            by=repro.KeySpec.of(**query.bits), values=query.values,
            aggs=tuple(cfg["aggs"]), cfg=ExecConfig(**cfg["exec_config"]),
            output_estimate=cfg["output_estimate"], mesh=mesh)
        self.results = []

    def one(self):
        result = repro.aggregate(self.q.keys, **self._kw)
        jax.block_until_ready(result.state)
        return result

    def warm(self) -> None:
        self.one()

    def window(self, seconds: float) -> Window:
        t0 = time.perf_counter()
        while True:
            with span("chipbench.query"):
                self.results.append(self.one())
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        n = len(self.results)
        return Window(
            seconds=elapsed, attempted=n,
            metrics={"agg_rows_per_s": n * self.q.rows / elapsed},
            counters={"queries": n, "rows_per_query": self.q.rows,
                      "stats": [r.stats for r in self.results]})

    def answers(self):
        """Each window query's relation, pulled to the host one by one
        (and released on the device)."""
        for i in range(len(self.results)):
            result, self.results[i] = self.results[i], None
            yield Answer(attempt=i, label=f"query {i}", rows=self.q.rows,
                         relation=relation_of(result, self.q))
