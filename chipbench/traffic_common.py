"""What every traffic generator shares: the window's record, an answer to
compare, and the benchmark's host spans."""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from chipbench.reference import Relation


@dataclasses.dataclass
class Window:
    seconds: float
    attempted: int
    metrics: dict[str, float]   # end-to-end, by name
    counters: dict              # what the per-layer readers read


@dataclasses.dataclass
class Answer:
    attempt: int        # the query or session it belongs to
    label: str
    rows: int           # the answer covers the first ``rows`` input rows
    relation: Relation


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    return jax.profiler.TraceAnnotation(name)


def relation_of(result, query) -> Relation:
    """An ``AggResult``'s relation, as the user reads it."""
    rel = result.relation()
    sums = rel.get("sum")
    avg = rel.get("avg")
    return Relation(
        keys=[np.asarray(rel[k]) for k in query.keys],
        count=np.asarray(rel["count"]).reshape(-1),
        sum=None if sums is None else np.asarray(sums, np.float64),
        avg=None if avg is None else np.asarray(avg, np.float64))
