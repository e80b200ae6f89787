"""Aggregation-serving driver: sustained synthetic ingest with periodic
merge-on-read snapshot queries — the streaming-service twin of the
model-serving loop in :mod:`repro.launch.serve`.

    PYTHONPATH=src python -m repro.launch.serve_agg --smoke
    PYTHONPATH=src python -m repro.launch.serve_agg \
        --chunks 200 --chunk-rows 8192 --snapshot-every 25 --policy rs

Drives one :class:`repro.service.AggregationService` session: synthetic
keyed traffic (watermark-major composite keys, Zipf-ish duplication)
flows through the double-buffered ingest path while every
``--snapshot-every`` chunks a snapshot query runs against the live
engine.  Reports sustained ingest rows/sec and snapshot latency
p50/p99, plus the service metrics facade.  ``--ttl`` retires watermark
buckets older than that many snapshot periods at each snapshot
boundary (sessionization mode).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.types import ExecConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.service import AggregationService


def synth_chunks(n_chunks: int, rows: int, *, keyspace: int, seed: int,
                 drift: float = 0.02):
    """Synthetic keyed traffic: a slowly drifting hot window over a large
    key space — duplicate-heavy inside a chunk (early aggregation has
    something to do), with keys trending upward so watermark eviction
    retires real data."""
    rng = np.random.default_rng(seed)
    for i in range(n_chunks):
        lo = int(i * drift * keyspace)
        keys = (lo + rng.integers(0, keyspace, rows)).astype(np.uint32)
        pay = rng.standard_normal((rows, 1)).astype(np.float32)
        yield keys, pay


def serve(*, chunks=100, chunk_rows=4096, snapshot_every=20, policy="rs",
          backend="auto", memory_rows=4096, batch_rows=512, ttl=0,
          overlap=True, warmup=True, seed=0, quiet=False):
    cfg = ExecConfig(memory_rows=memory_rows, page_rows=256, fanin=8,
                     batch_rows=batch_rows)
    keyspace = max(1024, chunk_rows)

    def make_service():
        return AggregationService(
            cfg, policy=policy, backend=backend, key_dtype=np.uint32,
            width=1,
            output_rows=1 << max(12, (chunks * chunk_rows - 1).bit_length()),
            # upper-bound the distinct-key estimate so the pre-merge
            # planner inserts enough levels for a session's worth of runs
            output_estimate=chunks * chunk_rows,
            overlap=overlap,
        )

    if warmup:
        # warm EVERY compiled-program bucket the measured session will
        # visit (absorb/grow/snapshot statics are pow2-bucketed, so a
        # twin session over the same schedule hits the same jit caches —
        # the measured loop then runs pure steady state)
        twin = make_service()
        for i, (k, p) in enumerate(synth_chunks(
                chunks, chunk_rows, keyspace=keyspace, seed=seed + 1)):
            twin.ingest(k, p)
            if snapshot_every and (i + 1) % snapshot_every == 0:
                if ttl:
                    lo = int((i + 1 - ttl * snapshot_every) * 0.02 * keyspace)
                    if lo > 0:
                        twin.retire_below(lo)
                twin.snapshot()
        twin.close()

    svc = make_service()
    drift = 0.02
    t_ingest = 0.0
    rows_done = 0
    t0 = time.perf_counter()
    for i, (keys, pay) in enumerate(
            synth_chunks(chunks, chunk_rows, keyspace=keyspace, seed=seed)):
        svc.ingest(keys, pay)
        rows_done += len(keys)
        if snapshot_every and (i + 1) % snapshot_every == 0:
            t_ingest += time.perf_counter() - t0
            if ttl:
                lo = int((i + 1 - ttl * snapshot_every) * drift * keyspace)
                if lo > 0:
                    svc.retire_below(lo)
            state, stats = svc.snapshot()
            if not quiet:
                print(f"  chunk {i + 1:5d}: snapshot groups="
                      f"{int(state.occupancy())} retired="
                      f"{stats.rows_retired} "
                      f"({svc.metrics.snapshot_latencies_s[-1] * 1e3:.1f} ms)")
            t0 = time.perf_counter()
    t_ingest += time.perf_counter() - t0
    state, stats = svc.close()
    m = svc.metrics
    report = {
        "rows_ingested": m.rows_ingested,
        "ingest_rows_per_s": rows_done / max(t_ingest, 1e-9),
        "snapshots": m.snapshots_taken,
        "snapshot_p50_ms": m.snapshot_latency_s(0.5) * 1e3,
        "snapshot_p99_ms": m.snapshot_latency_s(0.99) * 1e3,
        "final_groups": int(state.occupancy()),
        "rows_retired": int(stats.rows_retired),
        "duplicate_rate": m.duplicate_rate,
    }
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--chunks", type=int, default=100)
    ap.add_argument("--chunk-rows", type=int, default=4096)
    ap.add_argument("--snapshot-every", type=int, default=20)
    ap.add_argument("--policy", default="rs",
                    choices=("traditional", "inrun_dedup", "early_agg", "rs"))
    ap.add_argument("--backend", default="auto")
    ap.add_argument("--memory-rows", type=int, default=4096)
    ap.add_argument("--batch-rows", type=int, default=512)
    ap.add_argument("--ttl", type=int, default=0,
                    help="retire watermarks older than TTL snapshot "
                         "periods (0 = keep everything)")
    ap.add_argument("--no-overlap", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    kw = dict(chunks=args.chunks, chunk_rows=args.chunk_rows,
              snapshot_every=args.snapshot_every, policy=args.policy,
              backend=args.backend, memory_rows=args.memory_rows,
              batch_rows=args.batch_rows, ttl=args.ttl,
              overlap=not args.no_overlap)
    if args.smoke:
        kw.update(chunks=12, chunk_rows=512, snapshot_every=4,
                  memory_rows=256, batch_rows=64)
    enable_compile_cache()
    r = serve(**kw)
    print(f"ingested {r['rows_ingested']} rows at "
          f"{r['ingest_rows_per_s'] / 1e6:.2f} M rows/s sustained")
    print(f"{r['snapshots']} snapshots: p50 {r['snapshot_p50_ms']:.1f} ms, "
          f"p99 {r['snapshot_p99_ms']:.1f} ms")
    print(f"final groups {r['final_groups']}, rows retired "
          f"{r['rows_retired']}, duplicate rate {r['duplicate_rate']:.3f}")


if __name__ == "__main__":
    main()
