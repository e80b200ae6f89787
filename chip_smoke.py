#!/usr/bin/env python3
"""Run the aggregation engine's main path once on a TPU and check every answer.

    python chip_smoke.py [--sf 1] [--seed 0]     # one chip, three phases
    python chip_smoke.py --chips 4 [--sf 10]     # the mesh path only
    python chip_smoke.py --pallas-probe          # the Pallas kernels only

The data is TPC-H ``lineitem`` (key and quantity columns) at scale factor
``--sf``, generated from ``--seed`` by :mod:`repro.data.tpch`, with rows in
no particular order.  One chip runs three phases through the public entry
points, each with an ``ExecConfig`` whose memory holds far fewer rows than
the output has groups, so run generation spills runs that the wide merge
combines:

1. ``SELECT l_orderkey, count(*), sum(l_quantity) ... GROUP BY l_orderkey``
   through ``repro.aggregate`` (uint32 keys; TPC-H Q18's inner aggregate),
   with a merge fan-in small enough that the §4.3 planner puts one
   pre-merge level before the wide merge;
2. the same rows ``GROUP BY (l_suppkey, l_orderkey)`` (a key wider than 32
   bits, so the uint64 engine runs);
3. the phase-1 query over the rows fed in chunks to ``repro.serve_aggregate``,
   with two snapshots mid-stream and the final close.

``--chips 4`` runs phase 1's query over a 1-D mesh of four local chips and
on one chip, at the fan-in of phases 2 and 3 (each shard's output is far
below memory × fan-in either way), and compares both.  ``--pallas-probe`` runs each Pallas kernel at
its single-tile limit (``kernels.ops.MAX_TILE_ROWS``) on uint32 and
uint64 keys and compares it with the XLA engine and NumPy.  Every result is compared in full (keys, counts,
sums) with a NumPy reference that shares no code with ``repro``; quantity
sums are integers below 2^24, so the comparison is exact.  Each phase runs
once; JAX's own compile events split its wall time into compile seconds
(tracing, lowering, compiling or fetching from the persistent cache) and
run seconds.

Each phase prints one JSON line; the last line of standard output is
``{"ok": true, "device": {...}}``.  Any mismatch, any exception, or a
platform other than ``tpu`` exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402
from repro.core.types import ExecConfig  # noqa: E402
from repro.data import tpch  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Memory well below the 1.5M·SF groups (the paper's large-output
# regime).  At SF 1 the output is under MEMORY_ROWS × FANIN, so the §4.3
# planner sends every run straight to the wide merge; phase 1 merges
# with PREMERGE_FANIN, which makes it plan one pre-merge level.  Replacement
# selection merges each batch into its table, so its cost per row grows
# with MEMORY_ROWS / BATCH_ROWS; on a v5e it measured 22.8 µs per row at
# 2^18 / 2^14, so the batch here is as large as memory.  The wide merge
# pays its index width once per page: pages are large, but small enough
# that every run's current page fits the index.
MEMORY_ROWS = 1 << 19
BATCH_ROWS = 1 << 19
PAGE_ROWS = 1 << 16
FANIN = 8
PREMERGE_FANIN = 2
STREAM_CHUNK_ROWS = 1 << 19


class Mismatch(AssertionError):
    pass


def reference(key_cols, qty):
    """Sorted distinct key tuples with count and sum, by plain NumPy.
    Key columns are uint32 and combine major-first into one uint64."""
    k = np.zeros(len(qty), np.uint64)
    for c in key_cols:
        k = (k << np.uint64(32)) | c.astype(np.uint64)
    uk, inv = np.unique(k, return_inverse=True)
    cols = []
    for i in range(len(key_cols)):
        shift = np.uint64(32 * (len(key_cols) - 1 - i))
        cols.append(((uk >> shift) & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    count = np.bincount(inv, minlength=len(uk))
    total = np.bincount(inv, weights=qty.astype(np.float64), minlength=len(uk))
    return cols, count, total


def check(result, names, ref, where):
    """Compare an ``AggResult`` with the reference; check order."""
    keys = np.asarray(result.state.keys)
    live = keys[keys != np.iinfo(keys.dtype).max]
    if live.size > 1 and not bool(np.all(live[1:] > live[:-1])):
        raise Mismatch(f"{where}: output keys not strictly increasing")
    rel = result.relation()
    ref_cols, ref_count, ref_sum = ref
    for name, col in zip(names, ref_cols):
        if not np.array_equal(np.asarray(rel[name]).astype(np.uint32), col):
            raise Mismatch(f"{where}: key column {name} differs "
                           f"({len(rel[name])} rows vs {len(col)})")
    if not np.array_equal(np.asarray(rel["count"]).reshape(-1), ref_count):
        raise Mismatch(f"{where}: counts differ")
    if not np.array_equal(np.asarray(rel["sum"]).reshape(-1).astype(np.float64),
                          ref_sum):
        raise Mismatch(f"{where}: sums differ")
    return len(ref_count)


class CompileClock:
    """JAX's own compile events: the time spent tracing, lowering and
    compiling (or fetching from the persistent cache), as the union of
    their spans (traces of nested jits overlap), and cache hits."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.spans, self.cache_hits = [], 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event in self.EVENTS:
            self.spans.append((start, end))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return len(self.spans), self.cache_hits, time.perf_counter()

    def since(self, mark):
        """(wall seconds, compile seconds, cache hits) since ``mark``."""
        n0, h0, t0 = mark
        wall = time.perf_counter() - t0
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans[n0:]):
            if end > reach:
                total += end - max(start, reach)
                reach = end
        return wall, total, self.cache_hits - h0

    def timed(self, fn):
        """(result, wall, compile, cache hits) of one call whose result's
        ``state`` is waited for."""
        mark = self.mark()
        out = fn()
        jax.block_until_ready(out.state)
        return (out, *self.since(mark))


def emit(phase, *, rows, groups, timing, result, **extra):
    """One phase line.  ``run_s`` is wall time less compile time."""
    wall, compile_s, hits = timing
    line = {
        "phase": phase, "rows": rows, "groups": groups,
        "wall_s": wall, "compile_s": compile_s, "run_s": wall - compile_s,
        "cache_hits": hits, "backend": result.plan["backend"],
        "stats": result.stats.as_dict(),
    }
    line.update(extra)
    print(json.dumps(line), flush=True)


def require_spill_and_merge(result, where, *, premerge=False):
    """Run generation spilled and the wide merge ran; with ``premerge``,
    a §4.3 pre-merge level ran before it (one more level, and merge steps
    beyond the wide merge's one)."""
    s = result.stats
    levels = 2 if premerge else 1
    if not (s.runs_generated > 0 and s.rows_spilled_run_generation > 0
            and s.merge_levels >= levels and s.merge_steps >= levels):
        raise Mismatch(f"{where}: run generation/spill/"
                       f"{'pre-merge/' if premerge else ''}merge did not all "
                       f"run ({s.as_dict()})")


def one_shot(cols, spec_cols, *, cfg, n_groups, mesh=None):
    spec = repro.KeySpec.of(**{
        n: max(1, int(cols[n].max()).bit_length()) for n in spec_cols})
    return lambda: repro.aggregate(
        {n: cols[n] for n in spec_cols}, by=spec, values=cols["l_quantity"],
        aggs=("count", "sum"), cfg=cfg, output_estimate=n_groups, mesh=mesh)


def streamed(cols, *, cfg, n_groups, chunk_rows, snap_at, ref_at):
    """Feed the phase-1 rows in chunks to a serving session; snapshot
    after the chunks in ``snap_at`` and check each against the reference
    over the rows ingested so far (``ref_at(rows)``)."""
    n = len(cols["l_orderkey"])
    spec = repro.KeySpec.of(
        l_orderkey=int(cols["l_orderkey"].max()).bit_length())
    sess = repro.serve_aggregate(by=spec, values="l_quantity",
                                 aggs=("count", "sum"), cfg=cfg,
                                 output_estimate=n_groups)
    snaps = []
    starts = range(0, n, chunk_rows)
    for i, lo in enumerate(starts):
        sess.ingest({k: cols[k][lo:lo + chunk_rows]
                     for k in ("l_orderkey", "l_quantity")})
        if i in snap_at:
            t = time.perf_counter()
            snap = sess.snapshot()
            jax.block_until_ready(snap.state)
            rows = min(lo + chunk_rows, n)
            snaps.append((rows, time.perf_counter() - t, snap))
    t = time.perf_counter()
    final = sess.close()
    jax.block_until_ready(final.state)
    close_s = time.perf_counter() - t
    for rows, _, snap in snaps:
        check(snap, ["l_orderkey"], ref_at(rows), f"stream snapshot @{rows}")
    return final, [(r, s) for r, s, _ in snaps], close_s


def run_one_chip(cols, *, cfg, n_groups, chunk_rows, clock):
    qty = cols["l_quantity"]
    n = len(qty)
    ref1 = reference([cols["l_orderkey"]], qty)

    cfg1 = dataclasses.replace(cfg, fanin=PREMERGE_FANIN)
    res, *timing = clock.timed(one_shot(cols, ["l_orderkey"], cfg=cfg1,
                                        n_groups=n_groups))
    groups = check(res, ["l_orderkey"], ref1, "phase 1")
    require_spill_and_merge(res, "phase 1", premerge=True)
    emit("q18_inner_u32", rows=n, groups=groups, timing=timing, result=res,
         key_dtype=str(np.asarray(res.state.keys).dtype))

    ref2 = reference([cols["l_suppkey"], cols["l_orderkey"]], qty)
    # nothing bounds the pair count short of the row count, so phase 2
    # leaves the output estimate to the planner's default
    res, *timing = clock.timed(one_shot(cols, ["l_suppkey", "l_orderkey"],
                                        cfg=cfg, n_groups=None))
    groups = check(res, ["l_suppkey", "l_orderkey"], ref2, "phase 2")
    require_spill_and_merge(res, "phase 2")
    emit("supp_order_u64", rows=n, groups=groups, timing=timing, result=res,
         key_dtype=str(np.asarray(res.state.keys).dtype))

    n_chunks = -(-n // chunk_rows)
    snap_at = {n_chunks // 3, (2 * n_chunks) // 3}

    def ref_at(rows):
        return reference([cols["l_orderkey"][:rows]], qty[:rows])

    mark = clock.mark()
    final, snaps, close_s = streamed(
        cols, cfg=cfg, n_groups=n_groups, chunk_rows=chunk_rows,
        snap_at=snap_at, ref_at=ref_at)
    # the wall time includes the snapshots' own reference checks
    timing = clock.since(mark)
    groups = check(final, ["l_orderkey"], ref1, "phase 3 close")
    emit("q18_inner_streamed", rows=n, groups=groups, timing=timing,
         result=final, chunk_rows=chunk_rows,
         snapshots=[{"rows": r, "seconds": s} for r, s in snaps],
         close_s=close_s)


def run_mesh(cols, *, cfg, n_groups, chips, clock):
    """Phase 1 over the mesh, then on one chip; the mesh lines print
    before the one-chip run starts."""
    qty = cols["l_quantity"]
    ref1 = reference([cols["l_orderkey"]], qty)
    mesh = jax.make_mesh((chips,), ("data",), devices=jax.devices()[:chips])
    sharded, *timing = clock.timed(one_shot(
        cols, ["l_orderkey"], cfg=cfg, n_groups=n_groups, mesh=mesh))
    groups = check(sharded, ["l_orderkey"], ref1, "phase 1 on the mesh")
    s = sharded.stats
    print(json.dumps({
        "exchange_quota": s.exchange_quota,
        "exchange_max_fill": s.exchange_max_fill,
        "exchange_retries": s.exchange_retries,
        "rows_exchanged": s.rows_exchanged,
    }), flush=True)
    emit(f"q18_inner_u32_mesh{chips}", rows=len(qty), groups=groups,
         timing=timing, result=sharded)
    single, *single_timing = clock.timed(one_shot(
        cols, ["l_orderkey"], cfg=cfg, n_groups=n_groups))
    check(single, ["l_orderkey"], ref1, "phase 1 on one chip")
    a, b = sharded.relation(), single.relation()
    for k in b:
        if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
            raise Mismatch(f"mesh and one-chip results differ in {k}")
    emit("q18_inner_u32_one_chip", rows=len(qty), groups=groups,
         timing=single_timing, result=single)


def pallas_probe(seed):
    """Each Pallas kernel at its single-tile limit, through the public
    primitives with ``backend="pallas"``, against the XLA engine and
    NumPy, on uint32 and uint64 keys."""
    from repro.core import dispatch, merge_join, sorted_ops
    from repro.core.types import key_dtype_context, rows_to_state
    from repro.kernels.ops import MAX_TILE_ROWS

    dispatch.get_backend("pallas")  # compile-probes every kernel on a TPU
    rng = np.random.default_rng(seed)
    for dtype in (np.uint32, np.uint64):
        top = (1 << 40) if dtype == np.uint64 else (1 << 31)

        def keys(n, distinct):
            return rng.integers(0, top, distinct, dtype=np.uint64)[
                rng.integers(0, distinct, n)].astype(dtype)

        def same(name, fn, *args):
            t = time.perf_counter()
            with key_dtype_context(dtype):
                got, want = (jax.block_until_ready(jax.jit(
                    lambda *a, b=b: fn(*a, backend=b))(*args))
                    for b in ("pallas", "xla"))
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                if not np.array_equal(np.asarray(g), np.asarray(w)):
                    raise Mismatch(f"pallas {name} ({np.dtype(dtype).name}) "
                                   "differs from xla")
            print(json.dumps({"phase": "pallas_probe", "kernel": name,
                              "key_dtype": np.dtype(dtype).name,
                              "seconds": time.perf_counter() - t}), flush=True)
            return got

        def state(k):
            with key_dtype_context(dtype):
                return rows_to_state(k, np.ones((len(k), 1), np.float32))

        n = MAX_TILE_ROWS["sort"]
        k = keys(n, n)
        out = same("sort", sorted_ops.sort_state, state(k))
        if not np.array_equal(np.asarray(out.keys), np.sort(k)):
            raise Mismatch(f"pallas sort ({np.dtype(dtype).name}) is not "
                           "NumPy's sort")

        n = MAX_TILE_ROWS["scan"]
        k = np.sort(keys(n, n // 8))
        out = same("segmented_combine", sorted_ops.segmented_combine, state(k))
        uk, cnt = np.unique(k, return_counts=True)
        if not (np.array_equal(np.asarray(out.keys)[:len(uk)], uk)
                and np.array_equal(np.asarray(out.count)[:len(uk)], cnt)):
            raise Mismatch(f"pallas segmented_combine "
                           f"({np.dtype(dtype).name}) is not NumPy's")

        half = MAX_TILE_ROWS["merge"] // 2
        a, b = np.sort(keys(half, half)), np.sort(keys(half, half))
        same("merge_absorb", sorted_ops.merge_absorb, state(a), state(b))

        n = MAX_TILE_ROWS["probe"]
        a, b = np.sort(keys(n, n)), np.sort(keys(n, n // 2))
        pos, hit = same("join_probe", merge_join.join_probe, a, b)
        if not np.array_equal(np.asarray(hit), np.isin(a, b)):
            raise Mismatch(f"pallas join_probe ({np.dtype(dtype).name}) "
                           "hits are not NumPy's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (lineitem rows ≈ 6M × SF)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: phase 1 over a mesh of four chips, vs one chip")
    ap.add_argument("--pallas-probe", action="store_true",
                    help="run only the Pallas kernels, against XLA and NumPy")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} TPU device(s)", file=sys.stderr)
        return 2

    cache = enable_compile_cache()
    if args.pallas_probe:
        pallas_probe(args.seed)
        return ok(devices)
    clock = CompileClock()
    t = time.perf_counter()
    cols = tpch.lineitem(args.sf, args.seed)
    print(json.dumps({"phase": "generate", "sf": args.sf, "seed": args.seed,
                      "rows": len(cols["l_orderkey"]),
                      "seconds": time.perf_counter() - t,
                      "compile_cache": cache}), flush=True)
    cfg = ExecConfig(memory_rows=MEMORY_ROWS, batch_rows=BATCH_ROWS,
                     page_rows=PAGE_ROWS, fanin=FANIN)
    n_groups = tpch.orders_count(args.sf)
    if args.chips == 1:
        run_one_chip(cols, cfg=cfg, n_groups=n_groups,
                     chunk_rows=STREAM_CHUNK_ROWS, clock=clock)
    else:
        run_mesh(cols, cfg=cfg, n_groups=n_groups, chips=args.chips,
                 clock=clock)
    return ok(devices)


def ok(devices) -> int:
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
