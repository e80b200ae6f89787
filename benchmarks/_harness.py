"""Shared benchmark harness: timing loop, block-until-ready discipline,
JSON/CSV report writing, and ``--smoke`` plumbing.

Every benchmark in this directory follows the same protocol — warm up
(compile + caches), time a loop, print a table, optionally persist a
machine-readable report, and degrade to a tiny CI sanity run under
``--smoke``.  That boilerplate used to be copy-pasted per script; it
lives here now so a fix (e.g. to the block-until-ready discipline)
lands everywhere at once.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def add_common_args(p: argparse.ArgumentParser, *, iters: int,
                    backend: str = "xla") -> argparse.ArgumentParser:
    """The flags every benchmark shares: --iters, --backend, --smoke.
    Every benchmark's ``main`` builds its parser here, so this is also
    where the benchmarks turn on the persistent compilation cache."""
    enable_compile_cache()
    p.add_argument("--iters", type=int, default=iters)
    p.add_argument("--backend", type=str, default=backend,
                   choices=("xla", "pallas", "auto"))
    p.add_argument("--smoke", action="store_true",
                   help="tiny sizes / few iters — CI sanity run, not a "
                        "measurement; JSON reports are suppressed unless "
                        "an explicit output path is given")
    return p


def time_fn(fn, *args, iters: int, block_each: bool = False,
            reduce: str = "mean") -> float:
    """Seconds per call of ``fn(*args)`` over ``iters`` timed calls,
    after one untimed warmup call (compile + caches).

    ``block_each=True`` blocks on every call's result (end-to-end latency
    per call — use when the loop body's dispatch overlap would hide host
    orchestration costs being measured); the default blocks once after
    the loop (amortized device throughput).

    ``reduce`` picks the estimator: ``"mean"`` over the timed calls, or
    ``"min"`` (fastest call — robust when other processes contend for
    the cores, since interference only ever ADDS time).
    """
    out = fn(*args)  # warmup: compile + caches
    jax.block_until_ready(out)
    if reduce == "min" and block_each:
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t0)
        return min(times)
    if reduce != "mean":
        raise ValueError("reduce='min' requires block_each=True")
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        if block_each:
            jax.block_until_ready(out)
    if not block_each:
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def device_memory_stats() -> dict:
    """Peak / in-use device memory for report footprint tracking.

    Backed by ``jax.local_devices()[0].memory_stats()`` where the runtime
    exposes it (GPU/TPU); platforms without allocator stats (CPU) report
    ``{"available": False, "note": "n/a"}`` so BENCH_*.json trajectories
    always carry the field."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    if not stats:
        return {"available": False, "note": "n/a"}
    out = {"available": True}
    for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
              "largest_alloc_size"):
        if k in stats:
            out[k] = int(stats[k])
    return out


def write_json_report(report: dict, *, out: str | None, smoke: bool,
                      default_name: str) -> str | None:
    """Persist ``report`` as JSON.  Default path is the repo root (the
    committed ``BENCH_*.json`` convention); ``--smoke`` runs write
    nothing unless the caller passed an explicit path.  Every report
    carries the device kind and its peak-memory stats (footprint
    trajectories, not just wall-clock)."""
    if out is None and not smoke:
        out = str(REPO_ROOT / default_name)
    if out:
        report = dict(report, jax_device=jax.default_backend(),
                      device_memory=device_memory_stats())
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {out}")
    return out


def write_csv(path: str | None, header: list[str], rows: list[tuple]) -> None:
    if not path:
        return
    with open(path, "w") as f:
        f.write(",".join(header) + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")


def interpret_note(backend: str) -> bool:
    """Print the standard caveat when Pallas ran in interpret mode (the
    timings are emulator overhead, not kernel performance).  Returns
    whether the caveat applies — perf win-checks should be skipped."""
    from repro.core import dispatch

    if backend == "pallas" and dispatch.should_interpret():
        print("note: pallas ran in interpret mode (no TPU) — timings are "
              "emulator overhead, not kernel performance")
        return True
    return False
