"""Fit the cost model's per-row constants on THIS machine.

``make calibrate`` runs a small matrix of micro-benches against the
engine's actual primitives — one streamed absorb pass per
run-generation policy, the isolated batch sort, the linear
merge-absorb, the hash-table probe, and the run-store spill write —
then regenerates ``src/repro/core/_cost_constants.py`` with a fresh
entry for the current JAX device backend (other backends' entries are
preserved).  The hash-vs-sort empirical study (PAPERS.md) is explicit
that these crossovers are machine-dependent, so the constants are
*measured*, never hand-tuned; :mod:`repro.core.cost_model` refuses
stale-format files.

``--smoke`` runs the same code path at tiny sizes and writes to a
temp file instead of the checked-in constants (CI keeps the path
alive without clobbering a real calibration).  ``--check`` just
validates the checked-in file against the generator schema.
"""
from __future__ import annotations

import argparse
import pprint
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

import _harness as H

from repro.core import cost_model, hash_agg, sorted_ops  # noqa: E402
from repro.core.pipeline import POLICIES, StreamingAggregator  # noqa: E402
from repro.core.types import ExecConfig  # noqa: E402
from repro.core.ordered_index import empty_key  # noqa: E402

CONSTANTS_PATH = H.REPO_ROOT / "src" / "repro" / "core" / "_cost_constants.py"

HEADER = '''"""Measured per-row costs of the streaming engine's primitives.

AUTOGENERATED by ``make calibrate`` (benchmarks/calibrate.py) — do not
edit by hand; re-run calibration on new hardware instead.  The ``cpu``
entry is committed so :mod:`repro.core.cost_model` always has a floor
to stand on; other backends are added by running ``make calibrate`` on
a machine that has them.

All figures are nanoseconds per input row.  ``absorb_row_ns`` is the
end-to-end per-row cost of one streamed absorb step under each
run-generation policy (including the batch sort that policy pays);
``sort_row_ns`` is the isolated cost of that batch sort, so a caller
with pre-sorted input can subtract it (the "credit sorted input with a
zero sort term" rule in :func:`repro.core.cost_model.policy_cost_per_row`).
"""
'''


def _bench_absorb(policy: str, cfg: ExecConfig, n_rows: int, backend: str,
                  iters: int, *, dup: bool) -> float:
    """ns/row of the streamed absorb loop under ``policy`` at one of the
    two duplicate-rate anchors the cost model interpolates between:
    ``dup=False`` draws keys from a huge domain (d ≈ 0, spill-heavy
    worst case), ``dup=True`` from a tiny one (d ≈ 1, the window
    absorbs nearly everything)."""
    rng = np.random.default_rng(7)
    hi = 128 if dup else 2**31
    keys = rng.integers(1, hi, size=n_rows).astype(np.uint32)
    vals = rng.random((n_rows, 1)).astype(np.float32)
    B = cfg.batch_rows
    chunks = [(keys[i:i + B], vals[i:i + B]) for i in range(0, n_rows, B)]

    def run():
        agg = StreamingAggregator(cfg, policy=policy, key_dtype=np.uint32,
                                  width=1, backend=backend)
        staged = None
        for k, p in chunks:
            nxt = agg.stage(k, p)
            if staged is not None:
                agg.absorb_staged(staged)
            staged = nxt
        agg.absorb_staged(staged)
        agg.wait()
        return agg

    run()  # warmup: compile every absorb program for this geometry
    import time
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    return min(times) / n_rows * 1e9


def _bench_sort(m: int, iters: int) -> float:
    """ns/row of the batch sort the chunk=M policies pay per memory load."""
    rng = np.random.default_rng(11)
    keys = jnp.asarray(rng.integers(1, 2**31, size=m).astype(np.uint32))
    f = jax.jit(jnp.sort)
    return H.time_fn(f, keys, iters=iters, block_each=True,
                     reduce="min") / m * 1e9


def _bench_merge(m: int, backend: str, iters: int) -> float:
    """ns/row of one linear merge-absorb of two sorted M-row states —
    the unit of every pre-merge level and of the ordered-index absorb."""
    rng = np.random.default_rng(13)
    ka = np.sort(rng.choice(2**30, size=m, replace=False)).astype(np.uint32)
    kb = (np.sort(rng.choice(2**30, size=m, replace=False)) + 2**30).astype(
        np.uint32)
    a = sorted_ops.sorted_groupby(jnp.asarray(ka), backend=backend)
    b = sorted_ops.sorted_groupby(jnp.asarray(kb), backend=backend)
    f = jax.jit(lambda x, y: sorted_ops.merge_absorb(
        x, y, backend=backend, assume_unique=True))
    return H.time_fn(f, a, b, iters=iters, block_each=True,
                     reduce="min") / (2 * m) * 1e9


def _bench_hash_probe(n: int, cfg: ExecConfig, backend: str,
                      iters: int) -> float:
    """ns/row of the in-memory hash aggregation baseline (build+probe)."""
    rng = np.random.default_rng(17)
    keys = rng.integers(1, cfg.memory_rows // 2, size=n).astype(np.uint32)

    def f():
        return hash_agg.hash_aggregate(keys, None, cfg, backend=backend)

    return H.time_fn(f, iters=iters, block_each=True, reduce="min") / n * 1e9


def _bench_spill_write(m: int, slots: int, iters: int) -> float:
    """ns/row of retiring a sorted run into one store slot (the dynamic
    scatter the engine pays per spilled row)."""
    rng = np.random.default_rng(19)
    store = jnp.zeros((slots, m), dtype=jnp.uint32)
    block = jnp.asarray(rng.integers(1, 2**31, size=m).astype(np.uint32))

    @jax.jit
    def f(st, blk, slot):
        return st.at[slot].set(blk)

    return H.time_fn(f, store, block, jnp.int32(1), iters=iters,
                     block_each=True, reduce="min") / m * 1e9


def calibrate(args) -> dict:
    if args.smoke:
        cfg = ExecConfig(memory_rows=256, page_rows=32, fanin=4, batch_rows=64)
        n_rows, iters = 2_048, 1
    else:
        cfg = ExecConfig(memory_rows=args.memory_rows,
                         page_rows=min(512, args.memory_rows), fanin=8,
                         batch_rows=args.batch_rows)
        n_rows, iters = args.rows, args.iters
    M = cfg.memory_rows

    absorb, absorb_dup = {}, {}
    for policy in POLICIES:
        absorb[policy] = round(
            _bench_absorb(policy, cfg, n_rows, args.backend, iters,
                          dup=False), 3)
        absorb_dup[policy] = round(
            _bench_absorb(policy, cfg, n_rows, args.backend, iters,
                          dup=True), 3)
        print(f"absorb[{policy:12s}] {absorb[policy]:10.1f} ns/row uniq  "
              f"{absorb_dup[policy]:10.1f} ns/row dup")
    sort_ns = round(_bench_sort(M, max(3, iters)), 3)
    merge_ns = round(_bench_merge(M, args.backend, max(3, iters)), 3)
    hash_ns = round(_bench_hash_probe(n_rows, cfg, args.backend,
                                      max(2, iters)), 3)
    spill_ns = round(_bench_spill_write(M, 16, max(3, iters)), 3)
    print(f"sort                {sort_ns:10.1f} ns/row")
    print(f"merge               {merge_ns:10.1f} ns/row")
    print(f"hash probe          {hash_ns:10.1f} ns/row")
    print(f"spill write         {spill_ns:10.1f} ns/row")

    return {
        "schema_version": cost_model.COST_SCHEMA_VERSION,
        "absorb_row_ns": absorb,
        "absorb_dup_row_ns": absorb_dup,
        "sort_row_ns": sort_ns,
        "merge_row_ns": merge_ns,
        "hash_probe_row_ns": hash_ns,
        "spill_write_row_ns": spill_ns,
        "meta": {
            "generated_by": "benchmarks/calibrate.py",
            "backend": jax.default_backend(),
            "dispatch_backend": args.backend,
            "smoke": bool(args.smoke),
            "note": f"memory_rows={M} batch_rows={cfg.batch_rows} "
                    f"n_rows={n_rows} iters={iters}",
        },
    }


def render(table: dict) -> str:
    body = pprint.pformat(table, width=76, sort_dicts=True)
    return (
        f"{HEADER}\n"
        f"COST_SCHEMA_VERSION = {cost_model.COST_SCHEMA_VERSION}\n\n"
        f"COST_CONSTANTS = {body}\n"
    )


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    H.add_common_args(p, iters=3)
    p.add_argument("--out", type=str, default=None,
                   help="write the regenerated constants module here "
                        "(default: the checked-in core/_cost_constants.py; "
                        "--smoke defaults to a temp file)")
    p.add_argument("--memory-rows", type=int, default=4096,
                   help="ExecConfig.memory_rows the constants are fitted at")
    p.add_argument("--batch-rows", type=int, default=1024,
                   help="ExecConfig.batch_rows (also the streamed chunk)")
    p.add_argument("--rows", type=int, default=65_536,
                   help="rows streamed per absorb measurement")
    p.add_argument("--check", action="store_true",
                   help="only validate the checked-in constants against "
                        "the generator schema, then exit")
    args = p.parse_args(argv)

    if args.check:
        from repro.core import _cost_constants as cc
        cost_model.validate_constants(cc.COST_CONSTANTS)
        assert cc.COST_SCHEMA_VERSION == cost_model.COST_SCHEMA_VERSION, (
            f"module COST_SCHEMA_VERSION={cc.COST_SCHEMA_VERSION} != "
            f"generator {cost_model.COST_SCHEMA_VERSION}")
        print(f"{CONSTANTS_PATH} OK "
              f"(schema v{cost_model.COST_SCHEMA_VERSION}, backends: "
              f"{sorted(cc.COST_CONSTANTS)})")
        return

    entry = calibrate(args)

    # merge into the existing table so other backends' calibrations survive
    from repro.core import _cost_constants as cc
    table = dict(cc.COST_CONSTANTS)
    table[jax.default_backend()] = entry
    cost_model.validate_constants(table, source="<regenerated>")

    out = args.out
    if out is None:
        out = (tempfile.mkstemp(suffix="_cost_constants.py")[1]
               if args.smoke else str(CONSTANTS_PATH))
    src = render(table)
    with open(out, "w") as f:
        f.write(src)
    # round-trip: the emitted module must parse and re-validate
    ns: dict = {}
    exec(compile(src, out, "exec"), ns)
    cost_model.validate_constants(ns["COST_CONSTANTS"], source=out)
    print(f"wrote {out}")
    d_star = cost_model.crossover_dup_rate(
        constants=entry, merge_levels=1)
    print(f"fitted traditional↔early_agg crossover dup rate: {d_star:.3f}")


if __name__ == "__main__":
    main()
