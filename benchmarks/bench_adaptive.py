"""Adaptive vs fixed-policy streaming on adversarial phase-change keys.

The stream that defeats any up-front policy choice: keys drawn from a
huge domain (duplicate rate ≈ 0) that switch to a tiny domain
(duplicate rate ≈ 1) halfway through — and the reverse.  A fixed policy
is tuned for one phase and eats the other; ``policy="adaptive"`` reads
the engine's device-side observation block every k-th chunk and lets
the calibrated governor re-decide, so the wrong guess costs one
observation window.

Acceptance (ISSUE 8, checked here and recorded in BENCH_adaptive.json):
  * adaptive is within 10% of the BEST fixed policy on each phase;
  * adaptive is >= 1.5x faster than the WORST fixed policy end-to-end;
  * exact keys/counts parity with the one-shot oracle.

A second adaptive run starts from a deliberately wrong arm
(``start="rs"``) to demonstrate mid-flight recovery — its switch events
land in the report.
"""
from __future__ import annotations

import sys
import time

import jax
import numpy as np

import _harness as H

from repro.core.adaptive import GovernorConfig  # noqa: E402
from repro.core.pipeline import ADAPTIVE_ARMS, StreamingAggregator  # noqa: E402
from repro.core.types import ExecConfig, empty_key  # noqa: E402


def make_phases(cfg: ExecConfig, chunks_per_phase: int, order: str,
                seed: int = 3):
    """Two lists of (keys, payload) chunks: a unique-heavy phase and a
    duplicate-heavy phase, in the requested order."""
    rng = np.random.default_rng(seed)
    M = cfg.memory_rows
    n = chunks_per_phase * M

    def chunked(keys):
        vals = rng.random((n, 1)).astype(np.float32)
        return [(keys[i:i + M], vals[i:i + M]) for i in range(0, n, M)]

    uniq = chunked(rng.integers(1, 2**31, size=n).astype(np.uint32))
    dup = chunked(rng.integers(1, max(2, M // 64), size=n).astype(np.uint32))
    phases = {"uniq": uniq, "dup": dup}
    names = order.split("->")
    return [(nm, phases[nm]) for nm in names]


def run_stream(cfg, phases, *, policy, backend, output_estimate,
               governor=None):
    """One full streamed aggregation; returns per-phase wall seconds,
    finalize seconds, and the result."""
    agg = StreamingAggregator(
        cfg, policy=policy, key_dtype=np.uint32, width=1, backend=backend,
        output_estimate=output_estimate, governor=governor,
    )
    phase_s = []
    for _name, chunks in phases:
        t0 = time.perf_counter()
        for k, p in chunks:
            agg.absorb(k, p)
        agg.wait()
        phase_s.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    state, stats = agg.finalize()
    jax.block_until_ready(state.keys)
    fin_s = time.perf_counter() - t0
    return phase_s, fin_s, state, stats, agg


def oracle(phases):
    keys = np.concatenate([k for _n, chunks in phases for k, _p in chunks])
    uk, counts = np.unique(keys, return_counts=True)
    return uk, counts


def check_parity(state, phases) -> bool:
    uk, counts = oracle(phases)
    got_k = np.asarray(state.keys)
    live = got_k != empty_key(got_k.dtype)
    ok = (int(live.sum()) == len(uk)
          and bool(np.array_equal(np.sort(got_k[live]), uk)))
    if ok:
        got_c = np.asarray(state.count)[live]
        order = np.argsort(got_k[live])
        ok = bool(np.array_equal(got_c[order], counts))
    return ok


def bench_scenario(order: str, cfg, chunks_per_phase, backend, smoke,
                   iters: int = 3):
    phases = make_phases(cfg, chunks_per_phase, order)
    n_rows = 2 * chunks_per_phase * cfg.memory_rows
    uk, _ = oracle(phases)
    out_est = int(2 ** int(np.ceil(np.log2(len(uk) + 1))))
    print(f"\n== scenario {order}: {n_rows} rows, {len(uk)} groups ==")

    results = {}
    contenders = [(p, p, None) for p in ADAPTIVE_ARMS]
    contenders.append(("adaptive", "adaptive", None))
    contenders.append(
        ("adaptive_wrong_start", "adaptive",
         lambda: GovernorConfig(start="rs", interval_chunks=4)))
    for label, policy, gov_fn in contenders:
        run_stream(cfg, phases, policy=policy, backend=backend,
                   output_estimate=out_est,
                   governor=gov_fn() if gov_fn else None)  # warmup: compile
        # min over repeats, per phase: at ~0.1s per phase a single sample
        # carries allocator/scheduler noise comparable to the 10% bar, so
        # every contender gets the same noise-robust estimator (a fresh
        # governor per repeat — adaptive re-fights its switches each time)
        reps = []
        for _ in range(max(1, iters)):
            reps.append(run_stream(
                cfg, phases, policy=policy, backend=backend,
                output_estimate=out_est,
                governor=gov_fn() if gov_fn else None))
        parity = all(check_parity(r[2], phases) for r in reps)
        phase_s = [min(r[0][i] for r in reps) for i in range(len(phases))]
        fin_s = min(r[1] for r in reps)
        _, _, state, stats, agg = reps[-1]
        d = stats.as_dict()
        results[label] = {
            "phase_s": [round(t, 4) for t in phase_s],
            "finalize_s": round(fin_s, 4),
            "end_to_end_s": round(sum(phase_s) + fin_s, 4),
            "iters": max(1, iters),
            "parity": parity,
            "policy_switches": d["policy_switches"],
            "readbacks_paid": d["readbacks_paid"],
            "duplicate_rate": round(d["duplicate_rate"], 4),
            "policy_events": agg.policy_events,
        }
        row = results[label]
        print(f"{label:22s} phases={row['phase_s']} fin={row['finalize_s']}"
              f" e2e={row['end_to_end_s']:.3f}s switches="
              f"{row['policy_switches']} readbacks={row['readbacks_paid']}"
              f" parity={'OK' if parity else 'MISMATCH'}")

    fixed = {p: results[p] for p in ADAPTIVE_ARMS}
    ad = results["adaptive"]
    checks = {}
    for i, (pname, _c) in enumerate(phases):
        best = min(r["phase_s"][i] for r in fixed.values())
        checks[f"phase_{i}_{pname}_within_10pct"] = (
            ad["phase_s"][i] <= 1.10 * best)
    worst_e2e = max(r["end_to_end_s"] for r in fixed.values())
    checks["beats_worst_fixed_1p5x"] = (
        worst_e2e >= 1.5 * ad["end_to_end_s"])
    checks["parity_all"] = all(r["parity"] for r in results.values())
    checks["readbacks_sublinear"] = (
        ad["readbacks_paid"] <= 2 * chunks_per_phase * 2 // 4 + 2)
    for name, ok in checks.items():
        tag = "PASS" if ok else ("WARN(smoke)" if smoke else "FAIL")
        print(f"  {tag}: {name}")
    return {"rows": n_rows, "groups": len(uk), "results": results,
            "checks": checks}


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    H.add_common_args(p, iters=3)
    p.add_argument("--out", type=str, default=None)
    args = p.parse_args(argv)

    if args.smoke:
        cfg = ExecConfig(memory_rows=256, page_rows=32, fanin=4,
                         batch_rows=64)
        chunks_per_phase = 8
    else:
        cfg = ExecConfig(memory_rows=4096, page_rows=512, fanin=8,
                         batch_rows=1024)
        chunks_per_phase = 48
    report = {
        "bench": "adaptive",
        "cfg": {"memory_rows": cfg.memory_rows, "batch_rows": cfg.batch_rows,
                "fanin": cfg.fanin, "page_rows": cfg.page_rows},
        "chunks_per_phase": chunks_per_phase,
        "governor_interval": 4,
        "scenarios": {},
    }
    ok = True
    for order in ("uniq->dup", "dup->uniq"):
        res = bench_scenario(order, cfg, chunks_per_phase, args.backend,
                             args.smoke, iters=args.iters)
        report["scenarios"][order] = res
        ok &= all(res["checks"].values())
    H.write_json_report(report, out=args.out, smoke=args.smoke,
                        default_name="BENCH_adaptive.json")
    if not args.smoke and not ok:
        print("ACCEPTANCE FAILED")
        sys.exit(1)
    print("\nall scenarios done" + ("" if ok else " (smoke warnings)"))


if __name__ == "__main__":
    main()
