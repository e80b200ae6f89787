#!/usr/bin/env python3
"""The readings that each limit of the comparison is set from.

    python3 chipbench/readings.py --workload <cell> --seeds <n> --first-seed <s>

In one process, for each of ``n`` seeds: make the cell's inputs, drive
the cell's own traffic through a short window (at least one whole query
or session, at the cell's own sizes, on the compiled programs of the
timed path), and compare every answer with the reference; then put the
control (the reference with bfloat16 value planes) in the program's place
for the same answers.  Prints one JSON line per seed, and last the lower
reading of each number (the largest the program gave) and the upper one
(the smallest the control gave).  Not part of a benchmark run.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

from chipbench import harness, query as query_mod  # noqa: E402
from chipbench.traffic_common import Answer  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args()
    import jax

    cell = harness.load_cell(args.workload)
    devs = harness.devices_for(cell.entry["chips"])
    mesh = (jax.make_mesh((len(devs),), ("data",), devices=devs)
            if len(devs) > 1 else None)
    traffic_mod = harness.load_module("traffic", cell.workload["traffic"])
    limits = cell.config["limits"]
    lower = dict.fromkeys(harness.CHECK_NAMES, 0.0)
    upper = dict.fromkeys(harness.CHECK_NAMES, float("inf"))
    for i in range(args.seeds):
        seed = args.first_seed + i
        q = query_mod.build(cell.config, seed)
        traffic = traffic_mod.Traffic(q, cell.workload.get("params", {}),
                                      mesh=mesh)
        window = traffic.window(args.seconds)
        answers = list(traffic.answers())
        got, failed, n, _ = harness.check(q, answers, limits)
        control = [Answer(a.attempt, a.label, a.rows,
                          harness.expected(q, a.rows, control=True))
                   for a in answers]
        ctl, ctl_failed, _, _ = harness.check(q, control, limits)
        for k in harness.CHECK_NAMES:
            lower[k] = max(lower[k], got[k])
            upper[k] = min(upper[k], ctl[k])
        print(json.dumps({"seed": seed, "answers": n,
                          "window_s": window.seconds,
                          "program": got, "program_failed": len(failed),
                          "control": ctl, "control_failed": len(ctl_failed)}),
              flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower": lower, "upper": upper, "limits": limits,
                      "device": devs[0].device_kind,
                      "seconds": time.perf_counter() - T0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
