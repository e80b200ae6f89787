"""The benchmark's harness, driven by data.

``BENCHMARK.json`` names the cells and metrics.  Everything that belongs
to one cell, configuration, traffic kind or per-layer metric is found by
name:

* ``chipbench/workloads/<cell>.json``: the cell's configuration, traffic
  kind and traffic parameters;
* ``chipbench/configs/<config>.json``: the deployment (data scale, query,
  ``ExecConfig``, chips, limits of the comparison);
* ``chipbench/traffic/<kind>.py``: the generator of one traffic kind, a
  class ``Traffic``;
* ``chipbench/metrics/<metric>.py``: the reader of one per-layer metric,
  a function ``read(ctx)`` that returns a number, or None when the run
  holds nothing to read.

A run: make the query's inputs from the seed, warm up the cell's shapes
(set-up), measure for ``--seconds`` (profiled with ``--trace 1``), read
the peak device memory, then compare every answer with the NumPy
reference and print one JSON line last on standard output.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

from chipbench import peaks as peaks_mod
from chipbench import query as query_mod
from chipbench import reference as ref_mod
from chipbench import trace as trace_mod

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHECK_NAMES = ("order", "keys", "counts", "sum_rel")


class NoChip(RuntimeError):
    pass


class UnknownCell(KeyError):
    pass


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict       # the cell's entry in BENCHMARK.json
    workload: dict    # chipbench/workloads/<cell>.json
    config: dict      # chipbench/configs/<config>.json
    end_to_end: list  # the manifest's end-to-end metrics this cell reports
    per_layer: list   # the manifest's per-layer metrics this cell reports


def _applies(metric: dict, cell: str, e2e_names=None) -> bool:
    """A metric with a ``workloads`` list is that list's; without one, an
    end-to-end metric is every cell's, and a per-layer metric is every
    cell's that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(name: str) -> Cell:
    manifest = load_manifest()
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise UnknownCell(f"no cell {name!r}; cells: {sorted(entries)}")
    entry = entries[name]
    workload = _json("workloads", name)
    for key in ("config", "traffic", "chips"):
        if workload[key] != entry[key]:
            raise ValueError(f"cell {name}: {key} is {entry[key]!r} in "
                             f"BENCHMARK.json, {workload[key]!r} in its file")
    config = _json("configs", entry["config"])
    if config["chips"] != entry["chips"]:
        raise ValueError(f"cell {name}: config {entry['config']} is for "
                         f"{config['chips']} chips, the cell for {entry['chips']}")
    e2e = [m for m in manifest["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"] if _applies(m, name, names)]
    return Cell(name, entry, workload, config, e2e, layer)


class CompileClock:
    """JAX's own compile events: the time spent tracing, lowering and
    compiling (or fetching from the persistent cache), as the union of
    their spans, and their count."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.spans = []
        jax.monitoring.register_event_time_span_listener(self._span)

    def _span(self, event, start, end, **_):
        if event in self.EVENTS:
            self.spans.append((start, end))

    def count(self) -> int:
        return len(self.spans)

    def seconds(self) -> float:
        return sum(e - s for s, e in trace_mod.union(self.spans))


def devices_for(chips: int, *, require_chip: bool = True):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def expected(query, rows: int, *, control: bool = False) -> ref_mod.Relation:
    """The reference (or the control) over the first ``rows`` rows."""
    fn = ref_mod.control if control else ref_mod.reference
    vals = None if query.values is None else query.values[:rows]
    want = fn([c[:rows] for c in query.key_cols], vals)
    aggs = query.config["aggs"]
    if "sum" not in aggs:
        want.sum = None
    if "avg" not in aggs:
        want.avg = None
    return want


def check(query, answers, limits: dict):
    """Compare every answer with the reference over its rows.

    Returns ``(numbers, failed_attempts, answers_compared, references)``: for each
    number of :func:`reference.compare`, the worst over the answers."""
    worst = dict.fromkeys(CHECK_NAMES, 0.0)
    failed, refs, n = set(), {}, 0
    for a in answers:
        if a.rows not in refs:
            refs[a.rows] = expected(query, a.rows)
        got = ref_mod.compare(a.relation, refs[a.rows])
        n += 1
        for k, v in got.items():
            worst[k] = max(worst[k], v)
        if any(got[k] > limits[k] for k in CHECK_NAMES):
            failed.add(a.attempt)
    return worst, failed, n, refs


@dataclasses.dataclass
class Context:
    """What a per-layer reader reads."""

    trace: trace_mod.Summary | None
    window: object      # traffic_common.Window
    query: object       # query.Query
    chips: int
    peaks: dict
    groups: int         # output rows of the whole table, by the reference


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t0: float | None = None, require_chip: bool = True,
        config_overrides: dict | None = None,
        params_overrides: dict | None = None, log=None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``require_chip=False`` and the overrides (keys replaced in the
    configuration or the traffic parameters, e.g. a tiny scale factor)
    are for rehearsals off the chip; the command line sets none of them."""
    t0 = time.perf_counter() if t0 is None else t0
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    cell = load_cell(cell_name)
    cell.config = {**cell.config, **(config_overrides or {})}
    cell.workload = {**cell.workload, "params": {
        **cell.workload.get("params", {}), **(params_overrides or {})}}
    chips = cell.entry["chips"]
    import jax

    devs = devices_for(chips, require_chip=require_chip)
    device_kind = devs[0].device_kind
    pk = peaks_mod.peaks(device_kind) if require_chip or trace else {}
    traffic_mod = load_module("traffic", cell.workload["traffic"])
    clock = CompileClock()

    query = query_mod.build(cell.config, seed)
    mesh = None
    if chips > 1:
        mesh = jax.make_mesh((chips,), ("data",), devices=devs)
    traffic = traffic_mod.Traffic(query, cell.workload.get("params", {}),
                                  mesh=mesh)
    traffic.warm()
    setup_s = time.perf_counter() - t0
    compiles_before = clock.count()
    log(f"chipbench: {cell_name} seed {seed}: {query.rows} rows, set-up "
        f"{setup_s:.3f} s ({clock.seconds():.3f} s compiling)")

    summary = None
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            window = traffic.window(seconds)
        if trace:
            jax.profiler.stop_trace()
            summary = trace_mod.reduce(trace_mod.load(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    compiles_in_window = clock.count() - compiles_before
    peak = memory_peak(devs)

    limits = cell.config["limits"]
    worst, failed, n_answers, refs = check(query, traffic.answers(), limits)
    correct = not failed and window.attempted > 0

    if trace:
        full = refs.get(query.rows) or expected(query, query.rows)
        ctx = Context(trace=summary, window=window, query=query, chips=chips,
                      peaks=pk, groups=len(full.count))
        metrics = {}
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        metrics = {}
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else window.metrics[m["name"]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": int(window.attempted),
           "failed": len(failed), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": trace_mod.top_ops(summary),
            "idle_gaps": [list(g) for g in summary.gaps]}
    out["window_s"] = window.seconds
    out["answers_compared"] = n_answers
    out["compiles_in_window"] = compiles_in_window
    out["checks"] = {k: {"value": worst[k], "limit": limits[k]}
                     for k in CHECK_NAMES}
    return out


def main(argv=None, *, t0: float | None = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t0=t0)
    except NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
