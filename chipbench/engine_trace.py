#!/usr/bin/env python3
"""The engine's own layer names in a profiler trace.

The program names its stages on the device with ``jax.named_scope``
(``repro.core.trace.SCOPES``, carried in each op's ``op_name``) and its
host work with ``repro.*`` spans (``repro.core.trace.span``), all in the
one trace that :mod:`chipbench.trace` reduces.  This module reads them on
that clock and adds, beside :class:`chipbench.trace.Summary` (which it
leaves as it is):

* ``scope_s``: per scope, the union of the intervals of the ops whose
  ``op_name`` holds the scope as a path segment, inside the window, the
  mean over devices (a ``while`` op counts once, its body's ops under it);
* ``engine_spans``: per ``repro.*`` span name, (count, seconds) inside
  the window;
* ``idle_in``: device idle seconds inside the window, the mean over
  devices, per innermost ``repro.*`` span open at the time (idle time
  outside every ``repro.*`` span is not counted);
* ``module_in_span``: per outermost ``repro.*`` span name, the device
  seconds of each module (``XLA Modules``, short name) while such a span
  is open, the mean over devices.

:func:`layer_metrics` turns them into the per-layer numbers a window
gives.  Run as a script, this profiles one cell's window and prints them
with the device split by scope, the top ops with their scopes, and what
a ``repro.*`` span costs with the profiler off and on:

    python3 chipbench/engine_trace.py --workload <cell> --seed <n> --seconds <s>
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import sys
from collections import defaultdict

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
    # where none is set, the compile cache chipbench/run.py keeps
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(_ROOT, ".jax_cache"))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"

from chipbench import trace as trace_mod  # noqa: E402

# the program's scope and span names (``repro.core.trace``)
SCOPES = ("rungen", "premerge", "wide_merge", "exchange", "owner_merge")
ENGINE_PREFIX = "repro."


def scope_of(op_name: str) -> str | None:
    """The engine scope that is a path segment of ``op_name``, if any."""
    for seg in op_name.split("/"):
        if seg in SCOPES:
            return seg
    return None


# -- the op_name of each op, from the HLO the trace carries ---------------
#
# Device op events carry no op_name.  The profiler stores each loaded
# program's HloProto in the ``/host:metadata`` plane, under an event
# metadata named like the ``XLA Modules`` events (``jit_f(<id>)``);
# ``ProfileData`` does not expose it, so it is read from the protobuf
# wire format here (field numbers of tsl's xplane.proto and xla's
# hlo.proto / xla_data.proto).


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint,
    a memoryview for a length-delimited field, raw bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, value


def _first(buf, number):
    return next((v for num, v in _fields(buf) if num == number), None)


def _text(buf) -> str:
    return "" if buf is None else bytes(buf).decode()


def hlo_op_names(hlo_proto) -> dict[str, str]:
    """Instruction name -> ``op_name`` of a serialized HloProto
    (hlo_module 1 > computations 3 > instructions 2 > name 1,
    metadata 7 > op_name 2)."""
    out = {}
    for num, comp in _fields(_first(hlo_proto, 1) or b""):
        if num != 3:
            continue
        for num2, instr in _fields(comp):
            if num2 != 2:
                continue
            name = op_name = ""
            for num3, v in _fields(instr):
                if num3 == 1:
                    name = _text(v)
                elif num3 == 7:
                    op_name = _text(_first(v, 2))
            if name and op_name:
                out[name] = op_name
    return out


def program_op_names(xspace) -> dict[str, dict[str, str]]:
    """Per program, by the name its ``XLA Modules`` events carry
    (``jit__pipeline_body(<id>)``): instruction name -> ``op_name``.
    XSpace planes 1 > XPlane name 2, event_metadata 4 and stat_metadata
    5 (map entries: value 2); XEventMetadata name 2, stats 5; XStat
    metadata_id 1, bytes_value 6; XStatMetadata id 1, name 2."""
    out = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1 or _text(_first(plane, 2)) != "/host:metadata":
            continue
        stat_names, programs = {}, []
        for num2, entry in _fields(plane):
            if num2 == 5:
                value = _first(entry, 2)
                stat_names[_first(value, 1)] = _text(_first(value, 2))
            elif num2 == 4:
                programs.append(_first(entry, 2))
        for md in programs:
            for num3, stat in _fields(md):
                if (num3 == 5
                        and stat_names.get(_first(stat, 1)) == "Hlo Proto"):
                    out[_text(_first(md, 2))] = hlo_op_names(
                        _first(stat, 6) or b"")
    return out


def _scope_in(programs: dict[str, dict[str, str]], module: str, op: str):
    """The scope of ``op`` in the program named ``module``; a program
    found by its short name alone counts where every program of that
    name agrees."""
    if module in programs:
        return scope_of(programs[module].get(op, ""))
    short = trace_mod.short_module(module)
    scopes = {scope_of(names.get(op, "")) for name, names in programs.items()
              if trace_mod.short_module(name) == short}
    return scopes.pop() if len(scopes) == 1 else None


def load_scoped(trace_dir: str) -> list[list[tuple[str, str, int, int]]]:
    """Per device (in index order), its ops of an engine scope as
    ``(scope, "<module>/<op>", start_ns, end_ns)`` from the newest
    ``.xplane.pb`` under ``trace_dir``, the file :func:`trace.load`
    reads."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        raw = f.read()
    programs = program_op_names(raw)
    devices = {}
    prefix = trace_mod.DEVICE_PLANE_PREFIX
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith(prefix):
            continue
        lines = {line.name: trace_mod._events(line) for line in plane.lines}
        mods = sorted((s, e, n) for n, s, e in
                      lines.get(trace_mod.MODULES_LINE, []))
        starts = [s for s, _, _ in mods]
        ops = lines.get(trace_mod.OPS_LINE, [])
        scoped = []
        for name, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue
            op = name.partition(" = ")[0].lstrip("%")
            scope = _scope_in(programs, mods[i][2], op)
            if scope:
                module = trace_mod.short_module(mods[i][2])
                scoped.append((scope, f"{module}/{trace_mod.short_op(name)}",
                               s, e))
        devices[int(plane.name[len(prefix):])] = scoped
    return [devices[i] for i in sorted(devices)]


@dataclasses.dataclass
class EngineSummary:
    scope_s: dict[str, float]
    engine_spans: dict[str, tuple[int, float]]
    idle_in: dict[str, float]
    module_in_span: dict[str, dict[str, float]]
    op_scope: dict[str, str]       # "<module>/<op>" -> its scope


def _engine_spans(trace):
    """``(start, end, name, thread)`` of every ``repro.*`` host span."""
    return sorted((s, e, n, t) for t, events in trace.host.items()
                  for n, s, e in events if n.startswith(ENGINE_PREFIX))


def innermost(spans) -> list[tuple[int, int, str]]:
    """Disjoint ``(start, end, name)`` pieces of time, each with the
    innermost (latest started) span open over it."""
    points = sorted({x for s, e, *_ in spans for x in (s, e)})
    out = []
    for lo, hi in zip(points, points[1:]):
        open_ = [(s, -e, n) for s, e, n, *_ in spans if s <= lo and hi <= e]
        if open_:
            out.append((lo, hi, max(open_)[2]))
    return out


def outermost(spans) -> list[tuple[int, int, str]]:
    """Spans that lie inside no other span of their thread."""
    return [(s, e, n) for s, e, n, t in spans
            if not any(t2 == t and s2 <= s and e <= e2 and (s2, e2) != (s, e)
                       for s2, e2, _, t2 in spans)]


def overlap(a, b):
    """Per name of ``b``: the length of its overlap with the disjoint
    sorted intervals ``a`` (``(start, end)``); ``b`` holds
    ``(start, end, name)``."""
    ends = [e for _, e in a]
    out = defaultdict(int)
    for s2, e2, name in b:
        i = bisect.bisect_right(ends, s2)
        while i < len(a) and a[i][0] < e2:
            out[name] += min(a[i][1], e2) - max(a[i][0], s2)
            i += 1
    return out


def reduce_engine(trace: trace_mod.Trace, scoped) -> EngineSummary:
    lo, hi = trace_mod.window_bounds(trace)
    n = len(trace.devices)
    spans = [(max(s, lo), min(e, hi), name, t)
             for s, e, name, t in _engine_spans(trace) if e > lo and s < hi]
    engine_spans = defaultdict(lambda: [0, 0.0])
    for s, e, name, _ in spans:
        engine_spans[name][0] += 1
        engine_spans[name][1] += (e - s) / 1e9
    inner = innermost(spans)
    outer = sorted(outermost(spans))
    scope_s, idle_in = defaultdict(float), defaultdict(float)
    module_in_span = defaultdict(lambda: defaultdict(float))
    op_scope = {}
    for dev, ops in zip(trace.devices, scoped):
        for scope in {sc for sc, *_ in ops}:
            ivs = trace_mod.union((max(s, lo), min(e, hi))
                                  for sc, _, s, e in ops
                                  if sc == scope and e > lo and s < hi)
            scope_s[scope] += sum(e - s for s, e in ivs) / 1e9 / n
        op_scope.update((name, sc) for sc, name, _, _ in ops)
        busy = trace_mod.union((s, e) for _, s, e in
                               trace_mod._clip(dev.ops, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        for name, ns in overlap(idle, inner).items():
            idle_in[name] += ns / 1e9 / n
        for mod, s, e in trace_mod._clip(dev.modules, lo, hi):
            mod = trace_mod.short_module(mod)
            for name, ns in overlap([(s, e)], outer).items():
                module_in_span[name][mod] += ns / 1e9 / n
    return EngineSummary(
        scope_s=dict(scope_s),
        engine_spans={k: (c, s) for k, (c, s) in engine_spans.items()},
        idle_in=dict(idle_in),
        module_in_span={k: dict(v) for k, v in module_in_span.items()},
        op_scope=op_scope)


def _span_s(eng: EngineSummary, *names) -> float:
    return sum(eng.engine_spans.get(n, (0, 0.0))[1] for n in names)


def layer_metrics(eng: EngineSummary, counters: dict) -> dict[str, float]:
    """The per-layer numbers of one window: device seconds by scope per
    query, front-door host and idle milliseconds per query (batch), and
    staging milliseconds per chunk and absorb backlog seconds per answer
    (stream).  A number whose base the window lacks is left out."""
    out = {}
    queries = counters.get("queries")
    if queries:
        s = eng.scope_s.get
        if "rungen" in eng.scope_s:
            out["rungen.device_s"] = s("rungen") / queries
        if "premerge" in eng.scope_s or "wide_merge" in eng.scope_s:
            out["merge.device_s"] = (s("premerge", 0.0)
                                     + s("wide_merge", 0.0)) / queries
        if "exchange" in eng.scope_s or "owner_merge" in eng.scope_s:
            out["exchange.device_s"] = (s("exchange", 0.0)
                                        + s("owner_merge", 0.0)) / queries
        if eng.engine_spans:
            out["front_door.host_ms"] = 1e3 * _span_s(
                eng, "repro.pack", "repro.pad", "repro.place",
                "repro.dispatch") / queries
            out["front_door.device_idle_ms"] = (
                1e3 * sum(eng.idle_in.values()) / queries)
    chunks = counters.get("chunks")
    if chunks and eng.engine_spans:
        out["ingest.stage_ms_per_chunk"] = 1e3 * _span_s(
            eng, "repro.pack", "repro.pad", "repro.place") / chunks
    answers = counters.get("answers")
    if answers and eng.module_in_span:
        out["snapshot.backlog_s"] = sum(
            v for span in ("repro.snapshot", "repro.close")
            for mod, v in eng.module_in_span.get(span, {}).items()
            if "_absorb_chunk" in mod) / answers
    return out


# ---------------------------------------------------------------------------
# profiling one cell
# ---------------------------------------------------------------------------


def span_cost_ns(n: int = 100_000) -> dict[str, float]:
    """Nanoseconds per ``repro.*`` span entered and left, with an id
    argument, with the profiler off and then on."""
    import tempfile
    import time

    import jax

    from repro.core.trace import span

    def loop():
        t = time.perf_counter_ns()
        for i in range(n):
            with span("repro.ingest", chunk=i):
                pass
        return (time.perf_counter_ns() - t) / n

    out = {"profiler_off": loop()}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        out["profiler_on"] = loop()
        jax.profiler.stop_trace()
    return out


def profile(cell_name: str, seed: int, seconds: float, *,
            require_chip: bool = True, config_overrides=None,
            params_overrides=None) -> dict:
    """One cell: warm up, an untraced window, then a traced one."""
    import shutil
    import tempfile

    import jax

    from chipbench import harness
    from chipbench import query as query_mod

    cell = harness.load_cell(cell_name)
    cell.config = {**cell.config, **(config_overrides or {})}
    params = {**cell.workload.get("params", {}), **(params_overrides or {})}
    chips = cell.entry["chips"]
    devs = harness.devices_for(chips, require_chip=require_chip)
    traffic_mod = harness.load_module("traffic", cell.workload["traffic"])
    query = query_mod.build(cell.config, seed)
    mesh = (jax.make_mesh((chips,), ("data",), devices=devs)
            if chips > 1 else None)

    def traffic():
        return traffic_mod.Traffic(query, params, mesh=mesh)

    traffic().warm()
    plain = traffic().window(seconds)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-engine-trace-")
    try:
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            traced = traffic().window(seconds)
        jax.profiler.stop_trace()
        trace = trace_mod.load(trace_dir)
        summary = trace_mod.reduce(trace)
        eng = reduce_engine(trace, load_scoped(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    top = trace_mod.top_ops(summary, 12)
    return {
        "cell": cell_name, "seed": seed,
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(jax.devices())},
        "plain": plain.metrics, "traced": traced.metrics,
        "counters": {k: v for k, v in traced.counters.items()
                     if isinstance(v, (int, float))},
        "window_s": summary.window_s, "busy_s": summary.busy_s,
        "scope_s": eng.scope_s, "idle_in": eng.idle_in,
        "engine_spans": eng.engine_spans,
        "module_in_span": eng.module_in_span,
        "layer_metrics": layer_metrics(eng, traced.counters),
        "top_ops": [[name, s, eng.op_scope.get(name)] for name, s in top],
        "idle_gaps": summary.gaps,
        "span_cost_ns": span_cost_ns(),
    }


def main(argv=None) -> int:
    import argparse
    import json

    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        out = profile(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"engine_trace: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
