"""Reduce a JAX profiler trace to the numbers the benchmark reports.

The profiler writes one ``.xplane.pb`` per traced window.  :func:`load`
turns it into a :class:`Trace`: per device, the ``XLA Ops`` and
``XLA Modules`` events, and the host threads' events (the benchmark's
own ``chipbench.*`` spans among them), all as ``(name, start_ns,
end_ns)`` on one clock.  :func:`reduce` works on that plain structure
alone, so it is tested on a synthesised trace:

* busy time is the union of a device's op intervals inside the window
  (nested or overlapping events count once), averaged over devices;
* the idle share is 1 − busy / window;
* device time per module and per op are sums of event durations whose
  name contains a given string, averaged over devices; an op is named
  ``<module>/<op>`` (``jit__pipeline_body/while.168``, with the opcode
  where the op's name differs from it), and nested ops
  (the body of a ``while``) count under their own names too;
* each idle gap of a device is named by the innermost benchmark span and
  the innermost other host event open at its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Device:
    ops: list[tuple[str, int, int]]
    modules: list[tuple[str, int, int]]


@dataclasses.dataclass
class Trace:
    """Events as ``(name, start_ns, end_ns)``.  ``host`` maps a host
    thread's name to its events."""

    devices: list[Device]
    host: dict[str, list[tuple[str, int, int]]]


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def short_op(hlo: str) -> str:
    """``%while.168 = (...) while(...), body=...`` -> ``while.168``; the
    opcode follows where the name does not start with it:
    ``%all_to_all.21 = f32[4,8]{...} all-to-all(...)`` ->
    ``all_to_all.21 (all-to-all)``."""
    name, _, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    m = _OPCODE.search(rest)
    if m and name.rsplit(".", 1)[0] != m.group(1):
        return f"{name} ({m.group(1)})"
    return name


def short_module(name: str) -> str:
    """``jit__pipeline_body(1398...)`` -> ``jit__pipeline_body``."""
    return name.split("(", 1)[0]


def name_ops(ops, modules):
    """Name each op ``<module>/<op>`` by the module running when it
    starts, so that ops of one name in different programs stay apart."""
    mods = sorted((s, e, short_module(n)) for n, s, e in modules)
    starts = [s for s, _, _ in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        out.append((f"{mod}/{short_op(name)}", s, e))
    return out


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a :class:`Trace`."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices, host = {}, {}
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            index = int(plane.name[len(DEVICE_PLANE_PREFIX):])
            mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
            ops = (name_ops(_events(lines[OPS_LINE]), mods)
                   if OPS_LINE in lines else [])
            devices[index] = Device(ops=ops or mods, modules=mods)
        elif plane.name == "/host:CPU":
            for name, line in lines.items():
                host[name] = _events(line)
    return Trace(devices=[devices[i] for i in sorted(devices)], host=host)


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(intervals) -> list[tuple[int, int]]:
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over devices
    module_s: dict[str, float]         # per module name, mean over devices
    op_s: dict[str, float]             # per op name, mean over devices
    gaps: list[tuple[str, float]]      # longest idle gaps, named
    spans: dict[str, tuple[int, float]]  # benchmark span -> (count, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, part: str) -> float | None:
        """Device seconds of modules whose name contains ``part``; None
        when no such module ran."""
        hits = [v for k, v in self.module_s.items() if part in k]
        return sum(hits) if hits else None

    def op_seconds(self, part: str) -> float | None:
        hits = [v for k, v in self.op_s.items() if part in k]
        return sum(hits) if hits else None


def window_bounds(trace: Trace) -> tuple[int, int]:
    """Start and end of the benchmark's window span."""
    for events in trace.host.values():
        for name, s, e in events:
            if name == WINDOW_SPAN:
                return s, e
    raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")


def _host_at(trace: Trace, t: int) -> str:
    """What the host was doing at ``t``: the innermost benchmark span and
    the innermost other event on the same thread."""
    best = None
    for events in trace.host.values():
        spans = [(s, e, n) for n, s, e in events
                 if s <= t < e and n.startswith(SPAN_PREFIX)
                 and n != WINDOW_SPAN]
        if not spans:
            continue
        span = max(spans)  # latest start = innermost
        others = [(s, e, n) for n, s, e in events
                  if s <= t < e and not n.startswith(SPAN_PREFIX)]
        inner = max(others)[2] if others else None
        best = span[2] + (f" > {inner}" if inner else "")
    return best or "no benchmark span"


def reduce(trace: Trace, *, top: int = 10) -> Summary:
    if not trace.devices:
        raise ValueError("trace holds no device plane")
    lo, hi = window_bounds(trace)
    n = len(trace.devices)
    busy = 0
    module_s, op_s = defaultdict(float), defaultdict(float)
    gaps = []
    for dev in trace.devices:
        ops = _clip(dev.ops, lo, hi)
        merged = union((s, e) for _, s, e in ops)
        busy += sum(e - s for s, e in merged)
        for name, s, e in ops:
            op_s[name] += (e - s) / 1e9 / n
        for name, s, e in _clip(dev.modules, lo, hi):
            module_s[name] += (e - s) / 1e9 / n
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) // 2))
    gaps.sort(reverse=True)
    named = [(_host_at(trace, mid), length / 1e9) for length, mid in gaps[:top]]
    spans = defaultdict(lambda: [0, 0.0])
    for events in trace.host.values():
        for name, s, e in _clip(events, lo, hi):
            if name.startswith(SPAN_PREFIX) and name != WINDOW_SPAN:
                spans[name][0] += 1
                spans[name][1] += (e - s) / 1e9
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
        module_s=dict(module_s), op_s=dict(op_s), gaps=named,
        spans={k: (c, s) for k, (c, s) in spans.items()})


def top_ops(summary: Summary, k: int = 10) -> list[list]:
    return [[name, s] for name, s in
            sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:k]]
