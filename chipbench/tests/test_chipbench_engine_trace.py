"""The engine-name reduction (chipbench/engine_trace.py) on a synthesised
trace whose values are computed by hand, and its profile of a cell
rehearsed on the CPU over that trace."""
import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import engine_trace, trace  # noqa: E402

MS = 10**6


def fake_trace(_dir=None):
    """Two devices over a 1 s window.  Device 0: ops [0,100], [200,400]
    (holding [300,350]) and [600,900] ms, the pipeline module over
    [0,400] and the absorb module over [600,900]; device 1 busy all the
    window in the pipeline module.  Host spans: repro.aggregate [0,500]
    holding repro.pack [0,50] and repro.readback [100,450];
    repro.snapshot [550,950]."""
    dev0 = trace.Device(
        ops=[("jit__pipeline_body/a", 0, 100 * MS),
             ("jit__pipeline_body/b", 200 * MS, 400 * MS),
             ("jit__pipeline_body/c", 300 * MS, 350 * MS),
             ("jit__absorb_chunk_body/d", 600 * MS, 900 * MS)],
        modules=[("jit__pipeline_body(1)", 0, 400 * MS),
                 ("jit__absorb_chunk_body(2)", 600 * MS, 900 * MS)])
    dev1 = trace.Device(ops=[("jit__pipeline_body/a", 0, 1000 * MS)],
                        modules=[("jit__pipeline_body(1)", 0, 1000 * MS)])
    host = {"python": [
        (trace.WINDOW_SPAN, 0, 1000 * MS),
        ("chipbench.query", 0, 500 * MS),
        ("repro.aggregate", 0, 500 * MS),
        ("repro.pack", 0, 50 * MS),
        ("repro.readback", 100 * MS, 450 * MS),
        ("repro.snapshot", 550 * MS, 950 * MS),
        ("array.py:631 _value", 120 * MS, 440 * MS)]}
    return trace.Trace(devices=[dev0, dev1], host=host)


SCOPED = [
    [("rungen", "jit__pipeline_body/a", 0, 100 * MS),
     ("wide_merge", "jit__pipeline_body/b", 200 * MS, 400 * MS),
     ("wide_merge", "jit__pipeline_body/c", 300 * MS, 350 * MS),
     ("rungen", "jit__absorb_chunk_body/d", 600 * MS, 900 * MS)],
    [],
]


def fake_scoped(_dir=None):
    return copy.deepcopy(SCOPED)


def test_scope_of_reads_a_path_segment():
    assert engine_trace.scope_of("jit(_pipeline_body)/rungen/while") == "rungen"
    assert engine_trace.scope_of(
        "jit(run)/shard_map/exchange/all_to_all") == "exchange"
    assert engine_trace.scope_of("jit(f)/rungen_like/while") is None


def _pb(*fields) -> bytes:
    """A protobuf message from ``(field number, int | bytes | str)``."""
    def varint(x):
        out = b""
        while True:
            out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
            x >>= 7
            if not x:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _device_plane(events: dict[str, list[tuple[str, int, int]]]) -> bytes:
    """An XPlane ``/device:TPU:0`` holding the given lines of
    ``(name, offset_ps, duration_ps)`` events."""
    names, lines = {}, []
    for line_id, (line, evs) in enumerate(events.items(), 1):
        xevents = [(4, _pb((1, names.setdefault(n, len(names) + 1)),
                           (2, off), (3, dur)))
                   for n, off, dur in evs]
        lines.append((3, _pb((1, line_id), (2, line), (3, 1000), *xevents)))
    md = [(4, _pb((1, i), (2, _pb((1, i), (2, n))))) for n, i in names.items()]
    return _pb((1, 7), (2, "/device:TPU:0"), *lines, *md)


def test_scopes_come_from_the_hlo_the_trace_carries(tmp_path):
    """A real CPU trace of a one-shot query holds its program's HloProto;
    a device plane naming that program's ops gets their scopes."""
    import glob

    import jax
    import numpy as np

    import repro
    from repro.core.types import ExecConfig

    rng = np.random.default_rng(0)
    kw = dict(by=repro.KeySpec.of(k=12),
              values=rng.normal(size=3000).astype(np.float32),
              aggs=("count", "sum"),
              cfg=ExecConfig(memory_rows=256, page_rows=32, fanin=4,
                             batch_rows=64))
    cols = {"k": rng.integers(0, 4000, 3000)}
    jax.block_until_ready(repro.aggregate(cols, **kw).state)
    with jax.profiler.trace(str(tmp_path / "cpu")):
        jax.block_until_ready(repro.aggregate(cols, **kw).state)
    path, = glob.glob(str(tmp_path / "cpu" / "**" / "*.xplane.pb"),
                      recursive=True)
    raw = open(path, "rb").read()
    programs = engine_trace.program_op_names(raw)
    module, = [m for m in programs
               if trace.short_module(m) == "jit__pipeline_body"]
    by_scope = {}
    for op, op_name in programs[module].items():
        by_scope.setdefault(engine_trace.scope_of(op_name), op)
    assert {"rungen", "wide_merge"} <= set(by_scope)

    plane = _device_plane({
        "XLA Modules": [(module, 0, 10**9)],
        "XLA Ops": [(f"%{by_scope['rungen']} = s32[] while()", 0, 4 * 10**8),
                    (f"%{by_scope['wide_merge']} = s32[] while()",
                     5 * 10**8, 10**8),
                    ("%unknown.1 = s32[] copy()", 7 * 10**8, 10**8)]})
    out = tmp_path / "tpu" / "x.xplane.pb"
    out.parent.mkdir()
    out.write_bytes(raw + _pb((1, plane)))
    (ops,) = engine_trace.load_scoped(str(tmp_path / "tpu"))
    assert [(sc, name.split("/")[0], s, e) for sc, name, s, e in ops] == [
        ("rungen", "jit__pipeline_body", 1000, 1000 + 400 * MS // 1000),
        ("wide_merge", "jit__pipeline_body", 1000 + 500 * MS // 1000,
         1000 + 600 * MS // 1000)]


def test_engine_reduction_reads_the_hand_computed_values():
    eng = engine_trace.reduce_engine(fake_trace(), fake_scoped())
    # union per scope on device 0 (device 1 has none), mean over 2 devices
    assert eng.scope_s == pytest.approx({"rungen": 0.4 / 2,
                                         "wide_merge": 0.2 / 2})
    assert set(eng.engine_spans) == {"repro.aggregate", "repro.pack",
                                     "repro.readback", "repro.snapshot"}
    for name, (count, s) in {"repro.aggregate": (1, 0.5),
                             "repro.pack": (1, 0.05),
                             "repro.readback": (1, 0.35),
                             "repro.snapshot": (1, 0.4)}.items():
        assert eng.engine_spans[name][0] == count
        assert eng.engine_spans[name][1] == pytest.approx(s)
    # device 0 idles [100,200], [400,600], [900,1000]; device 1 never
    assert eng.idle_in == pytest.approx({
        "repro.readback": (0.1 + 0.05) / 2,
        "repro.aggregate": 0.05 / 2,
        "repro.snapshot": (0.05 + 0.05) / 2})
    assert eng.module_in_span["repro.aggregate"] == pytest.approx(
        {"jit__pipeline_body": (0.4 + 0.5) / 2})
    assert eng.module_in_span["repro.snapshot"] == pytest.approx(
        {"jit__absorb_chunk_body": 0.3 / 2, "jit__pipeline_body": 0.4 / 2})
    assert eng.op_scope["jit__pipeline_body/b"] == "wide_merge"


def test_layer_metrics_of_a_batch_and_a_stream_window():
    eng = engine_trace.reduce_engine(fake_trace(), fake_scoped())
    assert engine_trace.layer_metrics(eng, {"queries": 2}) == pytest.approx({
        "rungen.device_s": 0.2 / 2,
        "merge.device_s": 0.1 / 2,
        "front_door.host_ms": 1e3 * 0.05 / 2,
        "front_door.device_idle_ms": 1e3 * 0.15 / 2})
    assert engine_trace.layer_metrics(
        eng, {"chunks": 2, "answers": 1}) == pytest.approx({
            "ingest.stage_ms_per_chunk": 1e3 * 0.05 / 2,
            "snapshot.backlog_s": 0.15})


def test_the_benchmark_summary_is_unchanged_beside_it():
    tr = fake_trace()
    before = trace.reduce(tr)
    engine_trace.reduce_engine(tr, fake_scoped())
    assert trace.reduce(tr) == before
    assert before.busy_s == pytest.approx((0.6 + 1.0) / 2)
    assert before.spans == {"chipbench.query": (1, pytest.approx(0.5))}


@pytest.mark.parametrize("cell", ["q18_agg.batch", "q18_agg.stream"])
def test_profile_of_a_cell_rehearsed_on_the_cpu(cell, monkeypatch):
    import test_chipbench_harness as t
    from chipbench import harness

    monkeypatch.setattr(engine_trace.trace_mod, "load", fake_trace)
    monkeypatch.setattr(engine_trace, "load_scoped", fake_scoped)
    monkeypatch.setattr(engine_trace, "span_cost_ns",
                        lambda: {"profiler_off": 1.0, "profiler_on": 2.0})
    out = engine_trace.profile(
        cell, t.SEED, 0.01, require_chip=False,
        config_overrides=t.tiny_config(harness.load_cell(cell)),
        params_overrides=t.TINY_PARAMS.get(cell))
    assert out["device"]["platform"] == "cpu"
    assert out["plain"].keys() == out["traced"].keys()
    want = ({"rungen.device_s", "merge.device_s", "front_door.host_ms",
             "front_door.device_idle_ms"} if cell.endswith("batch")
            else {"ingest.stage_ms_per_chunk", "snapshot.backlog_s"})
    assert set(out["layer_metrics"]) == want
    assert out["top_ops"][0][2] in ("rungen", "wide_merge", None)
