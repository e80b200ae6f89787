"""The trace reduction and the table of peaks, on a synthesised trace."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import peaks, trace  # noqa: E402
from chipbench.trace import Device, Trace  # noqa: E402


def _trace():
    # one device, a 1000 ns window; ops a and b overlap, c is nested in d
    dev = Device(
        ops=[("a", 100, 200), ("b", 150, 250), ("d", 400, 500),
             ("c", 420, 480), ("outside", 1100, 1200)],
        modules=[("jit__absorb_chunk_body(7)", 100, 250),
                 ("jit__finalize_stream_body(9)", 400, 500)])
    host = {"python": [
        ("chipbench.window", 0, 1000),
        ("chipbench.query", 0, 600),
        ("PjitFunction(_pipeline_body)", 260, 390),
        ("chipbench.ingest", 600, 1000),
    ]}
    return Trace(devices=[dev], host=host)


def test_busy_is_the_union_of_op_intervals():
    s = trace.reduce(_trace())
    # [100, 250] and [400, 500]; the op after the window is left out
    assert s.busy_s == pytest.approx(250e-9)
    assert s.window_s == pytest.approx(1000e-9)


def test_idle_share():
    s = trace.reduce(_trace())
    assert s.idle_share == pytest.approx(0.75)


def test_busy_and_op_time_are_means_over_devices():
    t = _trace()
    t.devices.append(Device(ops=[("a", 0, 1000)], modules=[]))
    s = trace.reduce(t)
    assert s.busy_s == pytest.approx((250 + 1000) / 2 * 1e-9)
    assert s.op_seconds("a") == pytest.approx((100 + 1000) / 2 * 1e-9)


def test_device_time_per_module_and_op():
    s = trace.reduce(_trace())
    assert s.module_seconds("_absorb_chunk") == pytest.approx(150e-9)
    assert s.module_seconds("_finalize_stream") == pytest.approx(100e-9)
    assert s.module_seconds("_no_such_program") is None
    assert s.op_seconds("d") == pytest.approx(100e-9)
    assert trace.top_ops(s, 2)[0][0] in ("a", "b", "d")


def test_gaps_are_named_by_the_host_span_open_during_them():
    s = trace.reduce(_trace())
    names = dict((round(sec * 1e9), name) for name, sec in s.gaps)
    assert names[500] == "chipbench.ingest"  # [500, 1000]
    assert names[150] == "chipbench.query > PjitFunction(_pipeline_body)"
    assert names[100] == "chipbench.query"  # [0, 100]
    assert [sec for _, sec in s.gaps] == sorted(
        (sec for _, sec in s.gaps), reverse=True)


def test_benchmark_spans_are_counted():
    s = trace.reduce(_trace())
    assert s.spans["chipbench.query"] == (1, pytest.approx(600e-9))
    assert "chipbench.window" not in s.spans


def test_a_trace_without_the_window_span_or_a_device_is_refused():
    t = _trace()
    t.host["python"] = t.host["python"][1:]
    with pytest.raises(ValueError, match="window"):
        trace.reduce(t)
    with pytest.raises(ValueError, match="device"):
        trace.reduce(Trace(devices=[], host=_trace().host))


def test_union():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("chipbench.query"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(str(tmp_path))
    names = {n for events in t.host.values() for n, _, _ in events}
    assert {"chipbench.query", trace.WINDOW_SPAN} <= names
    lo, hi = trace.window_bounds(t)
    assert hi > lo


def test_ops_are_named_by_op_and_module():
    ops = [("%while.168 = (s32[]) while(s32[] %t), body=%region_0", 10, 20),
           ("%fusion.1 = u32[4] fusion(u32[4] %a)", 40, 50)]
    mods = [("jit__pipeline_body(123)", 0, 30)]
    assert trace.name_ops(ops, mods) == [
        ("jit__pipeline_body/while.168", 10, 20), ("?/fusion.1", 40, 50)]
    # a collective's name and opcode differ: the opcode is kept
    hlo = ("%all_to_all.21 = f32[4,524288,1]{1,2,0:T(1,128)} all-to-all("
           "%compare_select_fusion.478), channel_id=1, dimensions={0}")
    assert trace.short_op(hlo) == "all_to_all.21 (all-to-all)"
    assert trace.short_op("%fusion.248 = s32[8]{0:T(1024)S(1)} fusion(%a)") \
        == "fusion.248"
