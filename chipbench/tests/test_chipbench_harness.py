"""A CPU rehearsal of the harness: every cell driven at a tiny scale
through the functions a chip run uses, with the look for a chip
skipped; the measuring entry itself still refuses to run off the chip."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, peaks, trace  # noqa: E402

TINY = {"scale_factor": 0.002,
        "exec_config": {"memory_rows": 1024, "batch_rows": 1024,
                        "page_rows": 128, "fanin": 8}}
TINY_PARAMS = {"q18_agg.stream": {"chunk_rows": 2048, "snapshot_every": 2}}
SEED = 2**31 + 99


def tiny_config(cell):
    cfg = dict(TINY)
    if cell.config["output_estimate"] > 4:
        cfg["output_estimate"] = 3000 * cell.config["chips"]
        cfg["scale_factor"] = TINY["scale_factor"] * cell.config["chips"]
    return cfg


def rehearse(name, *, trace_on=False, seconds=0.01):
    cell = harness.load_cell(name)
    return harness.run(name, SEED, seconds, trace_on, require_chip=False,
                       config_overrides=tiny_config(cell),
                       params_overrides=TINY_PARAMS.get(name),
                       log=lambda *a: None)


MANIFEST = harness.load_manifest()
ONE_CHIP = [w["name"] for w in MANIFEST["workloads"] if w["chips"] == 1]


def test_every_cell_traffic_and_metric_is_found_by_name():
    for w in MANIFEST["workloads"]:
        cell = harness.load_cell(w["name"])
        assert hasattr(harness.load_module("traffic", cell.workload["traffic"]),
                       "Traffic")
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
    for m in MANIFEST["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    for c in MANIFEST["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))


def test_an_unknown_cell_or_module_is_an_error():
    with pytest.raises(harness.UnknownCell):
        harness.load_cell("no_such.cell")
    with pytest.raises(FileNotFoundError):
        harness.load_module("traffic", "no_such_traffic")


def _fake_trace(_dir):
    ops = [("fusion.1", 0, 10**8), ("all-to-all.3", 10**8, 2 * 10**8)]
    mods = [("jit__absorb_chunk_body(1)", 0, 10**8),
            ("jit__finalize_stream_body(2)", 10**8, 2 * 10**8)]
    return trace.Trace(devices=[trace.Device(ops=ops, modules=mods)],
                       host={"python": [(trace.WINDOW_SPAN, 0, 10**9)]})


@pytest.mark.parametrize("name", ONE_CHIP)
def test_cpu_rehearsal_of_each_one_chip_cell(name, monkeypatch):
    out = rehearse(name)
    cell = harness.load_cell(name)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert out["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(harness.CHECK_NAMES)
    # the per-layer readers, over a synthesised trace of the same run
    monkeypatch.setattr(harness.trace_mod, "load", _fake_trace)
    monkeypatch.setattr(harness.peaks_mod, "peaks",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    out = rehearse(name, trace_on=True)
    assert out["correct"]
    assert set(out["metrics"]) == {m["name"] for m in cell.per_layer}
    assert out["device"]["window_s"] == pytest.approx(1.0)
    assert 0 < out["metrics"].get("query_roofline", {"value": 1})["value"] < 100
    assert out["breakdown"]["idle_gaps"]


def test_cpu_rehearsal_of_the_four_chip_cell():
    code = f"""
import sys, json
sys.path[:0] = [{ROOT!r}]
sys.path.insert(0, {os.path.dirname(__file__)!r})
import test_chipbench_harness as t
print(json.dumps(t.rehearse("q18_agg_4chip.batch")))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["device"]["count"] == 4


def _entry(args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_the_measuring_entry_refuses_to_run_without_a_chip():
    p = _entry(["--workload", "q18_agg.batch", "--seed", str(SEED),
                "--seconds", "1", "--trace", "0"])
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
    p = _entry(["--workload", "no_such.cell", "--seed", "1", "--seconds",
                "1", "--trace", "0"])
    assert p.returncode != 0 and p.stdout.strip() == ""
