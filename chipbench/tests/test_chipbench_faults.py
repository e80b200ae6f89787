"""``correct`` comes out false for the control and for each fault a cell
can have: the rest of a run is driven at a tiny scale on the CPU, with
the look for a chip skipped and the timed path broken underneath."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]

from chipbench import harness, query, reference  # noqa: E402
from chipbench.traffic_common import Answer  # noqa: E402
from test_chipbench_harness import SEED, rehearse  # noqa: E402

import repro  # noqa: E402
from repro.core import pipeline  # noqa: E402
from repro.service import session  # noqa: E402

ONE_CHIP = ["q18_agg.batch", "q1.batch", "q18_agg.stream"]


@pytest.fixture(autouse=True)
def fresh_programs():
    """A patched engine function must be traced anew, and must not leak."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("config", ["tpch_q18_agg", "tpch_q1"])
def test_the_control_is_not_correct(config):
    """The reference in bfloat16 value planes, in the program's place."""
    cfg = dict(harness._json("configs", config), scale_factor=0.01)
    q = query.build(cfg, SEED)
    control = harness.expected(q, q.rows, control=True)
    worst, failed, _, _ = harness.check(
        q, [Answer(attempt=0, label="control", rows=q.rows, relation=control)],
        cfg["limits"])
    assert failed == {0}
    assert worst["sum_rel"] > cfg["limits"]["sum_rel"]


def _unchanged(es, ck, cp, **kw):
    return es


def _half(step):
    def half(es, ck, cp, **kw):
        keep = jnp.arange(ck.shape[-1]) < ck.shape[-1] // 2
        empty = np.array(np.iinfo(ck.dtype).max, ck.dtype)
        return step(es, jnp.where(keep, ck, empty), cp, **kw)
    return half


@pytest.mark.parametrize("name", ONE_CHIP)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_a_broken_step_is_not_correct(name, fault, monkeypatch):
    step = pipeline._engine_step
    monkeypatch.setattr(pipeline, "_engine_step",
                        _unchanged if fault == "state_unchanged" else _half(step))
    out = rehearse(name)
    assert not out["correct"] and out["failed"] == out["attempted"]


def _altered(result):
    s = result.state
    return dataclasses.replace(
        result, state=dataclasses.replace(s, count=s.count.at[0].add(1)))


@pytest.mark.parametrize("name", ONE_CHIP)
def test_an_answer_altered_where_it_is_produced_is_not_correct(name, monkeypatch):
    if name.endswith(".stream"):
        close = session.AggregationSession.close
        monkeypatch.setattr(session.AggregationSession, "close",
                            lambda self: _altered(close(self)))
    else:
        aggregate = repro.aggregate
        monkeypatch.setattr(repro, "aggregate",
                            lambda *a, **k: _altered(aggregate(*a, **k)))
    out = rehearse(name)
    assert not out["correct"] and out["failed"] == out["attempted"]
    assert out["checks"]["counts"]["value"] > 0


def test_the_exchange_left_out_is_not_correct():
    code = f"""
import sys, json
sys.path[:0] = [{ROOT!r}, {HERE!r}]
import jax.numpy as jnp
import numpy as np
from repro.distributed import groupby
import test_chipbench_harness as t

def no_exchange(st, axis, world, *, quota=None, **kw):
    zero = jnp.int32(0)
    return st, groupby.ExchangeInfo(zero, jnp.bool_(False), zero,
                                    jnp.bool_(False), quota or 1)

groupby.exchange_and_merge = no_exchange
print(json.dumps(t.rehearse("q18_agg_4chip.batch")))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert not out["correct"]
    assert out["checks"]["order"]["value"] > 0
