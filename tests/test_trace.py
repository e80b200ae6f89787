"""The engine's names in the profiler's trace (:mod:`repro.core.trace`):
the device scopes in the compiled programs' ``op_name`` metadata, and the
``repro.*`` host spans, with their ids and nesting, in a real trace."""
import glob
import os
import re
import subprocess
import sys
import tempfile
import textwrap

import jax
import numpy as np
import pytest

import repro
from repro.core import pipeline
from repro.core.trace import SCOPES
from repro.core.types import ExecConfig

CFG = ExecConfig(memory_rows=256, page_rows=32, fanin=4, batch_rows=64)
RNG = np.random.default_rng(13)


def scopes_in(hlo_text: str) -> set[str]:
    """The engine scopes that appear as a path segment of some op_name."""
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {seg for n in names for seg in n.split("/") if seg in SCOPES}


def test_one_shot_program_carries_its_scopes():
    keys = RNG.integers(0, 1200, 4096).astype(np.uint32)
    pay = RNG.normal(size=(4096, 1)).astype(np.float32)
    text = pipeline._pipeline_jit.lower(
        keys, pay, policy="rs", memory_rows=CFG.memory_rows,
        batch_rows=CFG.batch_rows, page_rows=CFG.page_rows,
        index_rows=CFG.memory_rows, fanin=CFG.fanin, premerge_levels=1,
        backend="xla", widths=None, merge=True,
    ).compile().as_text()
    assert scopes_in(text) == {"rungen", "premerge", "wide_merge"}


def test_absorb_and_finalize_programs_carry_their_scopes():
    agg = pipeline.StreamingAggregator(CFG, policy="rs", width=1)
    keys = RNG.integers(0, 1200, 1000).astype(np.uint32)
    pay = RNG.normal(size=(1000, 1)).astype(np.float32)
    staged = agg.stage(keys, pay)
    agg.absorb_staged(staged)
    absorb = pipeline._absorb_chunk.lower(
        agg._es, staged.bk, staged.bp, policy="rs",
        memory_rows=CFG.memory_rows, batch_rows=CFG.batch_rows,
        backend=agg.backend, widths=agg.widths,
        local_slots=agg._local_slots(staged.rows_padded),
    ).compile().as_text()
    assert scopes_in(absorb) == {"rungen"}
    pre, out_cap, trim = agg._merge_plan(bucketed=False)
    final = pipeline._finalize_stream.lower(
        agg._es, None, policy="rs", page_rows=CFG.page_rows,
        index_rows=agg.index_rows, fanin=CFG.fanin, premerge_levels=1,
        backend=agg.backend, out_capacity=out_cap, trim=trim,
    ).compile().as_text()
    assert scopes_in(final) == {"rungen", "premerge", "wide_merge"}


def test_mesh_program_carries_exchange_and_owner_merge():
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.dirname(__file__)!r})
        import jax, numpy as np
        from repro.core import pipeline
        from test_trace import CFG, scopes_in
        mesh = jax.make_mesh((4,), ("data",))
        keys = np.random.default_rng(0).integers(0, 3000, 8192)
        fn = pipeline._sharded_fn(
            mesh, "data", policy="rs", memory_rows=CFG.memory_rows,
            batch_rows=CFG.batch_rows, page_rows=CFG.page_rows,
            index_rows=CFG.memory_rows, fanin=CFG.fanin, premerge_levels=1,
            backend="xla", widths=(1, 1, 1))
        text = fn.lower(keys.astype(np.uint32),
                        np.ones((8192, 1), np.float32)).compile().as_text()
        print(sorted(scopes_in(text)))
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=560)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().splitlines()[-1] == str(sorted(SCOPES))


# ---------------------------------------------------------------------------
# host spans in a captured trace
# ---------------------------------------------------------------------------


def captured_spans(fn):
    """Run ``fn`` under ``jax.profiler.trace``; return its ``repro.*``
    host events as ``(name, start_ns, end_ns, args, thread)``."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            fn()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats), line.name))
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


def children(spans, parent):
    """Spans of ``parent``'s thread that lie inside it."""
    _, s, e, _, thread = parent
    return [c for c in spans if c is not parent and c[4] == thread
            and s <= c[1] and c[2] <= e]


def names_of(spans):
    return [(n, a.get("what")) for n, _, _, a, _ in spans]


def test_one_shot_aggregate_spans_nest_and_carry_their_query_id():
    by = repro.KeySpec.of(k=12)
    cols = {"k": RNG.integers(0, 4000, 3000)}
    vals = RNG.normal(size=3000).astype(np.float32)
    kw = dict(by=by, values=vals, aggs=("count", "sum"), cfg=CFG)
    repro.aggregate(cols, **kw)  # compile outside the trace

    def two_queries():
        for _ in range(2):
            jax.block_until_ready(repro.aggregate(cols, **kw).state)

    spans = captured_spans(two_queries)
    outer = [s for s in spans if s[0] == "repro.aggregate"]
    assert len(outer) == 2
    q0, q1 = (s[3]["query"] for s in outer)
    assert q1 == q0 + 1
    for q in outer:
        inner = children(spans, q)
        assert names_of(inner) == [
            ("repro.pack", None), ("repro.pad", None),
            ("repro.dispatch", None), ("repro.readback", "stats")]
    # every engine span of the two queries lies inside one of them
    assert len(spans) == 2 * 5


def test_session_spans_nest_and_carry_their_ids():
    by = repro.KeySpec.of(k=12)
    keys = RNG.integers(0, 4000, 3000)
    vals = RNG.normal(size=3000).astype(np.float32)

    def session():
        sess = repro.serve_aggregate(by=by, values="v", aggs=("count", "sum"),
                                     cfg=CFG, output_rows=4096)
        for lo in range(0, 3000, 1000):
            sess.ingest({"k": keys[lo:lo + 1000], "v": vals[lo:lo + 1000]})
        jax.block_until_ready(sess.snapshot().state)
        jax.block_until_ready(sess.close().state)

    session()  # compile outside the trace
    spans = captured_spans(session)
    ingests = [s for s in spans if s[0] == "repro.ingest"]
    assert [s[3]["chunk"] for s in ingests] == [0, 1, 2]
    staging = [("repro.pack", None), ("repro.pad", None),
               ("repro.place", None)]
    assert names_of(children(spans, ingests[0])) == staging
    # from the second chunk on, the previous chunk's absorb is dispatched
    for s in ingests[1:]:
        assert names_of(children(spans, s)) == staging + [
            ("repro.dispatch", None)]
    snap, = [s for s in spans if s[0] == "repro.snapshot"]
    assert snap[3]["snapshot"] == 0
    assert names_of(children(spans, snap)) == [
        ("repro.dispatch", None), ("repro.dispatch", None),
        ("repro.readback", "stats"), ("repro.readback", "occupancy")]
    close, = [s for s in spans if s[0] == "repro.close"]
    assert names_of(children(spans, close)) == [
        ("repro.dispatch", None), ("repro.readback", "stats")]
    # nothing outside the four kinds of outer span
    outer = ingests + [snap, close]
    assert sorted(spans) == sorted(
        outer + [c for o in outer for c in children(spans, o)])


@pytest.mark.parametrize("entry", ("snapshot", "finalize"))
def test_tracing_leaves_answers_unchanged(entry):
    """The same stream answers the same, stats included, with and without
    a trace running."""
    keys = RNG.integers(0, 500, 2000).astype(np.uint32)

    def run():
        agg = pipeline.StreamingAggregator(CFG, policy="rs", width=0)
        for lo in range(0, 2000, 500):
            agg.absorb(keys[lo:lo + 500])
        st, stats = getattr(agg, entry)()
        return np.asarray(st.keys), stats.as_dict()

    plain = run()
    traced = []
    captured_spans(lambda: traced.append(run()))
    np.testing.assert_array_equal(plain[0], traced[0][0])
    assert plain[1] == traced[0][1]
