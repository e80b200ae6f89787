"""Multi-device tests (8 fake CPU devices via subprocess — the main test
process must keep seeing 1 device, per the dry-run contract)."""
import os
import subprocess
import sys
import textwrap

import pytest

ENV = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH="src")


def run_py(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True, timeout=560,
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_distributed_groupby_matches_oracle():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.groupby import make_distributed_groupby
        from repro.core.types import EMPTY
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        n, o = 8 * 4096, 700
        keys = rng.integers(0, o, n).astype(np.uint32)
        pay = rng.normal(size=(n, 2)).astype(np.float32)
        gb = make_distributed_groupby(mesh, "data", capacity=4096)
        with mesh:
            st = gb(jnp.asarray(keys), jnp.asarray(pay))
        got_k = np.asarray(st.keys); valid = got_k != EMPTY
        got_k = got_k[valid]
        # global result: all unique keys exactly once, counts exact
        uk, cnt = np.unique(keys, return_counts=True)
        assert np.array_equal(np.sort(got_k), uk), (len(got_k), len(uk))
        got_c = np.asarray(st.count)[valid]
        order = np.argsort(got_k)
        assert np.array_equal(got_c[order], cnt)
        # each device's shard is sorted (distributed interesting ordering)
        print("distributed groupby OK", len(uk))
    """)


def test_distributed_groupby_overflow_fails_loudly():
    """Regression: the gather/fill path used to drop rows silently when a
    shard's received fragments exceeded ``capacity`` (or a send segment
    its per-peer quota).  It must fail loudly like the PR 3 wide merge —
    or hand back the device flag for jit-embedded callers."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.groupby import make_distributed_groupby
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        n = 8 * 4096
        keys = rng.integers(0, 700, n).astype(np.uint32)
        pay = rng.normal(size=(n, 2)).astype(np.float32)
        # capacity 256 < unique keys per range: fragments must overflow
        gb = make_distributed_groupby(mesh, "data", capacity=256)
        try:
            with mesh:
                gb(jnp.asarray(keys), jnp.asarray(pay))
            raise SystemExit("overflow did not raise")
        except RuntimeError as e:
            assert "dropped rows" in str(e), e
        # flag mode: same condition surfaces as a device scalar instead
        gb = make_distributed_groupby(mesh, "data", capacity=256,
                                      on_overflow="flag")
        with mesh:
            st, dropped = gb(jnp.asarray(keys), jnp.asarray(pay))
        assert bool(dropped)
        # generous capacity: no flag, exact oracle (unchanged behavior)
        gb = make_distributed_groupby(mesh, "data", capacity=4096,
                                      on_overflow="flag")
        with mesh:
            st, dropped = gb(jnp.asarray(keys), jnp.asarray(pay))
        assert not bool(dropped)
        # all-unique keys: the LOCAL aggregation trim (before any
        # exchange) is the loss site — must flag too
        uniq = np.arange(n, dtype=np.uint32)
        gb = make_distributed_groupby(mesh, "data", capacity=1024,
                                      on_overflow="flag")
        with mesh:
            st, dropped = gb(jnp.asarray(uniq), jnp.asarray(pay))
        assert bool(dropped)
        print("groupby loud overflow OK")
    """)


def test_ep_moe_grad_and_parity():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses as dc
        from repro.configs import get_config
        from repro.models import model as M, moe as MOE
        from repro.distributed import moe_parallel as MP
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        MP.set_current_mesh(mesh)
        # moe_chunk=64 exercises the chunked dispatch; the EP path computes
        # expert capacity PER CHUNK, so exact parity with the whole-batch
        # reference holds only when no chunk overflows its capacity ("equal
        # up to capacity drops").  cf=4 gives every 64-token chunk enough
        # headroom that nothing drops under this routing draw.
        cfg = dc.replace(get_config("qwen3-moe-30b-a3b", smoke=True),
                         mesh_axes=("data", "model"), moe_chunk=64)
        cfg = dc.replace(cfg, moe=dc.replace(cfg.moe, capacity_factor=4.0))
        p, _ = M.init(cfg, jax.random.PRNGKey(0))
        moe_p = jax.tree.map(lambda a: a[0], p["layers"])["moe"]
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 128, cfg.d_model),
                              jnp.float32)
        with mesh:
            y_ref, _ = MOE.moe_block(moe_p, cfg, x, dispatch="sorted")
            y_ep, _ = jax.jit(lambda pp, xx: MOE.moe_block(pp, cfg, xx,
                              dispatch="sorted_ep"))(moe_p, x)
            g = jax.jit(jax.grad(lambda pp, xx: MOE.moe_block(
                pp, cfg, xx, dispatch="sorted_ep")[0].sum()))(moe_p, x)
        assert float(jnp.abs(y_ref - y_ep).max()) < 1e-5
        assert all(bool(jnp.isfinite(a).all()) for a in jax.tree.leaves(g))
        print("EP MoE parity + grads OK")
    """)


def test_ring_collective_matmul():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.overlap import (ring_allgather_matmul,
                                               reference_allgather_matmul)
        mesh = jax.make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        x = rng.normal(size=(64, 128)).astype(np.float32)
        w = rng.normal(size=(128, 96)).astype(np.float32)
        with mesh:
            ring = jax.jit(ring_allgather_matmul(mesh))
            ref = jax.jit(reference_allgather_matmul(mesh))
            yr = ring(jnp.asarray(x), jnp.asarray(w))
            yref = ref(jnp.asarray(x), jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(yr), x @ w, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(yref), x @ w, rtol=1e-4, atol=1e-4)
        # the ring version contains collective-permute, not all-gather
        txt = jax.jit(ring_allgather_matmul(mesh)).lower(
            jax.ShapeDtypeStruct((64, 128), jnp.float32),
            jax.ShapeDtypeStruct((128, 96), jnp.float32)).compile().as_text()
        assert "collective-permute" in txt and "all-gather" not in txt
        print("ring collective matmul OK")
    """)


def test_sharded_train_step_runs_and_matches_single_device():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses as dc
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.launch import steps as ST
        from repro.distributed import sharding as SH
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg0 = get_config("llama3-8b", smoke=True)
        cfg = dc.replace(cfg0, mesh_axes=("data", "model"))
        step0, init0, opt = ST.make_train_step(cfg0, lr=1e-3)
        step1, init1, _ = ST.make_train_step(cfg, lr=1e-3)
        state0 = init0(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 64)),
                                       dtype=jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 64)),
                                       dtype=jnp.int32)}
        # single device
        s0, m0 = jax.jit(step0)(state0, batch)
        # sharded
        psh = ST.state_shardings(cfg, mesh, opt)
        bsh = {k: NamedSharding(mesh, SH.batch_spec(mesh, v.ndim))
               for k, v in batch.items()}
        with mesh:
            state1 = jax.device_put(init1(jax.random.PRNGKey(0)), psh)
            sb = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
            s1, m1 = jax.jit(step1, in_shardings=(psh, bsh),
                             out_shardings=(psh, None))(state1, sb)
        np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                                   rtol=2e-3)
        # parameters after one step agree
        l0 = jax.tree.leaves(s0.params)[0]
        l1 = jax.tree.leaves(s1.params)[0]
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   rtol=3e-2, atol=3e-3)
        print("sharded step parity OK", float(m0["loss"]), float(m1["loss"]))
    """)


def test_sparse_grad_compression_allreduce():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from repro.optim import compression as C
        mesh = jax.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        grads = rng.normal(size=(8, 1024)).astype(np.float32)

        def local(g):
            st = C.init_topk(g[0])
            out, _ = C.allreduce_topk(g[0], st, k=256, axis_name="data")
            return out[None]

        from jax import shard_map
        fn = shard_map(local, mesh=mesh, in_specs=(P("data", None),),
                       out_specs=P("data", None))
        with mesh:
            out = fn(jnp.asarray(grads))
        got = np.asarray(out)[0]
        # oracle: each index receives exactly the contributions of shards
        # where it made that shard's top-k (error feedback keeps the rest)
        want = np.zeros(1024, np.float32)
        for srow in grads:
            top = np.argsort(-np.abs(srow))[:256]
            want[top] += srow[top]
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        print("topk sparse allreduce OK")
    """)


def test_elastic_checkpoint_restore():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        mesh8 = jax.make_mesh((8,), ("data",))
        mesh4 = jax.make_mesh((4, 2), ("data", "model"))
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                "b": jnp.ones((8,), jnp.float32)}
        sh8 = {"w": NamedSharding(mesh8, P("data", None)),
               "b": NamedSharding(mesh8, P(None))}
        tree8 = jax.device_put(tree, sh8)
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d, keep=2)
            mgr.save(tree8, 10, extras={"loader": {"seed": 1, "step": 10}})
            # elastic: restore onto a DIFFERENT mesh/sharding
            sh4 = {"w": NamedSharding(mesh4, P("data", "model")),
                   "b": NamedSharding(mesh4, P("model"))}
            like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
            restored, manifest = mgr.restore(like, shardings=sh4)
            assert manifest["step"] == 10
            assert manifest["extras"]["loader"]["step"] == 10
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(tree["w"]))
            assert restored["w"].sharding == sh4["w"]
        print("elastic checkpoint OK")
    """)


def test_pipeline_parallel_matches_sequential():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import make_pipeline, bubble_fraction
        mesh = jax.make_mesh((4,), ("pod",))
        L, B, D = 8, 16, 32
        rng = np.random.default_rng(0)
        params = jnp.asarray(rng.normal(size=(L, D, D)).astype(np.float32)
                             / np.sqrt(D))
        x = jnp.asarray(rng.normal(size=(B, D)).astype(np.float32))

        def apply_layer(w, h):
            return jnp.tanh(h @ w)

        pipe = make_pipeline(mesh, apply_layer, L, microbatches=4)
        with mesh:
            y = jax.jit(pipe)(params, x)
            g = jax.jit(jax.grad(lambda p, xx: pipe(p, xx).sum()))(params, x)
        ref = np.asarray(x)
        for l in range(L):
            ref = np.tanh(ref @ np.asarray(params[l]))
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-5)
        assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).sum()) > 0
        assert abs(bubble_fraction(4, 4) - 3/7) < 1e-9
        print("pipeline parallel OK")
    """)
