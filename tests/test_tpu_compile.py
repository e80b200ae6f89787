"""Compile the engine's main-path programs and the Pallas kernels for a
described TPU v5e, without a chip.

The TPU compiler runs here against a topology that is described, not
attached: it refuses what the chip would refuse (unsupported Mosaic
lowering, too much VMEM, a program over device memory), so these tests
guard the chip path at no chip time.  Nothing runs and nothing is timed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so each test worker must
collect the same tests and only the worker running this file loads it.
Sizes are small so each compile takes seconds; ``chip_smoke.py`` runs
the full geometry on the chip.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import pipeline as pl_
from repro.core.insort import plan_pre_merge_levels
from repro.core.types import ExecConfig, key_dtype_context
from repro.kernels import bitonic_sort, merge_path, segmented_reduce
from repro.kernels.ops import MAX_TILE_ROWS

CFG = ExecConfig(memory_rows=1024, batch_rows=256, page_rows=256, fanin=4)
N_ROWS = 16384
KEY_DTYPES = [np.uint32, np.uint64]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler plugin in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, sharding), tree)


def _compiles(lowered):
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem is not None
    return compiled


@pytest.mark.parametrize("kd", KEY_DTYPES, ids=lambda d: np.dtype(d).name)
def test_one_shot_pipeline_compiles_for_v5e(one_chip, kd):
    """The device pipeline repro.aggregate runs: rs run generation, one
    §4.3 pre-merge level, and the wide merge, in one program."""
    chunk = pl_._engine_geometry("rs", CFG.memory_rows, CFG.batch_rows,
                                 CFG.page_rows)[0]
    n_pad = pl_._num_batches(N_ROWS, chunk) * chunk
    pre = plan_pre_merge_levels(
        N_ROWS, CFG, pl_._static_run_slots("rs", N_ROWS, CFG.memory_rows,
                                           CFG.batch_rows))
    assert pre >= 1
    with key_dtype_context(kd):
        lowered = pl_._pipeline_jit.lower(
            _spec((n_pad,), kd, one_chip), _spec((n_pad, 1), np.float32, one_chip),
            policy="rs", memory_rows=CFG.memory_rows,
            batch_rows=CFG.batch_rows, page_rows=CFG.page_rows,
            index_rows=CFG.memory_rows, fanin=CFG.fanin, premerge_levels=pre,
            backend="xla", widths=(1, 0, 0), merge=True,
        )
        _compiles(lowered)


@pytest.mark.parametrize("kd", KEY_DTYPES, ids=lambda d: np.dtype(d).name)
def test_stream_absorb_step_compiles_for_v5e(one_chip, kd):
    """One streamed absorb step of repro.serve_aggregate's engine."""
    M, B, P = CFG.memory_rows, CFG.batch_rows, CFG.page_rows
    chunk_rows = 4 * M
    slots = pl_._stream_run_slots("rs", chunk_rows, M)
    with key_dtype_context(kd):
        state = jax.eval_shape(lambda: pl_._engine_init_body(
            policy="rs", memory_rows=M, batch_rows=B, page_rows=P,
            run_slots=slots, width=1, key_dtype=np.dtype(kd).name,
            widths=(1, 0, 0)))
        t = chunk_rows // B
        lowered = pl_._absorb_chunk.lower(
            _on(state, one_chip), _spec((t, B), kd, one_chip),
            _spec((t, B, 1), np.float32, one_chip),
            policy="rs", memory_rows=M, batch_rows=B, backend="xla",
            widths=(1, 0, 0), local_slots=slots, with_obs=False,
        )
        _compiles(lowered)


def _lanes(n_lanes, rows, sharding):
    return tuple(_spec((1, rows), np.uint32, sharding) for _ in range(n_lanes))


# (kernel, padded rows compiled here).  The sort network compiles in
# about 30 s at its 4096-row limit, so it is checked at 1024 rows here.
KERNEL_SIZES = [
    ("sort", 1024),
    ("scan", MAX_TILE_ROWS["scan"]),
    ("merge", MAX_TILE_ROWS["merge"]),
    ("probe", 1024),
]


@pytest.mark.parametrize("lanes", [1, 2], ids=["u32", "u64"])
@pytest.mark.parametrize("kernel,rows", KERNEL_SIZES,
                         ids=[k for k, _ in KERNEL_SIZES])
def test_pallas_kernel_compiles_for_v5e(one_chip, kernel, rows, lanes):
    """Mosaic accepts each kernel at one tile (interpret=False), for
    64-bit keys under the scoped x64 the uint64 engine traces with."""
    s = one_chip
    keys = _lanes(lanes, rows, s)
    if kernel == "sort":  # + the argsort index lane
        fn = lambda *k: bitonic_sort.bitonic_sort_multi(k, (), interpret=False)
        args = keys + (_spec((1, rows), np.uint32, s),)
    elif kernel == "scan":
        v = _spec((1, 1, rows), np.float32, s)
        fn = lambda *a: segmented_reduce.segmented_scan_tiles(
            a[:lanes], *a[lanes:], interpret=False)
        args = keys + (_spec((1, rows), np.int32, s), v, v, v)
    elif kernel == "merge":
        half = rows // 2
        side = _lanes(lanes, half, s) + (
            _spec((1, half), np.int32, s),) + (
            _spec((1, 1, half), np.float32, s),) * 3
        fn = lambda *a: merge_path.merge_path_tiles(
            a[:lanes], *a[lanes:lanes + 4], a[lanes + 4:2 * lanes + 4],
            *a[2 * lanes + 4:], interpret=False)
        args = side + side
    else:
        fn = lambda *a: merge_path.merge_path_probe_tiles(
            a[:lanes], a[lanes:], interpret=False)
        args = keys + keys
    with key_dtype_context(np.uint64 if lanes == 2 else np.uint32):
        compiled = _compiles(jax.jit(fn).lower(*args))
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_kernel_compiles_per_shard_for_v5e(topo):
    """A kernel runs per shard inside a shard_map over the 2x2 mesh with
    the varying-manual-axes check on."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("data",))
    world, rows = len(topo.devices), MAX_TILE_ROWS["scan"]

    def body(k, c, v):
        return segmented_reduce.segmented_scan_tiles(
            k, c, v, v, v, interpret=False)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    s = NamedSharding(mesh, P("data"))
    compiled = _compiles(jax.jit(fn).lower(
        _spec((world, rows), np.uint32, s), _spec((world, rows), np.int32, s),
        _spec((world, 1, rows), np.float32, s)))
    assert "tpu_custom_call" in compiled.as_text()
