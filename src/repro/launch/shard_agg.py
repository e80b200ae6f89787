"""Multi-host sharded aggregation driver — the launch recipe for the
capacity-bounded cross-shard exchange.

Single host (fake devices make a world without hardware):

    PYTHONPATH=src python -m repro.launch.shard_agg --smoke
    PYTHONPATH=src python -m repro.launch.shard_agg --fake-devices 8 \
        --rows 65536 --zipf 1.2

Multi-host (one process per host, same command everywhere but the id):

    REPRO_COORDINATOR=host0:1234 REPRO_NUM_PROCESSES=2 REPRO_PROCESS_ID=0 \
        PYTHONPATH=src python -m repro.launch.shard_agg --rows 1048576
    REPRO_COORDINATOR=host0:1234 REPRO_NUM_PROCESSES=2 REPRO_PROCESS_ID=1 \
        PYTHONPATH=src python -m repro.launch.shard_agg --rows 1048576

Each process calls :func:`repro.distributed.sharding.init_distributed`
(a no-op without the env vars), builds a 1-D mesh over the GLOBAL
device list, feeds its process-local slice of a synthetic Zipf-skewed
batch through :func:`repro.core.pipeline.insort_aggregate_device`, and
prints the exchange accounting that this PR's quota work added to
:class:`~repro.core.types.SpillStats`: the derived per-peer quota, the
fullest segment actually sent (``exchange_max_fill``), the fill
fraction, retry count, and the analytic per-shard exchange footprint.

``--fake-devices N`` must be handled BEFORE jax import (it sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N``), which is why
argument parsing happens at module top level in :func:`main`.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

from repro.launch.compile_cache import enable_compile_cache


def _zipf_keys(rng, n, domain, s, dtype):
    """Bounded-domain Zipf(s) draw: p(rank) ~ 1/rank**s over ``domain``
    distinct keys (s=0 is uniform).  numpy's rng.zipf is unsuitable here:
    it needs s>1 and has unbounded support."""
    import numpy as np

    ranks = np.arange(1, domain + 1, dtype=np.float64)
    p = ranks ** -float(s)
    p /= p.sum()
    return rng.choice(domain, size=n, p=p).astype(dtype)


def run(*, rows=65536, zipf=0.0, policy="rs", memory_rows=4096,
        batch_rows=512, width=1, seed=0, quiet=False):
    # jax imported here, after main() fixed XLA_FLAGS.  init_distributed
    # must run before ANY jax computation (jax raises otherwise), so it
    # goes before the pipeline imports — those trace code at import time.
    import jax
    import numpy as np

    from repro.distributed.sharding import (
        data_mesh,
        host_local_array,
        init_distributed,
    )

    multi = init_distributed()

    from repro.core.pipeline import insort_aggregate_device
    from repro.core.types import ExecConfig, empty_key
    from repro.distributed import groupby as gb
    from jax.sharding import PartitionSpec as P
    mesh = data_mesh("shard")
    world = jax.device_count()
    nproc = jax.process_count()
    if not quiet:
        print(f"world={world} devices across {nproc} process(es) "
              f"(jax.distributed {'ON' if multi else 'off'})")

    # Every process generates only ITS slice of the global batch: the
    # global row count is rows, each process holds rows // nproc.
    rows -= rows % world
    loc = rows // nproc
    rng = np.random.default_rng(seed + jax.process_index())
    domain = max(1024, rows // 4)
    keys = _zipf_keys(rng, loc, domain, zipf, np.uint32)
    payload = rng.standard_normal((loc, width)).astype(np.float32)
    spec = P("shard")
    gkeys = host_local_array(keys, mesh, spec)
    gpay = host_local_array(payload, mesh, P("shard", None))

    cfg = ExecConfig(memory_rows=memory_rows, page_rows=256, fanin=8,
                     batch_rows=batch_rows)
    t0 = time.perf_counter()
    st, stats = insort_aggregate_device(
        gkeys, gpay, cfg, policy=policy, mesh=mesh, mesh_axis="shard")
    jax.block_until_ready(st.keys)
    dt = time.perf_counter() - t0
    # group count as a jitted global reduction (works on multi-host
    # arrays, where np.asarray on the sharded output would not)
    groups = int(jax.jit(
        lambda k: (k != empty_key(k.dtype)).sum())(st.keys))

    quota = stats.exchange_quota
    fill = stats.exchange_max_fill
    foot = gb.exchange_footprint_rows(world, quota) if quota else 0
    report = {
        "world": world,
        "processes": nproc,
        "rows_global": rows,
        "zipf_s": zipf,
        "policy": policy,
        "groups": groups,
        "rows_exchanged": int(stats.rows_exchanged),
        "exchange_quota": int(quota),
        "exchange_max_fill": int(fill),
        "fill_frac": round(fill / quota, 4) if quota else 0.0,
        "exchange_retries": int(stats.exchange_retries),
        "exchange_footprint_rows": int(foot),
        "seconds": round(dt, 4),
    }
    if not quiet:
        for k, v in report.items():
            print(f"  {k:24s} {v}")
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--rows", type=int, default=65536,
                    help="GLOBAL row count (split across processes)")
    ap.add_argument("--zipf", type=float, default=0.0,
                    help="Zipf skew s (0 = uniform)")
    ap.add_argument("--policy", default="rs",
                    choices=["rs", "ms", "insort", "hash"])
    ap.add_argument("--memory-rows", type=int, default=4096)
    ap.add_argument("--batch-rows", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="force N host-platform devices (single process)")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    if args.fake_devices:
        if "jax" in sys.modules:
            raise RuntimeError("--fake-devices must be set before jax import")
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.fake_devices}").strip()
    enable_compile_cache()

    if args.smoke:
        run(rows=4096, zipf=1.2, memory_rows=1024, batch_rows=256,
            seed=args.seed)
        return

    run(rows=args.rows, zipf=args.zipf, policy=args.policy,
        memory_rows=args.memory_rows, batch_rows=args.batch_rows,
        seed=args.seed)


if __name__ == "__main__":
    main()
