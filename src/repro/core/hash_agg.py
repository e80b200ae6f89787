"""Hash-aggregation baselines the paper compares against.

Two variants, both with exact spill accounting:

* ``hash_aggregate``      — textbook hybrid hash aggregation: an in-memory
  table absorbs matches; on overflow the key space is hash-partitioned
  into F spill partitions per level, recursively, until a partition's
  output fits memory (Examples 3/4/5, Fig 23/24 "hash + hybrid hashing").
  A resident fraction of the hash domain stays in memory (hybrid hashing),
  absorbing ~M/O of the input before any spill.

* ``f1_hash_aggregate``   — F1 Query's pre-paper production scheme (§5,
  Figs 17/18): "hash-based early aggregation in a sort-based spilling
  approach" [4] — the overflowing hash table is *sorted and written as a
  run*; runs are merged with traditional non-aggregating merge steps and
  duplicates are removed only in the final merge.

Hashing uses a fixed odd multiplicative constant per key width, a
**bijection** on uint32/uint64 — so equality on hashes is equality on
keys, spelling out the paper's observation that "hashing is in fact
equivalent to sorting by hash value" [25]: the machinery below literally
reuses the ordered-index primitives on hashed keys.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core import merge as merge_mod
from repro.core import run_generation as rg
from repro.core import sorted_ops
from repro.core.types import (
    AggState,
    ExecConfig,
    SpillStats,
    empty_key,
    key_dtype_context,
)

_KNUTH = np.uint32(2654435761)
_KNUTH_INV = np.uint32(pow(int(_KNUTH), -1, 1 << 32))
# 64-bit twin: the odd Fibonacci-hashing constant ⌊2^64/φ⌋ | 1.
_KNUTH64 = np.uint64(0x9E3779B97F4A7C15)
_KNUTH64_INV = np.uint64(pow(int(_KNUTH64), -1, 1 << 64))


def _consts(dtype) -> tuple[np.unsignedinteger, np.unsignedinteger, int]:
    if np.dtype(dtype) == np.uint64:
        return _KNUTH64, _KNUTH64_INV, 64
    return _KNUTH, _KNUTH_INV, 32


def hash_u32(keys):
    return (keys.astype(jnp.uint32) * _KNUTH).astype(jnp.uint32)


def unhash_u32(hkeys):
    return (hkeys.astype(jnp.uint32) * _KNUTH_INV).astype(jnp.uint32)


def unhash_keys(hkeys):
    """Invert the multiplicative hash at the stored key dtype."""
    _, inv, _ = _consts(hkeys.dtype)
    return (hkeys * inv.astype(hkeys.dtype)).astype(hkeys.dtype)


def _np_hash(keys: np.ndarray) -> np.ndarray:
    mul, _, bits = _consts(keys.dtype)
    if bits == 64:
        with np.errstate(over="ignore"):
            return (keys.astype(np.uint64) * mul).astype(np.uint64)
    return (keys.astype(np.uint64) * np.uint64(int(mul)) % (1 << 32)).astype(
        np.uint32
    )


def _np_unhash(hkeys: np.ndarray) -> np.ndarray:
    mul, inv, bits = _consts(hkeys.dtype)
    if bits == 64:
        with np.errstate(over="ignore"):
            return (hkeys.astype(np.uint64) * inv).astype(np.uint64)
    return (hkeys.astype(np.uint64) * np.uint64(int(inv)) % (1 << 32)).astype(
        np.uint32
    )


def _checked_hash(keys: np.ndarray) -> np.ndarray:
    """Hash + sentinel guard: the multiplicative hash is a bijection, so
    exactly ONE valid key maps onto the EMPTY sentinel (EMPTY · mul⁻¹);
    a row carrying it would silently vanish inside the engine.  Fail
    loudly instead — the in-sort operator (algorithm="auto") has no such
    restriction."""
    hk = _np_hash(keys)
    sentinel = empty_key(keys.dtype)
    if bool((hk == sentinel).any()):
        bad = _np_unhash(np.asarray([sentinel], dtype=keys.dtype))[0]
        raise ValueError(
            f"key {int(bad)} hashes to the reserved EMPTY sentinel and is "
            "unsupported by the hash baselines; use the sort-based operator"
        )
    return hk


def _in_memory_agg(keys_h, payload, backend, widths):
    """Aggregate one hash partition.  Partitions are EMPTY-padded to a
    power of two so that their many sizes share a few compilations."""
    n = len(keys_h)
    pad = (1 << max(0, (n - 1).bit_length())) - n
    if pad:
        keys_h = np.concatenate(
            [keys_h, np.full(pad, empty_key(keys_h.dtype), keys_h.dtype)])
        if payload is not None:
            payload = jnp.concatenate(
                [payload, jnp.zeros((pad,) + payload.shape[1:], payload.dtype)])
    return sorted_ops.sorted_groupby(keys_h, payload, backend=backend, widths=widths)


def hash_aggregate(
    keys: np.ndarray,
    payload: np.ndarray | None = None,
    cfg: ExecConfig | None = None,
    *,
    output_estimate: int | None = None,
    hybrid: bool = True,
    backend: str = "auto",
    widths: tuple[int, int, int] | None = None,
) -> tuple[AggState, SpillStats]:
    """Hybrid hash aggregation with recursive overflow partitioning.

    Result keys are returned un-hashed but the state is ordered by hash —
    i.e. *not* usefully sorted for downstream consumers, which is exactly
    the interesting-orderings deficit the paper's operator removes.
    """
    cfg = cfg or ExecConfig()
    backend = dispatch.resolve_backend_name(backend)
    stats = SpillStats()
    keys = rg._np_keys(keys)
    sentinel = empty_key(keys.dtype)
    key_bits = 64 if keys.dtype == np.uint64 else 32
    if payload is not None:
        payload = np.asarray(payload, dtype=np.float32)
        if payload.ndim == 1:
            payload = payload[:, None]
    mask = keys != sentinel  # sentinel rows are not data
    if not mask.all():
        keys = keys[mask]
        payload = None if payload is None else payload[mask]
    hk = _checked_hash(keys)
    M, F = cfg.memory_rows, cfg.fanin

    outputs: list[AggState] = []

    def process(hkeys, pay, level: int, lo: int, hi: int):
        """Aggregate the hash sub-range [lo, hi); recurse on overflow."""
        uniq = len(np.unique(hkeys))
        if uniq <= M:
            outputs.append(
                _in_memory_agg(
                    hkeys, None if pay is None else jnp.asarray(pay), backend, widths
                )
            )
            return
        # overflow: hybrid hashing keeps a resident slice of THIS sub-range
        resident_frac = (M / uniq) if hybrid else 0.0
        cut = lo + int(resident_frac * (hi - lo))
        resident = hkeys < np.asarray(cut, dtype=hkeys.dtype) if cut < (1 << key_bits) else np.ones_like(hkeys, bool)
        if resident.any():
            outputs.append(
                _in_memory_agg(
                    hkeys[resident],
                    None if pay is None else jnp.asarray(pay[resident]),
                    backend,
                    widths,
                )
            )
        rest_k, rest_p = hkeys[~resident], None if pay is None else pay[~resident]
        # hash-partition the overflow into F spill partitions (1 write each)
        stats.rows_spilled_merge += len(rest_k)
        stats.merge_levels = max(stats.merge_levels, level + 1)
        # integer edge arithmetic: float linspace loses precision at 2^64
        edges = [cut + (hi - cut) * i // F for i in range(F + 1)]
        inner = np.asarray(edges[1:-1], dtype=hkeys.dtype)
        part = np.digitize(rest_k, inner, right=False)
        for f in range(F):
            m = part == f
            if m.any():
                stats.merge_steps += 1
                process(rest_k[m], None if rest_p is None else rest_p[m],
                        level + 1, edges[f], edges[f + 1])

    with key_dtype_context(keys):
        process(hk, payload, 0, 0, 1 << key_bits)
        # splice partition outputs together: each is sorted (by hash) over a
        # disjoint hash range, so a tree of linear merges orders the union —
        # no full sort of the spliced result.
        cat = sorted_ops.merge_absorb_many(
            outputs, backend=backend, assume_unique=True
        )
        # report user keys (un-hash), order remains hash order
        out = AggState(
            keys=jnp.where(cat.keys != sentinel, unhash_keys(cat.keys), sentinel),
            count=cat.count,
            sum=cat.sum,
            min=cat.min,
            max=cat.max,
        )
    return out, stats


def f1_hash_aggregate(
    keys: np.ndarray,
    payload: np.ndarray | None = None,
    cfg: ExecConfig | None = None,
    *,
    backend: str = "auto",
    widths: tuple[int, int, int] | None = None,
) -> tuple[AggState, SpillStats]:
    """Pre-paper F1 scheme: hash-table early aggregation, sorted-run spill,
    non-aggregating merges, dedup only at the final merge (Figs 17/18)."""
    cfg = cfg or ExecConfig()
    backend = dispatch.resolve_backend_name(backend)
    keys = rg._np_keys(keys)
    sentinel = empty_key(keys.dtype)
    mask = keys != sentinel
    if not mask.all():
        keys = keys[mask]
        if payload is not None:
            payload = np.asarray(payload, dtype=np.float32)[mask]
    hk = _checked_hash(keys)
    # The overflowing hash table == our early-aggregation index on hashes:
    # identical in-memory absorption, identical run counts/sizes (§6.2).
    with key_dtype_context(keys):
        runs, table, stats = rg.generate_runs(
            hk, payload, cfg, policy="early_agg", backend=backend, widths=widths
        )
        if table is not None:
            out = table
        else:
            out = merge_mod.final_merge_traditional(
                runs, cfg, aggregate=False, stats=stats, backend=backend
            )
        out = AggState(
            keys=jnp.where(out.keys != sentinel, unhash_keys(out.keys), sentinel),
            count=out.count,
            sum=out.sum,
            min=out.min,
            max=out.max,
        )
    return out, stats
