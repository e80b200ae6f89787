"""Backend registry for the ordered-index engine.

Every grouping primitive (argsort, segmented combine, sorted merge-absorb)
used to be selected by a ``backend: str`` threaded through each call site
with ad-hoc lazy imports.  This module centralizes that plumbing:

* ``register_backend(name, loader)`` — loaders build a :class:`Backend`
  on first use and may raise :class:`BackendUnavailable` (capability
  probing: e.g. the Pallas backend probes its kernel imports).
* ``get_backend(name)`` — resolves a name (or ``"auto"``) to a cached
  :class:`Backend`.  ``"auto"`` is ``"xla"`` on every platform.
* ``should_interpret()`` — the single source of truth for Pallas
  ``interpret=`` mode: interpret everywhere except on a TPU.

Built-in backends:

* ``"xla"``    — pure-jnp reference engine (:mod:`repro.core.ordered_index`);
  always available, bit-exact oracle for tests and dry-runs.
* ``"pallas"`` — TPU kernels (:mod:`repro.kernels`): bitonic argsort,
  fused segmented scan, and the merge-path merge-absorb kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax


class BackendUnavailable(RuntimeError):
    """A backend's loader determined it cannot run in this environment."""


@dataclasses.dataclass(frozen=True)
class Backend:
    """The three primitives every engine backend must provide.

    ``argsort(keys) -> perm``
        Key-argsort of a 1-D uint32/uint64 vector (the per-dtype EMPTY
        sentinel sorts to the end).  uint64 callers hold
        :func:`repro.core.types.key_dtype_context`.
    ``segmented_combine(state) -> state``
        Combine adjacent equal-key rows of a *key-sorted* AggState;
        unique groups compacted to the front, EMPTY-padded tail.
    ``merge_sorted(a, b, assume_unique=False) -> state``
        Linear merge-absorb of two *key-sorted* AggStates; returns a
        sorted, duplicate-combined state of capacity ``|a| + |b|``.
        Must not perform a full sort of the union.  ``assume_unique``
        promises both inputs are duplicate-free (the OrderedIndex
        invariant), licensing a cheaper pair-combine.
    ``interleave(a, b) -> state`` (optional)
        Linear merge of two *key-sorted* AggStates WITHOUT combining
        duplicates — the raw sorted multiset union (traditional merge
        levels that defer aggregation need exactly this).  ``None``
        means the engine falls back to the XLA rank-gather interleave.
    ``join_probe(a_keys, b_keys) -> (pos, hit)`` (optional)
        Rank-align each key of a *sorted* vector against a second
        *sorted* vector (the merge join's probe phase; see
        :func:`repro.core.merge_join.join_probe`).  ``None`` means the
        join falls back to the XLA searchsorted probe.
    ``shardable``
        Whether the backend's primitives may be traced inside a
        ``shard_map`` manual-collective region (the mesh-sharded
        pipeline runs the whole engine per shard).  Capability flag, not
        a promise of speed.
    """

    name: str
    argsort: Callable
    segmented_combine: Callable
    merge_sorted: Callable
    interleave: Callable | None = None
    join_probe: Callable | None = None
    shardable: bool = True


_loaders: dict[str, Callable[[], Backend]] = {}
_cache: dict[str, Backend] = {}


def register_backend(
    name: str, loader: Callable[[], Backend], *, overwrite: bool = False
) -> None:
    """Register a lazy backend loader.  The loader runs on first
    ``get_backend(name)`` and may raise :class:`BackendUnavailable`."""
    if name in _loaders and not overwrite:
        raise ValueError(f"backend {name!r} already registered")
    _loaders[name] = loader
    _cache.pop(name, None)


def registered_backends() -> tuple[str, ...]:
    return tuple(_loaders)


def backend_available(name: str) -> bool:
    """Capability probe: can ``name`` actually be constructed here?"""
    try:
        get_backend(name)
        return True
    except (KeyError, BackendUnavailable):
        return False


def get_backend(name: str = "xla") -> Backend:
    """Resolve a backend name to a Backend instance.  ``"auto"`` (or
    None) is the XLA engine on every platform: no Pallas kernel has yet
    beaten it on a chip, and a backend that cannot run raises instead of
    handing the query to another one."""
    if name in ("auto", None):
        name = "xla"
    if name in _cache:
        return _cache[name]
    if name not in _loaders:
        raise KeyError(
            f"unknown backend {name!r}; registered: {registered_backends()}"
        )
    be = _loaders[name]()
    _cache[name] = be
    return be


def resolve_backend_name(name: str) -> str:
    """Normalize ``"auto"`` to a concrete backend name (for static args)."""
    return get_backend(name).name


def check_shardable(name: str) -> None:
    """Raise :class:`BackendUnavailable` if ``name`` cannot run inside a
    ``shard_map`` region (mesh-sharded pipeline front door guard)."""
    be = get_backend(name)
    if not be.shardable:
        raise BackendUnavailable(
            f"backend {be.name!r} does not support shard_map execution; "
            "use backend='xla' (or 'auto') for mesh-sharded aggregation"
        )


def checks_vma(name: str) -> bool:
    """Whether a ``shard_map`` running backend ``name`` per shard keeps
    JAX's varying-manual-axes check on: everywhere except for Pallas
    kernels in interpret mode.  JAX's Pallas interpreter evaluates a
    kernel by binding its primitives one by one, so the kernel's own
    constants (iotas, literals) meet per-shard blocks and the check
    refuses ("Primitive le requires varying manual axes to match, but
    got [frozenset({'data'}), frozenset()] ... as a temporary
    workaround pass the check_vma=False argument to `jax.shard_map`").
    Compiled for a TPU, the kernels run per shard with the check on."""
    return not (get_backend(name).name == "pallas" and should_interpret())


def should_interpret() -> bool:
    """Pallas interpret mode: exactly when JAX's default backend is not a
    TPU.  Read at trace time, so a TPU never runs the interpreter."""
    return jax.default_backend() != "tpu"


def _load_xla() -> Backend:
    import jax.numpy as jnp

    from repro.core import ordered_index as oi

    return Backend(
        name="xla",
        argsort=jnp.argsort,
        segmented_combine=oi.segmented_combine_xla,
        merge_sorted=oi.merge_absorb_xla,
        interleave=oi.interleave_sorted,
    )


def _probe_tpu_kernels(kops) -> None:
    """Compile every kernel once at a small tile; a kernel the TPU
    compiler refuses makes the whole backend unavailable, quoting it."""
    import jax.numpy as jnp

    from repro.core.types import rows_to_state

    keys = jnp.arange(256, dtype=jnp.uint32)
    state = rows_to_state(keys, jnp.ones((256, 1), jnp.float32))
    for name, fn, args in (
        ("argsort", kops.argsort_keys, (keys,)),
        ("segmented_combine", kops.segmented_combine, (state,)),
        ("merge_sorted", kops.merge_absorb_sorted, (state, state)),
        ("join_probe", kops.join_probe, (keys, keys)),
    ):
        try:
            jax.jit(fn).lower(*args).compile()
        except (jax.errors.JaxRuntimeError, NotImplementedError, ValueError) as e:
            raise BackendUnavailable(
                f"pallas {name} kernel does not compile for this TPU: {e}"
            ) from e


def _load_pallas() -> Backend:
    try:
        from repro.kernels import ops as kops
    except Exception as e:  # missing pallas / mosaic in this build
        raise BackendUnavailable(f"pallas kernels unavailable: {e}") from e
    if not should_interpret():
        _probe_tpu_kernels(kops)
    return Backend(
        name="pallas",
        argsort=kops.argsort_keys,
        segmented_combine=kops.segmented_combine,
        merge_sorted=kops.merge_absorb_sorted,
        # no fused non-combining merge kernel yet: the rank-gather
        # interleave is memory-bound and the XLA fallback serves it
        interleave=None,
        join_probe=kops.join_probe,
    )


register_backend("xla", _load_xla)
register_backend("pallas", _load_pallas)
