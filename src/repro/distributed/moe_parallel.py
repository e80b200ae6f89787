"""Expert-parallel MoE dispatch — the paper's sort-based grouping as the
production routing engine (shard_map + all_to_all).

Dense one-hot dispatch materializes an (E, T, D) tensor — at deepseek scale
(E=256, T=1M, D=7168) that is 3.7 TB per layer and simply cannot exist.
The sort-based pipeline is the scalable form, and it is exactly the
paper's algorithm applied to routing:

  per device (data-parallel shard of tokens; "model" axis = 16-way EP):
  1. run generation (§3): key-sort local (token, expert) pairs by expert
     id → contiguous per-expert segments, capacity-clamped to C rows
     (fixed shapes; overflow rows drop, like any capacity-factor MoE);
  2. partition ≡ sort (§2.1): the sorted layout reshapes directly into
     (EP_peers, E_local, C, D) — the all_to_all send buffer needs no
     further shuffling because key-range partitioning of a sorted stream
     is a reshape;
  3. all_to_all over "model": each peer receives its 16 experts' rows;
  4. grouped expert FFN on (E_local, peers·C, D) — contiguous blocks, the
     grouped-matmul kernel's layout;
  5. all_to_all back + combine: a weighted aggregation keyed by original
     token position (§4's merge-with-aggregation, scatter-add form).

  Token chunking: the dispatch runs as a lax.scan over token chunks so
  send/recv buffers stay ~(T_chunk·k·cf·D) — production MoEs micro-batch
  the dispatch the same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig

from jax import shard_map


def _local_sorted_dispatch(x_flat, eidx, w, e: int, cap: int):
    """Sort-based grouping of local rows by expert id (paper §3).

    x_flat (T, D); eidx/w (T,) — returns (slots (T,), keep (T,), xs (E*C, D))
    where xs rows are expert-contiguous, capacity-padded."""
    t, d = x_flat.shape
    order = jnp.argsort(eidx * t + jnp.arange(t, dtype=eidx.dtype))  # stable
    se = eidx[order]
    seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")
    rank = jnp.arange(t) - seg_start[se]
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, e * cap)
    xs = jnp.zeros((e * cap + 1, d), x_flat.dtype).at[slot].set(
        x_flat[order], mode="drop")[:-1]
    return order, slot, keep, xs


def make_ep_moe(mesh, dp_axes: tuple, ep_axis: str = "model"):
    """Returns moe_fn(params, x, cfg) implementing sorted EP dispatch.

    x (B, S, D) with batch sharded over dp_axes; experts sharded over
    ep_axis.  Differentiable (gather/scatter/all_to_all transposes)."""
    ep = mesh.shape[ep_axis]

    def _ffn(p, xs):
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, p["wg"])) * jnp.einsum(
            "ecd,edf->ecf", xs, p["wi"])
        return jnp.einsum("ecf,efd->ecd", h, p["wo"])

    def local_fn(p, x, cfg: ModelConfig):
        # everything here sees LOCAL shards: x (b_loc, S, D); experts
        # p["wi"] (E_loc, D, F)
        m = cfg.moe
        e, k = m.num_experts, m.top_k
        e_loc = e // ep
        b, s, d = x.shape
        logits = (x @ p["router"]["kernel"]).astype(jnp.float32)
        # router weights are replicated row-shards over ep: psum partial? —
        # router kernel is small; sharded (D, E): gather E via all_gather
        logits = jax.lax.all_gather(logits, ep_axis, axis=2, tiled=True)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        if m.router_scale:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        w = w.astype(x.dtype)
        me = probs.mean(axis=(0, 1))
        frac = jax.nn.one_hot(idx, e).mean(axis=(0, 1, 2))
        aux = e * jnp.sum(me * frac)
        aux = jax.lax.pmean(aux, dp_axes)

        tokens = b * s
        chunk = min(getattr(cfg, "moe_chunk", 8192), tokens)
        n_chunks = tokens // chunk
        x_flat = x.reshape(tokens, d)
        eidx = idx.reshape(tokens, k)
        wflat = w.reshape(tokens, k)
        cap = max(8, int(m.capacity_factor * chunk * k / e + 7) // 8 * 8)

        def chunk_step(_, inp):
            xc, ec, wc = inp  # (chunk, D), (chunk, k), (chunk, k)
            t = chunk * k
            xr = jnp.repeat(xc, k, axis=0)  # row per (token, k)
            er = ec.reshape(t)
            wr = wc.reshape(t)
            order, slot, keep, xs = _local_sorted_dispatch(xr, er, wr, e, cap)
            # sorted layout ≡ range partitioning: reshape → a2a
            send = xs.reshape(ep, e_loc * cap, d)
            recv = jax.lax.all_to_all(send, ep_axis, split_axis=0,
                                      concat_axis=0, tiled=False)
            # recv (ep, e_loc*cap, d): peer j's rows for MY e_loc experts
            xs_loc = (recv.reshape(ep, e_loc, cap, d)
                      .transpose(1, 0, 2, 3).reshape(e_loc, ep * cap, d))
            ys_loc = _ffn(p, xs_loc)
            back = (ys_loc.reshape(e_loc, ep, cap, d)
                    .transpose(1, 0, 2, 3).reshape(ep, e_loc * cap, d))
            ys = jax.lax.all_to_all(back, ep_axis,
                                    split_axis=0, concat_axis=0, tiled=False)
            ys = ys.reshape(e * cap, d)
            # combine: weighted aggregation by original token id (§4)
            contrib = ys[jnp.minimum(slot, e * cap - 1)] * wr[order][:, None]
            contrib = jnp.where(keep[:, None], contrib, 0)
            tok = (jnp.arange(t, dtype=jnp.int32) // k)[order]
            out = jnp.zeros((chunk, d), x.dtype).at[tok].add(contrib)
            return None, out

        xcs = x_flat.reshape(n_chunks, chunk, d)
        ecs = eidx.reshape(n_chunks, chunk, k)
        wcs = wflat.reshape(n_chunks, chunk, k)
        _, outs = jax.lax.scan(jax.checkpoint(chunk_step), None,
                               (xcs, ecs, wcs))
        y = outs.reshape(b, s, d)
        return y, aux

    return local_fn


_CURRENT_MESH = [None]


def set_current_mesh(mesh):
    """Launchers register the concrete mesh here; shard_map needs it."""
    _CURRENT_MESH[0] = mesh


def ep_moe_block(p, cfg: ModelConfig, x, mesh=None):
    """shard_map wrapper used by models/moe.py when dispatch='sorted_ep'."""
    mesh = mesh or _CURRENT_MESH[0]
    assert mesh is not None, "call set_current_mesh(mesh) before tracing"
    dp = tuple(a for a in ("pod", "data") if a in cfg.mesh_axes)
    fn = make_ep_moe(mesh, dp)
    dpspec = dp if len(dp) > 1 else dp[0]
    m = cfg.moe

    pspec = {
        "router": {"kernel": P(None, "model")},
        "wi": P("model", None, None),
        "wg": P("model", None, None),
        "wo": P("model", None, None),
    }

    shard_fn = shard_map(
        functools.partial(_wrapped, fn, cfg),
        mesh=mesh,
        in_specs=(pspec, P(dpspec, None, None)),
        out_specs=(P(dpspec, None, None), P()),
    )
    y, aux = shard_fn({k: p[k] for k in pspec}, x)
    if m.num_shared_experts:
        from repro.models.layers import mlp

        y = y + mlp(p["shared"], x, "swiglu")
    return y, aux


def _wrapped(fn, cfg, p, x):
    y, aux = fn(p, x, cfg)
    return y, aux
