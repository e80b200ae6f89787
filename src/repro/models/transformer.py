"""Layer blocks and the scanned stacks composing all ten architectures.

Homogeneous layer runs are stacked (L, …) and driven by ``lax.scan`` —
compile time stays flat in depth (61–88 layer models) and remat applies
per layer.  Heterogeneous structure (deepseek's first-k-dense, zamba2's
shared attention block) becomes a short python-level composition of
scanned segments.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM


def _stacked_init(fn, key, n: int):
    """vmap an initializer over layer keys → params with leading (n,)."""
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: fn(k)[0])(keys)
    _, specs = fn(key)  # structure only
    specs = jax.tree.map(lambda s: ("layers",) + tuple(s), specs,
                         is_leaf=lambda s: isinstance(s, tuple))
    return params, specs


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def make_block(key, cfg: ModelConfig, kind: str, dtype):
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    if kind == "mamba":
        p["norm"], s["norm"] = L.make_norm(cfg.d_model, dtype)
        p["mixer"], s["mixer"] = SSM.make_mamba2(ks[0], cfg, dtype)
        return p, s
    p["ln1"], s["ln1"] = L.make_norm(cfg.d_model, dtype)
    p["ln2"], s["ln2"] = L.make_norm(cfg.d_model, dtype)
    if cfg.mla is not None:
        p["attn"], s["attn"] = L.make_mla(ks[0], cfg, dtype)
    else:
        p["attn"], s["attn"] = L.make_attention(ks[0], cfg, dtype)
    if kind == "attn_moe":
        p["moe"], s["moe"] = MOE.make_moe(ks[1], cfg, dtype)
    else:
        p["mlp"], s["mlp"] = L.make_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p, s


def apply_block(p, cfg: ModelConfig, kind: str, x, positions, *, cache=None,
                mrope_pos=None, dispatch=None):
    aux = jnp.float32(0.0)
    if kind == "mamba":
        h, new_cache = SSM.mamba2_block(
            p["mixer"], cfg, L.rmsnorm(p["norm"], x, cfg.norm_eps), cache=cache
        )
        return L.hint(x + h, cfg, "dp", "sp", None), new_cache, aux
    if cfg.mla is not None:
        h, new_cache = L.mla_attention(
            p["attn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps), positions,
            cache=cache,
        )
    else:
        h, new_cache = L.attention(
            p["attn"], cfg, L.rmsnorm(p["ln1"], x, cfg.norm_eps), positions,
            cache=cache, mrope_pos=mrope_pos,
        )
    x = L.hint(x + h, cfg, "dp", "sp", None)
    hn = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if kind == "attn_moe":
        h, aux = MOE.moe_block(p["moe"], cfg, hn, dispatch=dispatch)
    else:
        h = L.mlp(p["mlp"], hn, cfg.act)
    return L.hint(x + h, cfg, "dp", "sp", None), new_cache, aux


# ---------------------------------------------------------------------------
# scanned stack
# ---------------------------------------------------------------------------


def make_stack(key, cfg: ModelConfig, kind: str, n_layers: int, dtype):
    return _stacked_init(lambda k: make_block(k, cfg, kind, dtype), key, n_layers)


def apply_stack(params, cfg: ModelConfig, kind: str, x, positions, *,
                caches=None, mrope_pos=None, dispatch=None):
    """Apply a homogeneous stack: lax.scan over stacked (L, …) params by
    default (flat compile time in depth), or an unrolled python loop when
    ``cfg.scan_layers=False`` (for XLA cost_analysis, which counts a
    scanned body once and must see every layer)."""

    def body(carry, xs):
        h, aux = carry
        p_l, cache_l = xs
        h, new_cache, a = apply_block(
            p_l, cfg, kind, h, positions, cache=cache_l, mrope_pos=mrope_pos,
            dispatch=dispatch,
        )
        return (h, aux + a), new_cache

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)

    if not cfg.scan_layers:
        n = jax.tree.leaves(params)[0].shape[0]
        aux = jnp.float32(0.0)
        outs = []
        for i in range(n):
            p_l = jax.tree.map(lambda a: a[i], params)
            c_l = None if caches is None else jax.tree.map(lambda a: a[i], caches)
            (x, aux), nc = body((x, aux), (p_l, c_l))
            outs.append(nc)
        new_caches = (None if outs[0] is None
                      else jax.tree.map(lambda *xs: jnp.stack(xs, 0), *outs))
        return x, new_caches, aux

    (x, aux), new_caches = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                        (params, caches))
    return x, new_caches, aux
