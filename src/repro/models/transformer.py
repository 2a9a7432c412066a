"""LM family: dense + MoE decoder-only transformers.

Covers all five assigned LM architectures (deepseek-moe-16b, arctic-480b,
phi3-mini-3.8b, qwen2-1.5b, deepseek-coder-33b): GQA, RoPE, optional QKV bias,
SwiGLU, DeepSeek-style shared experts + first-k-dense, Arctic-style dense
residual branch.

Layers are stacked on a leading axis and applied with ``lax.scan`` (one HLO
layer body regardless of depth — keeps 62-layer compiles tractable and is the
remat unit). MoE models with ``first_k_dense`` keep those prefix layers
unstacked (they have a different MLP width).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import LMConfig
from repro.models import layers as L
from repro.sharding.rules import constrain

Params = Dict[str, Any]


def _dtype(cfg: LMConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: LMConfig, *, moe: bool) -> Params:
    ka, km = jax.random.split(key)
    dt = _dtype(cfg)
    p = {
        "ln1": jnp.ones((cfg.d_model,), jnp.float32),
        "ln2": jnp.ones((cfg.d_model,), jnp.float32),
        "attn": L.init_attn(ka, cfg, dt),
    }
    if moe:
        p["moe"] = L.init_moe(km, cfg, dt)
    else:
        p["mlp"] = L.init_mlp(km, cfg.d_model, cfg.d_ff, dt)
    return p


def init_lm(key, cfg: LMConfig) -> Params:
    dt = _dtype(cfg)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    n_prefix = cfg.first_k_dense if cfg.moe is not None else 0
    n_main = cfg.n_layers - n_prefix
    lkeys = jax.random.split(k_layers, cfg.n_layers)

    main = jax.vmap(lambda k: _init_layer(k, cfg, moe=cfg.moe is not None))(
        lkeys[n_prefix:])
    params: Params = {
        "embed": jax.random.normal(k_embed, (cfg.vocab_size, cfg.d_model), dt)
        * cfg.d_model ** -0.5,
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": main,
    }
    if n_prefix:
        params["prefix"] = [
            _init_layer(lkeys[i], cfg, moe=False) for i in range(n_prefix)]
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(k_head, (cfg.d_model, cfg.vocab_size), dt)
            * cfg.d_model ** -0.5)
    return params


def lm_head_weight(params: Params) -> jax.Array:
    if "lm_head" in params:
        return params["lm_head"]
    return params["embed"].T   # tied embeddings


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _layer_fwd(p: Params, cfg: LMConfig, x, positions, *, moe: bool,
               n_groups: int, causal_skip: bool):
    h, _ = L.attn_block(p["attn"], cfg, L.rms_norm(x, p["ln1"], cfg.norm_eps),
                        positions=positions, causal_skip=causal_skip)
    x = x + h
    z = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if moe:
        mo, aux = L.moe_block(p["moe"], cfg, z, n_groups=n_groups)
    else:
        mo, aux = L.mlp_block(p["mlp"], z), jnp.zeros((), jnp.float32)
    return x + mo, aux


def forward(params: Params, cfg: LMConfig, tokens: jax.Array, *,
            n_groups: int = 1, causal_skip: bool = False) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) -> (hidden (B, S, d), aux_loss)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, "dp", None, None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.float32)[None], (B, S))

    aux_total = jnp.zeros((), jnp.float32)
    for p in params.get("prefix", []):
        x, aux = _layer_fwd(p, cfg, x, positions, moe=False,
                            n_groups=n_groups, causal_skip=causal_skip)
        aux_total = aux_total + aux

    is_moe = cfg.moe is not None

    def body(carry, lp):
        x, aux_total = carry
        x = constrain(x, "dp", None, None)
        # barrier: keep the remat stash consumed slice-wise in bf16 — without
        # it XLA hoists convert(slice(stash)) into a full f32 copy of the
        # (L, B, S, d) stash (observed +10.5 GiB on train_4k)
        x = lax.optimization_barrier(x)
        x, aux = _layer_fwd(lp, cfg, x, positions, moe=is_moe,
                            n_groups=n_groups, causal_skip=causal_skip)
        x = constrain(x, "dp", None, None)
        return (x, aux_total + aux), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, aux_total), _ = lax.scan(body, (x, aux_total), params["layers"])
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux_total


def lm_loss(params: Params, cfg: LMConfig, tokens: jax.Array,
            labels: jax.Array, *, n_groups: int = 1,
            causal_skip: bool = False) -> jax.Array:
    hidden, aux = forward(params, cfg, tokens, n_groups=n_groups,
                          causal_skip=causal_skip)
    head = lm_head_weight(params)
    return L.chunked_softmax_xent(hidden, head, labels) + aux


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

class LMCache(NamedTuple):
    prefix_k: Optional[jax.Array]   # (P, B, Hkv, S, hd) or None
    prefix_v: Optional[jax.Array]
    main_k: jax.Array               # (L', B, Hkv, S, hd)
    main_v: jax.Array
    length: jax.Array               # (B,) int32


def init_cache(cfg: LMConfig, batch: int, max_len: int,
               dtype=None) -> LMCache:
    dt = dtype or _dtype(cfg)
    n_prefix = cfg.first_k_dense if cfg.moe is not None else 0
    n_main = cfg.n_layers - n_prefix
    shp = (batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    mk = jnp.zeros((n_main,) + shp, dt)
    mv = jnp.zeros((n_main,) + shp, dt)
    pk = pv = None
    if n_prefix:
        pk = jnp.zeros((n_prefix,) + shp, dt)
        pv = jnp.zeros((n_prefix,) + shp, dt)
    return LMCache(pk, pv, mk, mv, jnp.zeros((batch,), jnp.int32))


def _layer_decode(p: Params, cfg: LMConfig, x, cache: L.KVCache, *,
                  moe: bool, n_groups: int):
    h, new_cache = L.attn_decode_block(
        p["attn"], cfg, L.rms_norm(x, p["ln1"], cfg.norm_eps), cache)
    x = x + h
    z = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if moe:
        mo, _ = L.moe_block(p["moe"], cfg, z, n_groups=n_groups)
    else:
        mo = L.mlp_block(p["mlp"], z)
    return x + mo, new_cache


def decode_step(params: Params, cfg: LMConfig, tokens: jax.Array,
                cache: LMCache, *, n_groups: int = 1
                ) -> Tuple[jax.Array, LMCache]:
    """tokens: (B, 1) -> (logits (B, 1, V), updated cache). One new token
    against a KV cache of ``max_len`` slots (``cache.length`` valid)."""
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)

    new_pk, new_pv = cache.prefix_k, cache.prefix_v
    if cache.prefix_k is not None:
        pks, pvs = [], []
        for i, p in enumerate(params["prefix"]):
            kv = L.KVCache(cache.prefix_k[i], cache.prefix_v[i], cache.length)
            x, kv = _layer_decode(p, cfg, x, kv, moe=False, n_groups=n_groups)
            pks.append(kv.k)
            pvs.append(kv.v)
        new_pk = jnp.stack(pks)
        new_pv = jnp.stack(pvs)

    is_moe = cfg.moe is not None

    def body(x, xs):
        lp, k, v = xs
        kv = L.KVCache(k, v, cache.length)
        x, kv = _layer_decode(lp, cfg, x, kv, moe=is_moe, n_groups=n_groups)
        return x, (kv.k, kv.v)

    x, (mk, mv) = lax.scan(body, x, (params["layers"], cache.main_k, cache.main_v))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ lm_head_weight(params)).astype(jnp.float32)
    return logits, LMCache(new_pk, new_pv, mk, mv, cache.length + 1)


def prefill_step(params: Params, cfg: LMConfig, tokens: jax.Array, *,
                 n_groups: int = 1, causal_skip: bool = False
                 ) -> Tuple[jax.Array, LMCache]:
    """Full-sequence prefill: returns last-position logits + filled cache."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.float32)[None], (B, S))
    is_moe = cfg.moe is not None

    def run_layer(p, x, moe):
        z = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L._project_qkv(p["attn"], cfg, z)
        q = L.apply_rope(q, positions[:, None, :], cfg.rope_theta)
        k = L.apply_rope(k, positions[:, None, :], cfg.rope_theta)
        o = L.chunked_attention(q, k, v, causal=True, causal_skip=causal_skip)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.head_dim)
        x = x + o @ p["attn"]["wo"]
        z2 = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if moe:
            mo, _ = L.moe_block(p["moe"], cfg, z2, n_groups=n_groups)
        else:
            mo = L.mlp_block(p["mlp"], z2)
        return x + mo, k, v

    new_pk = new_pv = None
    if "prefix" in params:
        pks, pvs = [], []
        for p in params["prefix"]:
            x, k, v = run_layer(p, x, False)
            pks.append(k)
            pvs.append(v)
        new_pk, new_pv = jnp.stack(pks), jnp.stack(pvs)

    def body(x, lp):
        x, k, v = run_layer(lp, x, is_moe)
        return x, (k, v)

    x, (mk, mv) = lax.scan(body, x, params["layers"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, -1:] @ lm_head_weight(params)).astype(jnp.float32)
    length = jnp.full((B,), S, jnp.int32)
    return logits, LMCache(new_pk, new_pv, mk, mv, length)
