"""Transformer building blocks: RMSNorm, RoPE, chunked (flash-style) attention,
SwiGLU MLP, and capacity-bucketed MoE.

Everything is pure JAX (jnp / lax) so it lowers under pjit on any mesh. The
attention is *blockwise with online softmax* — at the assigned shapes a naive
(B, H, S, S) score tensor would be petabytes, so chunking is structural, not an
optimization. A Pallas kernel (kernels/flash_attention) targets TPU for the
same computation; models default to the XLA-chunked path so the dry-run lowers
on any backend.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.compat import shard_map
from repro.configs.base import LMConfig, MoEConfig
from repro.sharding.rules import constrain

Params = Dict[str, Any]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norm + RoPE
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, head_dim); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta)                     # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, hd/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — blockwise online softmax (GQA-aware)
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q: (B, Hkv, G, Sq, hd); k: (B, Hkv, Skv, hd) -> (B, Hkv, G, Sq, Skv)."""
    return jnp.einsum("bhgqd,bhkd->bhgqk", q, k, preferred_element_type=jnp.float32)


def chunked_attention(
    q: jax.Array,              # (B, Hq, Sq, hd)
    k: jax.Array,              # (B, Hkv, Skv, hd)
    v: jax.Array,              # (B, Hkv, Skv, hd)
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,   # absolute position of q[0] (for decode/prefill-continue)
    kv_valid: Optional[jax.Array] = None,  # (B, Skv) bool mask of valid cache slots
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    causal_skip: bool = False,  # skip fully-masked KV blocks (dynamic trip count)
) -> jax.Array:
    """Memory-efficient attention. Never materializes (Sq, Skv).

    GQA: Hq = Hkv * group; KV is broadcast across the group dim (no repeat
    materialization). Returns (B, Hq, Sq, hd) in q.dtype.
    """
    B, Hq, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    q = (q * scale).reshape(B, Hkv, group, Sq, hd)

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    assert Sq % q_chunk == 0 and Skv % kv_chunk == 0, (Sq, q_chunk, Skv, kv_chunk)

    kc = k.reshape(B, Hkv, nk, kv_chunk, hd)
    vc = v.reshape(B, Hkv, nk, kv_chunk, hd)
    validc = None if kv_valid is None else kv_valid.reshape(B, nk, kv_chunk)

    def q_block(qi, qb):
        # qb: (B, Hkv, G, qc, hd)
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, inputs):
            m, l, acc = carry
            kb, vb, kj = inputs["k"], inputs["v"], inputs["j"]
            # barrier: stop XLA loop-invariant code motion from materializing
            # every iteration's mask/score block outside the scan (observed
            # 3.2 GB hoisted mask tensors on the train_4k baseline)
            (kb, vb, kj) = lax.optimization_barrier((kb, vb, kj))
            s = _gqa_scores(qb, kb)                    # (B,Hkv,G,qc,kc) f32
            if causal:
                kv_pos = kj * kv_chunk + jnp.arange(kv_chunk)
                mask = q_pos[:, None] >= kv_pos[None, :]
                s = jnp.where(mask, s, NEG_INF)
            if validc is not None:
                vm = inputs["valid"]                  # (B, kc)
                s = jnp.where(vm[:, None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, group, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, group, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, group, q_chunk, hd), jnp.float32)
        xs = {"k": jnp.moveaxis(kc, 2, 0), "v": jnp.moveaxis(vc, 2, 0),
              "j": jnp.arange(nk)}
        if validc is not None:
            xs["valid"] = jnp.moveaxis(validc, 1, 0)

        kv_step = jax.checkpoint(kv_step)   # flash bwd: recompute p per block
        if causal and causal_skip:
            # Beyond-paper perf option: only run KV blocks that intersect the
            # causal triangle for this q block (dynamic trip count).
            n_run = jnp.minimum(nk, (qi * q_chunk + q_chunk + kv_chunk - 1) // kv_chunk)

            def body(j, carry):
                inp = jax.tree.map(lambda a: a[j], xs)
                carry, _ = kv_step(carry, inp)
                return carry
            m, l, acc = lax.fori_loop(0, n_run, body, (m0, l0, a0))
        else:
            (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), xs)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out  # (B,Hkv,G,qc,hd) f32

    if nq == 1:
        out = q_block(0, q)
    else:
        qs = jnp.moveaxis(q.reshape(B, Hkv, group, nq, q_chunk, hd), 3, 0)
        out = lax.map(lambda args: q_block(args[0], args[1]),
                      (jnp.arange(nq), qs))           # (nq,B,Hkv,G,qc,hd)
        out = jnp.moveaxis(out, 0, 3).reshape(B, Hkv, group, Sq, hd)
    return out.reshape(B, Hq, Sq, hd).astype(v.dtype)


def decode_attention(
    q: jax.Array,              # (B, Hq, 1, hd)
    k_cache: jax.Array,        # (B, Hkv, S, hd)
    v_cache: jax.Array,        # (B, Hkv, S, hd)
    cache_len: jax.Array,      # (B,) or scalar — number of valid slots
) -> jax.Array:
    """Single-token decode: one query against the full KV cache.

    Linear in S (no Sq x Skv tensor) — this is why long_500k decode is
    runnable even for full-attention models. f32 softmax accumulation.
    """
    B, Hq, _, hd = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    group = Hq // Hkv
    qg = (q / math.sqrt(hd)).reshape(B, Hkv, group, hd)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32)   # (B,Hkv,G,S)
    valid = jnp.arange(S)[None, :] < jnp.reshape(cache_len, (-1, 1))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhgk,bhkd->bhgd", (p / l).astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Hq, 1, hd).astype(v_cache.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array          # (B, Hkv, S, hd)
    v: jax.Array          # (B, Hkv, S, hd)
    length: jax.Array     # (B,) int32 — valid prefix length


def init_attn(key, cfg: LMConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    std = d ** -0.5
    p = {
        "wq": jax.random.normal(kq, (d, cfg.n_heads * hd), dtype) * std,
        "wk": jax.random.normal(kk, (d, cfg.n_kv_heads * hd), dtype) * std,
        "wv": jax.random.normal(kv, (d, cfg.n_kv_heads * hd), dtype) * std,
        "wo": jax.random.normal(ko, (cfg.n_heads * hd, d), dtype) * std,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def _project_qkv(p: Params, cfg: LMConfig, x: jax.Array):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    q = constrain(q, "dp", "tp", None, None)
    k = constrain(k, "dp", "tp", None, None)
    v = constrain(v, "dp", "tp", None, None)
    return q, k, v


def attn_block(p: Params, cfg: LMConfig, x: jax.Array, *,
               positions: jax.Array, cache: Optional[KVCache] = None,
               causal_skip: bool = False):
    """Full-sequence attention (train / prefill). Returns (out, new_cache)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    q = apply_rope(q, positions[:, None, :], cfg.rope_theta)
    k = apply_rope(k, positions[:, None, :], cfg.rope_theta)
    out = chunked_attention(q, k, v, causal=True, causal_skip=causal_skip)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.head_dim)
    out = constrain(out, "dp", None, "tp")
    new_cache = None
    if cache is not None:
        new_cache = KVCache(k=k.astype(cache.k.dtype), v=v.astype(cache.v.dtype),
                            length=jnp.full((B,), S, jnp.int32))
    return out @ p["wo"], new_cache


def attn_decode_block(p: Params, cfg: LMConfig, x: jax.Array, cache: KVCache):
    """One-token decode step. x: (B, 1, d). Updates cache in place (functional)."""
    B = x.shape[0]
    hd = cfg.head_dim
    q, k, v = _project_qkv(p, cfg, x)
    pos = cache.length.astype(jnp.float32)             # (B,)
    q = apply_rope(q, pos[:, None, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None, None], cfg.rope_theta)
    # Insert the new KV at position `length` for every batch row. All rows
    # share the same length in our serving path (contiguous batches), so use
    # row 0's scalar for a single dynamic_update_slice (cheapest HLO form).
    idx = cache.length[0]
    k_cache = lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, 0, idx, 0))
    v_cache = lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, 0, idx, 0))
    new_len = cache.length + 1
    out = decode_attention(q, k_cache, v_cache, new_len)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, cfg.n_heads * hd)
    return out @ p["wo"], KVCache(k_cache, v_cache, new_len)


# ---------------------------------------------------------------------------
# Dense SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, ff: int, dtype) -> Params:
    kg, ku, kd = jax.random.split(key, 3)
    return {
        "w_gate": jax.random.normal(kg, (d, ff), dtype) * d ** -0.5,
        "w_up": jax.random.normal(ku, (d, ff), dtype) * d ** -0.5,
        "w_down": jax.random.normal(kd, (ff, d), dtype) * ff ** -0.5,
    }


def mlp_block(p: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = constrain(h, "dp", None, "tp")
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE — group-local capacity-bucketed dispatch
# ---------------------------------------------------------------------------
#
# This is the same routing pattern as WebParF's URL dispatcher (core/router.py
# documents the correspondence): score -> top-k -> position-in-bucket via
# cumsum -> capacity drop -> scatter to (E, C) buckets -> expert GEMM ->
# gather back -> weighted combine. Tokens keep a leading `group` axis that is
# sharded over the data mesh axes so every index op stays shard-local; the
# only cross-device traffic is the expert-dim resharding around the expert
# GEMM (all-to-all under pjit), exactly the MoE/crawler exchange pattern.

def init_moe(key, cfg: LMConfig, dtype) -> Params:
    m = cfg.moe
    d = cfg.d_model
    keys = jax.random.split(key, 6)
    p = {
        "router": jax.random.normal(keys[0], (d, m.n_experts), jnp.float32) * d ** -0.5,
        "w_gate": jax.random.normal(keys[1], (m.n_experts, d, m.d_ff_expert), dtype) * d ** -0.5,
        "w_up": jax.random.normal(keys[2], (m.n_experts, d, m.d_ff_expert), dtype) * d ** -0.5,
        "w_down": jax.random.normal(keys[3], (m.n_experts, m.d_ff_expert, d), dtype) * m.d_ff_expert ** -0.5,
    }
    if m.n_shared:
        p["shared"] = init_mlp(keys[4], d, m.n_shared * m.d_ff_expert, dtype)
    if m.dense_residual:
        p["dense"] = init_mlp(keys[5], d, m.d_ff_dense or cfg.d_ff, dtype)
    return p


def moe_capacity(m: MoEConfig, tokens_per_group: int) -> int:
    c = int(math.ceil(tokens_per_group * m.top_k * m.capacity_factor / m.n_experts))
    return max(8, -(-c // 8) * 8)   # round up to 8 (TPU sublane)


def moe_dispatch(router_logits: jax.Array, m: MoEConfig, capacity: int):
    """Group-local top-k routing with capacity bucketing.

    router_logits: (G, T, E). Returns (combine_w (G,T,K), expert_idx (G,T,K),
    slot_idx (G,T,K), keep (G,T,K), aux_loss scalar).
    """
    from repro.core.router import position_in_bucket

    G, T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    top_w, top_e = lax.top_k(probs, m.top_k)           # (G,T,K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # slot within the expert bucket — the SAME capacity-bucketed dispatch
    # primitive WebParF's URL dispatcher uses (core/router.py)
    slot, keep = position_in_bucket(top_e.reshape(G, T * m.top_k), E, capacity)
    slot = slot.reshape(G, T, m.top_k)
    keep = keep.reshape(G, T, m.top_k)

    # load-balancing aux loss (Switch/GShard style)
    me = probs.mean(axis=(0, 1))                        # (E,) mean router prob
    ce = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(2).mean(axis=(0, 1))
    aux = (me * ce).sum() * E * m.aux_loss_weight
    return top_w, top_e, slot, keep, aux


def _moe_scatter(xt, e_idx, slot, keep, E: int, capacity: int):
    """Per-k-slice scatter: (T, d) tokens -> (E, C, d) buckets. Looping over
    the K assignments keeps the largest intermediate at (T, d) — a (T, K, d)
    materialization is terabytes at train_4k scale."""
    T, d = xt.shape
    buckets = jnp.zeros((E, capacity, d), xt.dtype)
    for k in range(e_idx.shape[-1]):
        s_safe = jnp.where(keep[:, k], slot[:, k], capacity - 1)
        vals = jnp.where(keep[:, k, None], xt, 0)
        buckets = buckets.at[e_idx[:, k], s_safe].add(vals, mode="drop")
    return buckets


def _moe_combine(y, w, e_idx, slot, keep, capacity: int):
    """Per-k-slice gather + weighted sum: (E, C, d) -> (T, d)."""
    T = e_idx.shape[0]
    out = jnp.zeros((T, y.shape[-1]), jnp.float32)
    for k in range(e_idx.shape[-1]):
        s_safe = jnp.where(keep[:, k], slot[:, k], capacity - 1)
        got = y[e_idx[:, k], s_safe].astype(jnp.float32)
        out = out + jnp.where(keep[:, k], w[:, k], 0.0)[:, None] * got
    return out


def _moe_local(p: Params, m: MoEConfig, xt: jax.Array):
    """Shard-local MoE over (T, d) tokens: route -> bucket -> expert GEMMs ->
    combine. Used directly on hosts without a mesh; inside shard_map on the
    production mesh (where the expert dim exchange is an explicit all_to_all)."""
    T, d = xt.shape
    E = m.n_experts
    logits = xt.astype(jnp.float32) @ p["router"]          # (T, E)
    capacity = moe_capacity(m, T)
    w, e_idx, slot, keep, aux = moe_dispatch(logits[None], m, capacity)
    w, e_idx, slot, keep = w[0], e_idx[0], slot[0], keep[0]
    buckets = _moe_scatter(xt, e_idx, slot, keep, E, capacity)
    h = jnp.einsum("ecd,edf->ecf", buckets, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buckets, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["w_down"])
    out = _moe_combine(y, w, e_idx, slot, keep, capacity)
    return out.astype(xt.dtype), aux


def _moe_spmd(p: Params, cfg: LMConfig, x: jax.Array, mesh, dp, tp):
    """Expert-parallel MoE via shard_map: tokens stay on their data shard for
    routing/bucketing (zero collective), then the (E, C, d) buckets exchange
    over the model axis with two explicit all_to_alls around the expert GEMMs
    — the same capacity-bucketed exchange as the crawler's URL dispatcher
    (core/router.exchange), which is the point (DESIGN.md §2)."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, d = x.shape
    E, tp_size = m.n_experts, mesh.shape[tp]

    def local(xl, router, wg, wu, wd):
        # xl: (B_l, S/tp, d) — the sequence dim is SHARDED over the model
        # axis so every device routes/buckets a distinct token slice (a
        # replicated-x formulation quietly does tp-x redundant expert work —
        # EXPERIMENTS.md §Perf, MoE iteration 1: 16x flops inflation)
        Bl, Sl, _ = xl.shape
        xt = xl.reshape(Bl * Sl, d)
        T = xt.shape[0]
        logits = xt.astype(jnp.float32) @ router
        capacity = moe_capacity(m, T)
        w, e_idx, slot, keep, aux = moe_dispatch(logits[None], m, capacity)
        w, e_idx, slot, keep = w[0], e_idx[0], slot[0], keep[0]
        buckets = _moe_scatter(xt, e_idx, slot, keep, E, capacity)
        # EP exchange: each model shard keeps E/tp experts, gains tp x tokens
        b = lax.all_to_all(buckets, tp, split_axis=0, concat_axis=1, tiled=True)
        h = jnp.einsum("ecd,edf->ecf", b, wg)
        u = jnp.einsum("ecd,edf->ecf", b, wu)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd)
        y = lax.all_to_all(y, tp, split_axis=1, concat_axis=0, tiled=True)
        out = _moe_combine(y, w, e_idx, slot, keep, capacity)
        aux = lax.pmean(aux, dp + (tp,))
        return out.reshape(Bl, Sl, d).astype(xl.dtype), aux

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(dp, tp, None), P(), P(tp, None, None),
                  P(tp, None, None), P(tp, None, None)),
        out_specs=(P(dp, tp, None), P()))
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def moe_block(p: Params, cfg: LMConfig, x: jax.Array, *, n_groups: int):
    """x: (B, S, d) -> (out, aux_loss)."""
    from repro.sharding import rules

    m = cfg.moe
    B, S, d = x.shape
    mesh, dp, tp = rules._ACT["mesh"], rules._ACT["dp"], rules._ACT["tp"]
    use_spmd = (
        mesh is not None
        and B % int(math.prod(mesh.shape[a] for a in dp)) == 0
        and S % mesh.shape[tp] == 0
        and m.n_experts % mesh.shape[tp] == 0)
    if use_spmd:
        out, aux = _moe_spmd(p, cfg, x, mesh, dp, tp)
    else:
        out, aux = _moe_local(p, m, x.reshape(B * S, d))
        out = out.reshape(B, S, d)

    if m.n_shared:
        out = out + mlp_block(p["shared"], x)
    if m.dense_residual:
        out = out + mlp_block(p["dense"], x)
    return out, aux


# ---------------------------------------------------------------------------
# Chunked cross-entropy (never materializes (B, S, V) at once)
# ---------------------------------------------------------------------------

def chunked_softmax_xent(hidden: jax.Array, lm_head: jax.Array,
                         labels: jax.Array, *, chunk: int = 512) -> jax.Array:
    """hidden: (B, S, d); lm_head: (d, V); labels: (B, S) -> scalar mean loss."""
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    n = S // chunk
    hc = jnp.moveaxis(hidden.reshape(B, n, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)

    def step(tot, xs):
        h, l = xs
        # barrier: without it XLA hoists the (loop-invariant-looking) logits
        # matmul out of the scan and materializes ALL chunks' logits at once
        h, l = lax.optimization_barrier((h, l))
        logits = (h @ lm_head).astype(jnp.float32)     # (B, chunk, V)
        logits = constrain(logits, "dp", None, "tp")
        logz = jax.nn.logsumexp(logits, axis=-1)
        # vocab-parallel gold pick (Megatron-style): a one-hot contraction is
        # shard-local over the model-sharded V axis; take_along_axis would
        # force XLA to all-gather the full (B, chunk, V) logits (4.7 GiB at
        # qwen2 train_4k)
        V = logits.shape[-1]
        gold = jnp.einsum("bcv,bcv->bc", logits,
                          jax.nn.one_hot(l, V, dtype=logits.dtype))
        return tot + (logz - gold).sum(), None

    step = jax.checkpoint(step)             # recompute logits chunk in bwd
    tot, _ = lax.scan(step, jnp.zeros((), jnp.float32), (hc, lc))
    return tot / (B * S)
