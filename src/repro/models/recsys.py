"""RecSys family: BERT4Rec, DIEN, Wide&Deep, DCN-v2.

The embedding LOOKUP is the hot path. JAX has no native EmbeddingBag, so it is
built here from ``jnp.take`` + ``jax.ops.segment_sum`` (the assignment's
required substrate). Tables are row-sharded over the `model` mesh axis by the
sharding rules; the baseline lookup is a plain gather (XLA all-gathers the
table — measured in §Roofline), and ``sharded_lookup`` provides the optimized
shard_map masked-psum path used in the §Perf hillclimb.

``retrieval_*`` scores one query against 10^6 candidates as a batched dot +
chunked running top-k — never a loop over candidates.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.compat import shard_map
from repro.configs.base import RecSysConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Embedding substrate
# ---------------------------------------------------------------------------

def embedding_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Plain row gather: (..., ) int -> (..., d)."""
    return jnp.take(table, ids, axis=0, mode="clip")


def embedding_bag(table: jax.Array, ids: jax.Array, *,
                  mode: str = "mean",
                  valid: Optional[jax.Array] = None) -> jax.Array:
    """EmbeddingBag over multi-hot ids (B, bag) -> (B, d).

    Built from jnp.take + jax.ops.segment_sum: gather every id's row, then
    segment-reduce rows belonging to the same example.
    """
    B, bag = ids.shape
    rows = jnp.take(table, ids.reshape(-1), axis=0, mode="clip")   # (B*bag, d)
    if valid is not None:
        rows = rows * valid.reshape(-1, 1).astype(rows.dtype)
    seg = jnp.repeat(jnp.arange(B), bag)
    out = jax.ops.segment_sum(rows, seg, num_segments=B)           # (B, d)
    if mode == "mean":
        cnt = (jnp.full((B,), bag, rows.dtype) if valid is None
               else jax.ops.segment_sum(valid.reshape(-1).astype(rows.dtype),
                                        seg, num_segments=B))
        out = out / jnp.maximum(cnt[:, None], 1.0)
    elif mode == "max":
        out = jax.ops.segment_max(
            jnp.take(table, ids.reshape(-1), axis=0, mode="clip"),
            seg, num_segments=B)
    return out


def sharded_lookup(table: jax.Array, ids: jax.Array, *, mesh, model_axis: str,
                   data_axes) -> jax.Array:
    """TP-sharded lookup: each model shard gathers only its row range and the
    partial results psum over the model axis — collective bytes = output size,
    not table size. Used by the optimized recsys configs (§Perf)."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[model_axis]
    rows_total = table.shape[0]
    rows_per = -(-rows_total // n_shards)

    def local(table_l, ids_l):
        shard = lax.axis_index(model_axis)
        lo = shard * rows_per
        rel = ids_l - lo
        ok = (rel >= 0) & (rel < table_l.shape[0])
        got = jnp.take(table_l, jnp.clip(rel, 0, table_l.shape[0] - 1),
                       axis=0, mode="clip")
        got = jnp.where(ok[..., None], got, 0)
        return lax.psum(got, model_axis)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(model_axis, None), P(data_axes)),
        out_specs=P(data_axes))(table, ids)


def mlp(params, x, *, final_act=None):
    n = len([k for k in params if k.startswith("w")])
    for i in range(n):
        x = x @ params[f"w{i}"] + params[f"b{i}"]
        if i < n - 1:
            x = jax.nn.relu(x)
    return final_act(x) if final_act else x


def init_mlp_params(key, dims, dtype=jnp.float32) -> Params:
    p = {}
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        p[f"w{i}"] = jax.random.normal(k, (dims[i], dims[i + 1]), dtype) * dims[i] ** -0.5
        p[f"b{i}"] = jnp.zeros((dims[i + 1],), dtype)
    return p


def chunked_topk_scores(query: jax.Array, table: jax.Array, *, k: int = 100,
                        chunk: int = 16384) -> Tuple[jax.Array, jax.Array]:
    """query (B, d) x table (V, d) -> (top-k scores, ids) without ever
    materializing the full (B, V) score matrix. The running top-k state is
    constrained to stay batch-sharded — without it XLA reassembles the
    (B, chunk+k) concat across the data axis (64 GiB at serve_bulk)."""
    B, d = query.shape
    V = table.shape[0]
    chunk = min(chunk, V)
    pad = (-V) % chunk
    if pad:
        table = jnp.concatenate([table, jnp.zeros((pad, d), table.dtype)])
    n = table.shape[0] // chunk
    tc = table.reshape(n, chunk, d)

    from repro.sharding.rules import constrain

    def step(carry, xs):
        best_s, best_i = carry
        block, j = xs
        block = lax.optimization_barrier(block)   # keep per-chunk (no hoist)
        # replicate the 4 MB table block (NOT the 1 GiB score block): scores
        # inherit the table's model sharding otherwise, and the top-k concat
        # then all-gathers (B, chunk) every scan step
        block = constrain(block, None, None)
        s = query @ block.T                                  # (B, chunk)
        s = constrain(s, "dp", None)
        ids = j * chunk + jnp.arange(chunk)
        valid = ids < V
        s = jnp.where(valid[None, :], s, -jnp.inf)
        cs = jnp.concatenate([best_s, s], axis=1)
        cs = constrain(cs, "dp", None)
        ci = jnp.concatenate([best_i, jnp.broadcast_to(ids[None], (B, chunk))], axis=1)
        # sort-based top-k merge: lax.top_k lowers to a TopK custom-call that
        # the SPMD partitioner cannot shard (it all-gathers the full (B,
        # chunk+k) state, 62 GiB at serve_bulk); lax.sort partitions fine on
        # the batch dim
        order = jnp.argsort(-cs, axis=1)[:, :k]
        ts = jnp.take_along_axis(cs, order, axis=1)
        return (ts, jnp.take_along_axis(ci, order, axis=1)), None

    init = (jnp.full((B, k), -jnp.inf, query.dtype), jnp.zeros((B, k), jnp.int32))
    (s, i), _ = lax.scan(step, init, (tc, jnp.arange(n)))
    return s, i


def _bce(logit: jax.Array, label: jax.Array) -> jax.Array:
    z = logit.astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0) - z * label + jnp.log1p(jnp.exp(-jnp.abs(z))))


# ===========================================================================
# BERT4Rec — bidirectional transformer over item sequences
# ===========================================================================

def init_bert4rec(key, cfg: RecSysConfig) -> Params:
    d = cfg.embed_dim
    V = cfg.tables["item"]
    ks = jax.random.split(key, 3 + cfg.n_blocks)
    blocks = []
    for kb in ks[3:]:
        kq, kk, kv, ko, k1, k2 = jax.random.split(kb, 6)
        blocks.append({
            "wq": jax.random.normal(kq, (d, d)) * d ** -0.5,
            "wk": jax.random.normal(kk, (d, d)) * d ** -0.5,
            "wv": jax.random.normal(kv, (d, d)) * d ** -0.5,
            "wo": jax.random.normal(ko, (d, d)) * d ** -0.5,
            "ln1": jnp.ones((d,)), "ln2": jnp.ones((d,)),
            "ffn": init_mlp_params(k1, (d, 4 * d, d)),
        })
    return {
        "item": jax.random.normal(ks[0], (V + 2, d)) * d ** -0.5,  # +mask,+pad
        "pos": jax.random.normal(ks[1], (cfg.seq_len, d)) * d ** -0.5,
        "out_ln": jnp.ones((d,)),
        "blocks": blocks,
    }


def _ln(x, g, eps=1e-6):
    m = x.mean(-1, keepdims=True)
    v = jnp.var(x, axis=-1, keepdims=True)
    return (x - m) * lax.rsqrt(v + eps) * g


def bert4rec_encode(params: Params, cfg: RecSysConfig, items: jax.Array) -> jax.Array:
    """items (B, L) -> hidden (B, L, d). Bidirectional (encoder-only)."""
    B, Lseq = items.shape
    d, H = cfg.embed_dim, cfg.n_heads
    hd = d // H
    x = embedding_lookup(params["item"], items) + params["pos"][None, :Lseq]
    for blk in params["blocks"]:
        z = _ln(x, blk["ln1"])
        q = (z @ blk["wq"]).reshape(B, Lseq, H, hd).transpose(0, 2, 1, 3)
        k = (z @ blk["wk"]).reshape(B, Lseq, H, hd).transpose(0, 2, 1, 3)
        v = (z @ blk["wv"]).reshape(B, Lseq, H, hd).transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", a, v).transpose(0, 2, 1, 3).reshape(B, Lseq, d)
        x = x + o @ blk["wo"]
        x = x + mlp(blk["ffn"], _ln(x, blk["ln2"]))
    return _ln(x, params["out_ln"])


def bert4rec_train_loss(params: Params, cfg: RecSysConfig, batch) -> jax.Array:
    """Masked-item prediction with shared sampled negatives (1M-item vocab)."""
    h = bert4rec_encode(params, cfg, batch["items"])            # (B, L, d)
    hm = jnp.take_along_axis(
        h, batch["mask_pos"][..., None], axis=1)                # (B, M, d)
    gold_e = embedding_lookup(params["item"], batch["targets"])  # (B, M, d)
    neg_e = embedding_lookup(params["item"], batch["neg_samples"])  # (NS, d)
    gold = (hm * gold_e).sum(-1, keepdims=True)                 # (B, M, 1)
    neg = jnp.einsum("bmd,nd->bmn", hm, neg_e)                  # (B, M, NS)
    logits = jnp.concatenate([gold, neg], axis=-1)
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - gold[..., 0])


def bert4rec_serve(params: Params, cfg: RecSysConfig, batch):
    """Next-item top-k at the final position (the model's real serving mode)."""
    h = bert4rec_encode(params, cfg, batch["items"])[:, -1]     # (B, d)
    return chunked_topk_scores(h, params["item"][: cfg.tables["item"]], k=100)


def bert4rec_retrieval(params: Params, cfg: RecSysConfig, batch):
    h = bert4rec_encode(params, cfg, batch["items"])[:, -1]     # (1, d)
    cand = embedding_lookup(params["item"], batch["candidates"])  # (C, d)
    scores = h @ cand.T                                          # (1, C)
    return lax.top_k(scores, 100)


# ===========================================================================
# DIEN — GRU interest extraction + AUGRU interest evolution
# ===========================================================================

def _init_gru(key, d_in, d_h) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wx": jax.random.normal(k1, (d_in, 3 * d_h)) * d_in ** -0.5,
        "wh": jax.random.normal(k2, (d_h, 3 * d_h)) * d_h ** -0.5,
        "b": jnp.zeros((3 * d_h,)),
    }


def _gru_cell(p, x, h, a=None):
    gx = x @ p["wx"] + p["b"]
    gh = h @ p["wh"]
    xr, xz, xn = jnp.split(gx, 3, axis=-1)
    hr, hz, hn = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    z = jax.nn.sigmoid(xz + hz)
    n = jnp.tanh(xn + r * hn)
    if a is not None:                      # AUGRU: attention-scaled update gate
        z = a[:, None] * z
    return (1.0 - z) * h + z * n


def init_dien(key, cfg: RecSysConfig) -> Params:
    d = cfg.embed_dim
    ks = jax.random.split(key, 7)
    d_in = 2 * d                           # item ++ category
    return {
        "item": jax.random.normal(ks[0], (cfg.tables["item"], d)) * d ** -0.5,
        "category": jax.random.normal(ks[1], (cfg.tables["category"], d)) * d ** -0.5,
        "user": jax.random.normal(ks[2], (cfg.tables["user"], d)) * d ** -0.5,
        "gru1": _init_gru(ks[3], d_in, cfg.gru_dim),
        "gru2": _init_gru(ks[4], cfg.gru_dim, cfg.gru_dim),
        "att_w": jax.random.normal(ks[5], (cfg.gru_dim, d_in)) * cfg.gru_dim ** -0.5,
        # final MLP: [user, target, final interest] -> 200 -> 80 -> 1
        "mlp": init_mlp_params(ks[6], (d + d_in + cfg.gru_dim,) + tuple(cfg.mlp_dims) + (1,)),
    }


def dien_user_state(params: Params, cfg: RecSysConfig, batch) -> jax.Array:
    """History -> final evolved interest state (B, gru_dim)."""
    hist = jnp.concatenate([
        embedding_lookup(params["item"], batch["hist_items"]),
        embedding_lookup(params["category"], batch["hist_cats"]),
    ], axis=-1)                                                 # (B, S, 2d)
    mask = batch["hist_mask"].astype(jnp.float32)               # (B, S)
    B, S, _ = hist.shape

    def step1(h, xs):
        x, m = xs
        h2 = _gru_cell(params["gru1"], x, h)
        h = jnp.where(m[:, None] > 0, h2, h)
        return h, h

    h0 = jnp.zeros((B, cfg.gru_dim))
    _, hs = lax.scan(step1, h0, (jnp.moveaxis(hist, 1, 0), mask.T))  # (S, B, gd)
    hs = jnp.moveaxis(hs, 0, 1)                                 # (B, S, gd)

    tgt = jnp.concatenate([
        embedding_lookup(params["item"], batch["target_item"]),
        embedding_lookup(params["category"], batch["target_cat"]),
    ], axis=-1)                                                 # (B, 2d)
    att = jnp.einsum("bsg,gd,bd->bs", hs, params["att_w"], tgt)
    att = jnp.where(mask > 0, att, -1e30)
    att = jax.nn.softmax(att, axis=-1)                          # (B, S)

    def step2(h, xs):
        x, a, m = xs
        h2 = _gru_cell(params["gru2"], x, h, a=a)
        return jnp.where(m[:, None] > 0, h2, h), None

    hfin, _ = lax.scan(step2, jnp.zeros((B, cfg.gru_dim)),
                       (jnp.moveaxis(hs, 1, 0), att.T, mask.T))
    return hfin, tgt


def dien_logit(params: Params, cfg: RecSysConfig, batch) -> jax.Array:
    hfin, tgt = dien_user_state(params, cfg, batch)
    u = embedding_lookup(params["user"], batch["user"])         # (B, d)
    feats = jnp.concatenate([u, tgt, hfin], axis=-1)
    return mlp(params["mlp"], feats)[:, 0]


def dien_train_loss(params, cfg, batch):
    return _bce(dien_logit(params, cfg, batch), batch["label"])


def dien_serve(params, cfg, batch):
    return jax.nn.sigmoid(dien_logit(params, cfg, batch))


def dien_retrieval(params: Params, cfg: RecSysConfig, batch):
    """User interest state scored against 1M candidate item embeddings."""
    # use a neutral target (the last history item) to evolve interests
    b = dict(batch)
    b["target_item"] = batch["hist_items"][:, -1]
    b["target_cat"] = batch["hist_cats"][:, -1]
    hfin, _ = dien_user_state(params, cfg, b)                   # (1, gd)
    q = hfin @ params["att_w"]                                  # (1, 2d) project to item space
    cand = jnp.concatenate([
        embedding_lookup(params["item"], batch["candidates"]),
        embedding_lookup(params["category"], batch["cand_cats"]),
    ], axis=-1)                                                 # (C, 2d)
    return lax.top_k(q @ cand.T, 100)


# ===========================================================================
# Wide&Deep
# ===========================================================================

N_WIDE_BUCKETS = 1_000_000
N_WIDE_CROSS = 32


def init_wide_deep(key, cfg: RecSysConfig) -> Params:
    ks = jax.random.split(key, len(cfg.tables) + 3)
    p: Params = {"tables": {}}
    for (name, rows), k in zip(sorted(cfg.tables.items()), ks):
        p["tables"][name] = jax.random.normal(k, (rows, cfg.embed_dim)) * cfg.embed_dim ** -0.5
    d_in = len(cfg.tables) * cfg.embed_dim
    p["deep"] = init_mlp_params(ks[-3], (d_in,) + tuple(cfg.mlp_dims) + (1,))
    p["wide"] = jax.random.normal(ks[-2], (N_WIDE_BUCKETS,)) * 0.01
    p["retrieval_proj"] = jax.random.normal(
        ks[-1], (cfg.mlp_dims[-1], cfg.embed_dim)) * cfg.mlp_dims[-1] ** -0.5
    return p


def _wide_deep_embed(params: Params, cfg: RecSysConfig, batch) -> jax.Array:
    names = sorted(cfg.tables)
    cols = []
    onehot_i = 0
    for name in names:
        if name in cfg.multi_hot:
            cols.append(embedding_bag(params["tables"][name],
                                      batch["bag_ids"][name], mode="mean"))
        else:
            cols.append(embedding_lookup(params["tables"][name],
                                         batch["sparse_ids"][:, onehot_i]))
            onehot_i += 1
    return jnp.concatenate(cols, axis=-1)


def wide_deep_logit(params: Params, cfg: RecSysConfig, batch) -> jax.Array:
    deep_in = _wide_deep_embed(params, cfg, batch)
    deep = mlp(params["deep"], deep_in)[:, 0]
    # wide: hashed cross features, multi-hot sum of scalar weights
    wide = embedding_bag(params["wide"][:, None], batch["wide_ids"],
                         mode="sum")[:, 0]
    return deep + wide


def wide_deep_train_loss(params, cfg, batch):
    return _bce(wide_deep_logit(params, cfg, batch), batch["label"])


def wide_deep_serve(params, cfg, batch):
    return jax.nn.sigmoid(wide_deep_logit(params, cfg, batch))


def wide_deep_retrieval(params: Params, cfg: RecSysConfig, batch):
    """Two-tower factorization: user tower = deep MLP trunk -> proj;
    item tower = first sparse table's embeddings."""
    deep_in = _wide_deep_embed(params, cfg, batch)
    # trunk = all but last deep layer
    x = deep_in
    n = len([k for k in params["deep"] if k.startswith("w")])
    for i in range(n - 1):
        x = jax.nn.relu(x @ params["deep"][f"w{i}"] + params["deep"][f"b{i}"])
    u = x @ params["retrieval_proj"]                            # (1, d)
    first = sorted(cfg.tables)[0]
    cand = embedding_lookup(params["tables"][first], batch["candidates"])
    return lax.top_k(u @ cand.T, 100)


# ===========================================================================
# DCN-v2
# ===========================================================================

def init_dcn_v2(key, cfg: RecSysConfig) -> Params:
    ks = jax.random.split(key, len(cfg.tables) + cfg.n_cross_layers + 3)
    p: Params = {"tables": {}}
    for (name, rows), k in zip(sorted(cfg.tables.items()), ks):
        p["tables"][name] = jax.random.normal(k, (rows, cfg.embed_dim)) * cfg.embed_dim ** -0.5
    d0 = cfg.n_dense + cfg.n_sparse * cfg.embed_dim
    p["cross"] = []
    for i in range(cfg.n_cross_layers):
        k = ks[len(cfg.tables) + i]
        p["cross"].append({
            "w": jax.random.normal(k, (d0, d0)) * d0 ** -0.5,
            "b": jnp.zeros((d0,)),
        })
    p["deep"] = init_mlp_params(ks[-3], (d0,) + tuple(cfg.mlp_dims))
    p["head"] = init_mlp_params(ks[-2], (cfg.mlp_dims[-1] + d0, 1))
    p["retrieval_proj"] = jax.random.normal(
        ks[-1], (cfg.mlp_dims[-1] + d0, cfg.embed_dim)) * (cfg.mlp_dims[-1] + d0) ** -0.5
    return p


def _dcn_x0(params: Params, cfg: RecSysConfig, batch) -> jax.Array:
    embeds = [embedding_lookup(params["tables"][name], batch["sparse_ids"][:, i])
              for i, name in enumerate(sorted(cfg.tables))]
    return jnp.concatenate([batch["dense"]] + embeds, axis=-1)  # (B, d0)


def dcn_v2_trunk(params: Params, cfg: RecSysConfig, batch) -> jax.Array:
    x0 = _dcn_x0(params, cfg, batch)
    x = x0
    for c in params["cross"]:
        x = x0 * (x @ c["w"] + c["b"]) + x                      # DCN-v2 cross
    deep = mlp(params["deep"], x0, final_act=jax.nn.relu)
    return jnp.concatenate([x, deep], axis=-1)


def dcn_v2_logit(params, cfg, batch):
    return mlp(params["head"], dcn_v2_trunk(params, cfg, batch))[:, 0]


def dcn_v2_train_loss(params, cfg, batch):
    return _bce(dcn_v2_logit(params, cfg, batch), batch["label"])


def dcn_v2_serve(params, cfg, batch):
    return jax.nn.sigmoid(dcn_v2_logit(params, cfg, batch))


def dcn_v2_retrieval(params: Params, cfg: RecSysConfig, batch):
    u = dcn_v2_trunk(params, cfg, batch) @ params["retrieval_proj"]  # (1, d)
    first = sorted(cfg.tables)[0]
    cand = embedding_lookup(params["tables"][first], batch["candidates"])
    return lax.top_k(u @ cand.T, 100)


# ===========================================================================
# Dispatch table
# ===========================================================================

INIT = {"bert4rec": init_bert4rec, "dien": init_dien,
        "wide_deep": init_wide_deep, "dcn_v2": init_dcn_v2}
TRAIN_LOSS = {"bert4rec": bert4rec_train_loss, "dien": dien_train_loss,
              "wide_deep": wide_deep_train_loss, "dcn_v2": dcn_v2_train_loss}
SERVE = {"bert4rec": bert4rec_serve, "dien": dien_serve,
         "wide_deep": wide_deep_serve, "dcn_v2": dcn_v2_serve}
RETRIEVAL = {"bert4rec": bert4rec_retrieval, "dien": dien_retrieval,
             "wide_deep": wide_deep_retrieval, "dcn_v2": dcn_v2_retrieval}

N_MASK = 20           # BERT4Rec masked positions per sequence
N_NEG = 8192          # shared sampled negatives


def make_batch(cfg: RecSysConfig, shape, *, rng_key=0, numpy=False):
    """Random-but-valid input batch for a shape cell (smoke tests + benches)."""
    import numpy as np
    rng = np.random.default_rng(rng_key)
    B = shape.get("batch", 2)
    k = cfg.kind

    def ids(rows, *shp):
        return rng.integers(0, rows, shp).astype(np.int32)

    if k == "bert4rec":
        V = cfg.tables["item"]
        b = {"items": ids(V, B, cfg.seq_len)}
        if shape.kind == "train":
            b.update(mask_pos=np.sort(ids(cfg.seq_len, B, N_MASK)),
                     targets=ids(V, B, N_MASK), neg_samples=ids(V, N_NEG))
        if shape.kind == "retrieval":
            b["candidates"] = ids(V, shape["n_candidates"])
    elif k == "dien":
        b = {"hist_items": ids(cfg.tables["item"], B, cfg.seq_len),
             "hist_cats": ids(cfg.tables["category"], B, cfg.seq_len),
             "hist_mask": np.ones((B, cfg.seq_len), bool),
             "user": ids(cfg.tables["user"], B),
             "target_item": ids(cfg.tables["item"], B),
             "target_cat": ids(cfg.tables["category"], B)}
        if shape.kind == "train":
            b["label"] = rng.random(B).round().astype(np.float32)
        if shape.kind == "retrieval":
            C = shape["n_candidates"]
            b["candidates"] = ids(cfg.tables["item"], C)
            b["cand_cats"] = ids(cfg.tables["category"], C)
    elif k == "wide_deep":
        onehot = [n for n in sorted(cfg.tables) if n not in cfg.multi_hot]
        b = {"sparse_ids": np.stack(
                [ids(cfg.tables[n], B) for n in onehot], axis=1),
             "bag_ids": {n: ids(cfg.tables[n], B, bag)
                         for n, bag in cfg.multi_hot.items()},
             "wide_ids": ids(N_WIDE_BUCKETS, B, N_WIDE_CROSS)}
        if shape.kind == "train":
            b["label"] = rng.random(B).round().astype(np.float32)
        if shape.kind == "retrieval":
            b["candidates"] = ids(cfg.tables[sorted(cfg.tables)[0]],
                                  shape["n_candidates"])
    elif k == "dcn_v2":
        b = {"dense": rng.normal(size=(B, cfg.n_dense)).astype(np.float32),
             "sparse_ids": np.stack(
                 [ids(cfg.tables[n], B) for n in sorted(cfg.tables)], axis=1)}
        if shape.kind == "train":
            b["label"] = rng.random(B).round().astype(np.float32)
        if shape.kind == "retrieval":
            b["candidates"] = ids(cfg.tables[sorted(cfg.tables)[0]],
                                  shape["n_candidates"])
    else:
        raise ValueError(k)
    if not numpy:
        b = jax.tree.map(jnp.asarray, b)
    return b
