"""Config dataclasses for all architecture families and input-shape cells.

Every assigned architecture gets one module in this package exposing
``CONFIG`` (the exact published config) and ``SHAPES`` (its input-shape set).
``reduced()`` returns a CPU-smoke-test-sized config of the same family.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple


# ---------------------------------------------------------------------------
# Shape cells
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeSpec:
    """One (arch x shape) dry-run cell.

    ``kind`` selects which step function is lowered:
      lm:     "train" -> train_step, "prefill" -> prefill_step,
              "decode" -> serve_step (1 new token, KV cache of seq_len)
      gnn:    "full_graph" | "minibatch" | "batched_graphs"
      recsys: "train" | "serve" | "retrieval"
    """
    name: str
    kind: str
    dims: Dict[str, int] = field(default_factory=dict)

    def __getitem__(self, k: str) -> int:
        return self.dims[k]

    def get(self, k: str, default: int = 0) -> int:
        return self.dims.get(k, default)


LM_SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec("train_4k", "train", dict(seq_len=4096, global_batch=256)),
    ShapeSpec("prefill_32k", "prefill", dict(seq_len=32768, global_batch=32)),
    ShapeSpec("decode_32k", "decode", dict(seq_len=32768, global_batch=128)),
    # Decode against a 512k KV cache is LINEAR in seq_len (1 query token), so
    # this cell is runnable even for full-attention archs; see DESIGN.md §6.
    ShapeSpec("long_500k", "decode", dict(seq_len=524288, global_batch=1)),
)

GNN_SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec("full_graph_sm", "full_graph",
              dict(n_nodes=2708, n_edges=10556, d_feat=1433, n_classes=7)),
    ShapeSpec("minibatch_lg", "minibatch",
              dict(n_nodes=232965, n_edges=114615892, batch_nodes=1024,
                   fanout0=15, fanout1=10, d_feat=602, n_classes=41)),
    ShapeSpec("ogb_products", "full_graph",
              dict(n_nodes=2449029, n_edges=61859140, d_feat=100, n_classes=47)),
    ShapeSpec("molecule", "batched_graphs",
              dict(n_nodes=30, n_edges=64, batch=128, d_feat=16, n_classes=2)),
)

RECSYS_SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec("train_batch", "train", dict(batch=65536)),
    ShapeSpec("serve_p99", "serve", dict(batch=512)),
    ShapeSpec("serve_bulk", "serve", dict(batch=262144)),
    ShapeSpec("retrieval_cand", "retrieval", dict(batch=1, n_candidates=1_000_000)),
)


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    n_experts: int               # routed experts
    top_k: int
    d_ff_expert: int
    n_shared: int = 0            # always-on shared experts (DeepSeekMoE)
    dense_residual: bool = False # parallel dense MLP branch (Arctic)
    d_ff_dense: int = 0          # width of dense residual / first-k-dense MLP
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    router_jitter: float = 0.0


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    family: str = "lm"
    head_dim: int = 0            # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    first_k_dense: int = 0       # first k layers use the dense MLP even in MoE models
    dtype: str = "bfloat16"
    remat: bool = True           # activation checkpointing per layer (train)
    scan_layers: bool = True     # lax.scan over layers (compile-time + remat unit)

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def n_params(self) -> int:
        """Total parameter count (embedding + per-layer), analytic."""
        d, h = self.d_model, self.head_dim
        attn = d * (self.n_heads * h) + 2 * d * (self.n_kv_heads * h) + (self.n_heads * h) * d
        if self.qkv_bias:
            attn += (self.n_heads + 2 * self.n_kv_heads) * h
        dense_mlp = 3 * d * self.d_ff
        per_layer = []
        for i in range(self.n_layers):
            mlp = dense_mlp
            if self.moe is not None and i >= self.first_k_dense:
                m = self.moe
                mlp = (m.n_experts + m.n_shared) * 3 * d * m.d_ff_expert + d * m.n_experts
                if m.dense_residual:
                    mlp += 3 * d * (m.d_ff_dense or self.d_ff)
            per_layer.append(attn + mlp + 2 * d)
        embed = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return embed + sum(per_layer) + d

    @property
    def n_active_params(self) -> int:
        """Active params per token (MoE: only top_k + shared experts count)."""
        if self.moe is None:
            return self.n_params
        d = self.d_model
        m = self.moe
        full_moe = (m.n_experts + m.n_shared) * 3 * d * m.d_ff_expert
        act_moe = (m.top_k + m.n_shared) * 3 * d * m.d_ff_expert
        n_moe_layers = self.n_layers - self.first_k_dense
        return self.n_params - n_moe_layers * (full_moe - act_moe)


# ---------------------------------------------------------------------------
# GNN family
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GNNConfig:
    name: str
    n_layers: int
    d_hidden: int
    n_heads: int
    aggregator: str = "attn"     # GAT edge-softmax attention
    family: str = "gnn"
    attn_dropout: float = 0.6
    negative_slope: float = 0.2
    dtype: str = "float32"


# ---------------------------------------------------------------------------
# RecSys family
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RecSysConfig:
    name: str
    kind: str                    # "bert4rec" | "dien" | "wide_deep" | "dcn_v2"
    embed_dim: int
    family: str = "recsys"
    # sequential models
    seq_len: int = 0
    n_blocks: int = 0
    n_heads: int = 0
    gru_dim: int = 0
    # tabular models
    n_dense: int = 0
    n_sparse: int = 0
    n_cross_layers: int = 0
    mlp_dims: Tuple[int, ...] = ()
    # embedding tables: (table_name -> n_rows); the lookup is the hot path
    tables: Dict[str, int] = field(default_factory=dict)
    # multi-hot fields use EmbeddingBag (gather + segment_sum); bag size per field
    multi_hot: Dict[str, int] = field(default_factory=dict)
    dtype: str = "float32"
    interaction: str = ""

    @property
    def total_rows(self) -> int:
        return sum(self.tables.values())


# ---------------------------------------------------------------------------
# WebParF (the paper's own system) config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CrawlConfig:
    """WebParF crawl-simulation configuration (the paper's system)."""
    name: str = "webparf"
    family: str = "crawl"
    n_domains: int = 256              # topical domains (Phase I partitions)
    frontier_capacity: int = 4096     # per-domain priority-queue capacity
    fetch_batch: int = 64             # URLs fetched per shard per step (downloader width)
    outlinks_per_page: int = 16       # parser yield per page
    n_priority_buckets: int = 8       # prioritized-queue levels (Fig. 5)
    bloom_bits_log2: int = 24         # Bloom filter per frontier row, byte
                                      # per bit: 2^24 -> 16 MiB per row, 8 GiB
                                      # for the default 512 rows (one v5e
                                      # chip fits 2^23: chip_smoke.py)
    bloom_hashes: int = 4
    dispatch_interval: int = 4        # steps between batched URL exchanges (C5)
    dispatch_capacity: int = 2048     # max URLs exchanged per shard per dispatch
    topical_locality: float = 0.8     # P(outlink stays in-domain) — paper's premise
    link_pop_bias: float = 0.0        # preferential attachment: P(an outlink's
                                      # local target is tournament-picked by
                                      # popularity); 0 = uniform targets (the
                                      # historical web, bit-for-bit)
    alias_fraction: float = 0.05      # URLs that alias another page's content (C2)
    url_space_log2: int = 30          # 2^30 synthetic URL ids
    seed_urls_per_domain: int = 32    # Phase I hub seeds per domain pool
    zipf_a: float = 1.1               # domain-size skew
    partitioning: str = "webparf"     # "webparf" | "url_hash" | "random" (baselines)
    ordering: str = "backlink"        # URL-ordering policy per partitioned queue:
                                      # "fifo" | "backlink" | "opic" |
                                      # "opic_url" | "learned"
                                      # (repro.ordering registry; backlink = the
                                      # ranker's static linear blend; opic_url =
                                      # per-URL cash over the frontier columns)
    coordination: str = "exchange"    # inter-process coordination mode at
                                      # dispatch time (repro.coordination
                                      # registry): "exchange" | "firewall" |
                                      # "crossover" | "batched" — the classic
                                      # parallel-crawler taxonomy; what a
                                      # C-proc does with foreign URLs trades
                                      # communication bandwidth against
                                      # coverage (firewall), overlap
                                      # (crossover), or latency (batched)
    comm_quota: int = -1              # "batched" only: max URLs shipped per
                                      # shard per dispatch (value-aware top-k
                                      # picks what ships; the rest parks in
                                      # the persistent outbox). -1 = unbounded
                                      # (bit-identical URL flow to "exchange")
    slot_factor: int = 2              # frontier rows per domain (spare slots so
                                      # C4 rebalancing never merges queues)
    kernel_impl: str = "auto"         # frontier-select/bloom implementation:
                                      # "ref" | "pallas" | "interpret" | "auto"
                                      # (auto = each family's registered TPU
                                      # default on TPU — Pallas for the
                                      # select families, ref for the rest —
                                      # and ref elsewhere; resolved by
                                      # kernels/registry.py)
    telemetry: bool = False           # observability layer (DESIGN.md §17):
                                      # collect the per-shard, per-step load
                                      # ledger inside the step/scan (extra
                                      # stacked device output — no host
                                      # callbacks in the hot path) and attach
                                      # a wall-clock span tracer to the
                                      # session. Off = bit-for-bit the
                                      # untraced program (test-enforced).
                                      # REPRO_TELEMETRY=1 flips it on
                                      # globally (CI invariants cell).
    rebalance: str = "hot_domain"     # load-driven elastic repartitioning
                                      # policy (repro.rebalance registry,
                                      # DESIGN.md §18): which domains leave
                                      # the peak shard when the trigger fires
    rebalance_threshold: float = 0.0  # arm the elastic rebalancer: when the
                                      # windowed load-imbalance factor
                                      # (CrawlTelemetry.imbalance, max/mean
                                      # frontier depth over live shards)
                                      # EXCEEDS this at a dispatch boundary,
                                      # migrate hot domains to cold shards.
                                      # <= 0 disables (the default — the
                                      # crawl trajectory is then bit-identical
                                      # to a build without the feature;
                                      # test-enforced). Requires telemetry.
    rebalance_window: int = 2         # dispatch boundaries averaged into the
                                      # trigger signal (sliding window — one
                                      # noisy interval doesn't fire a
                                      # migration)
    rebalance_max_domains: int = 4    # max domains migrated per decision
                                      # (bounds one decision's gather traffic)
    fused_dispatch: bool = True       # fuse the dispatch hot path (DESIGN.md
                                      # §15): Bloom probe + queued-twin match
                                      # + cash deposit in one dedup_deposit
                                      # kernel pass, pop + cell harvest in one
                                      # select launch, and a single whole-
                                      # queue rescore instead of a per-insert
                                      # score pass. False keeps the unfused
                                      # composition — the semantics oracle
                                      # and the benchmark baseline
                                      # (bit-identical trajectories either
                                      # way; tests/test_fused_dispatch.py)

    @property
    def n_slots(self) -> int:
        return self.n_domains * self.slot_factor


CRAWL_SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec("crawl_step", "crawl", dict()),
)


ArchConfig = Any  # LMConfig | GNNConfig | RecSysConfig | CrawlConfig


def scaled(cfg, **overrides):
    """Return a copy of a frozen config with fields replaced."""
    return dataclasses.replace(cfg, **overrides)
