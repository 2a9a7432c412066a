"""The staged crawl pipeline — WebParF's Phase II step as composable stages.

``crawler.make_crawl_step`` used to be one 340-line closure; it is now a
pipeline of typed stage functions over a shared ``(CrawlState, StepCarry)``
pair (DESIGN.md §10):

    allocate -> fetch_analyze -> extract_stage  [-> dispatch_exchange]

Every stage has the same signature::

    stage(ctx: StageContext, state: CrawlState, carry: StepCarry | None)
        -> (CrawlState, StepCarry, StatsDelta)

where ``StatsDelta`` is a dict of stat-counter increments the composer folds
into ``state.stats`` after each stage. New scenarios slot in as extra stages
without touching the core four — ``make_politeness_stage`` (per-domain fetch
budgets) and ``make_revisit_stage`` (freshness-driven re-enqueue via
core/freshness.py) are the shipped examples.

All frontier pops and Bloom probes route through the kernel registry
(kernels/registry.py) via ``ctx.impl`` = ``CrawlConfig.kernel_impl``, so the
same pipeline runs the pure-XLA reference, the Pallas TPU kernels, or the
interpreted kernel bodies, selected by config. Likewise every partitioning
decision (ownership split, dispatch routing, local row placement) resolves
through the policy registry (core/partitioner.py) via ``ctx.policy`` =
``get_policy(CrawlConfig.partitioning)`` — no policy string branches here.

Coordination is the fourth registry (repro/coordination, DESIGN.md §14):
``ctx.coord`` = ``get_coordination(CrawlConfig.coordination)`` owns what
``dispatch_exchange`` does with each staged URL — ship it to its predicted
owner (exchange, the default), keep or drop it locally without
communicating (crossover / firewall), or ship a bounded value-aware top-k
and park the rest in the persistent ``CrawlState.outbox_*`` buffer
(batched, ``CrawlConfig.comm_quota``). The stage traces only the machinery
the mode's static flags ask for, so zero-communication modes compile
without the all_to_all.

URL ordering is the third registry (repro/ordering, DESIGN.md §12):
``ctx.score_fn`` is produced by the ordering policy named in
``CrawlConfig.ordering`` and is state-aware — ``score_fn(urls, cfg, state)``
— so stateful estimators (OPIC) can rank by importance learned during the
crawl. The stages themselves carry no ordering logic; they provide two
generic mechanisms the policies build on (DESIGN.md §13):

  * a per-URL float VALUE CHANNEL (``StepCarry.link_cash`` ->
    ``staging_val`` -> a 4th dispatch payload lane) conserved end to end —
    every value is either delivered or refunded, never dropped;
  * a per-URL VALUE LANE over the frontier columns, for policies with
    ``OrderingPolicy.url_lane`` set (opic_url): ``order_state[:, 2:]`` is
    cell-aligned with the frontier queues. ``allocate`` harvests a popped
    URL's cell into ``StepCarry.url_cash``; give-backs travel with their
    value (``frontier.insert_valued``); ``dispatch_exchange`` delivers a
    received value into the exact cell its URL wins, refunding duplicates
    and overflow to the receiving row's slot cash (column 0).
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import CrawlConfig
# ORD_URL0 = first column of the per-URL value lane in order_state (the
# slot-level columns come first); repro.ordering.policies owns the layout
from repro.ordering.policies import ORD_URL0
from repro.core import classifier as CLS
from repro.core import dedup as DD
from repro.core import freshness as FR
from repro.core import frontier as F
from repro.core import partitioner as PT
from repro.core import router as RT
from repro.core import webgraph as W

# stats counters (per shard)
STATS = ("fetched", "fetch_own", "fetch_foreign", "discovered", "dedup_exact",
         "dedup_bloom", "staging_drop", "frontier_drop", "dispatch_sent",
         "dispatch_recv", "dispatch_rounds", "revived",
         "politeness_deferred", "revisit_enqueued",
         "coord_dropped", "coord_deferred")
NSTAT = len(STATS)
SIDX = {n: i for i, n in enumerate(STATS)}

StatsDelta = Dict[str, jax.Array]


class CrawlState(NamedTuple):
    # row-sharded (n_slots, ...)
    f_url: jax.Array
    f_pri: jax.Array
    f_valid: jax.Array
    f_arrival: jax.Array
    f_dropped: jax.Array
    f_inserted: jax.Array
    f_rebased: jax.Array         # (n_slots,) FIFO tie-break rebase events
    bloom_bits: jax.Array
    slot_domain: jax.Array       # (n_slots,) domain living in each slot
    order_state: jax.Array       # (n_slots, ORD_WIDTH) ordering-policy state
                                 # (OPIC: [:, 0] cash, [:, 1] history; zeros
                                 # for stateless policies)
    # shard-sharded (n_shards, ...)
    staging_url: jax.Array       # (n_shards, S) uint32
    staging_src: jax.Array       # (n_shards, S) int32 source-page domain
    staging_val: jax.Array       # (n_shards, S) f32 piggybacked URL values
    staging_n: jax.Array         # (n_shards,) int32
    # the batched coordination mode's persistent carry buffer
    # (repro/coordination/outbox.py) — zeros under the other modes
    outbox_url: jax.Array        # (n_shards, B) uint32
    outbox_src: jax.Array        # (n_shards, B) int32
    outbox_val: jax.Array        # (n_shards, B) f32
    outbox_n: jax.Array          # (n_shards,) int32
    stats: jax.Array             # (n_shards, NSTAT) int32
    # replicated
    slot_of_domain: jax.Array    # (n_domains,)
    shard_alive: jax.Array       # (n_shards,) bool
    step: jax.Array              # () int32


class StageContext(NamedTuple):
    """Static per-build inputs every stage shares (closed over, not traced)."""
    cfg: CrawlConfig
    n_shards: int
    axes: Tuple[str, ...]
    score_fn: Callable           # (urls, cfg, state) -> scores in [0, 1)
    classify_accuracy: float
    cumw: jax.Array              # static Zipf cumulative weights
    k_row: int                   # URLs popped per domain row per step
    S: int                       # staging (dispatch buffer) capacity
    cap_ex: int                  # per-destination exchange bucket size
    impl: str                    # kernel impl knob ("ref"|"pallas"|...)
    policy: PT.PartitionPolicy   # resolved from cfg.partitioning (registry)
    ordering: "object"           # resolved from cfg.ordering (repro.ordering)
    url_lane: bool = False       # ordering keeps a frontier-cell-aligned
                                 # per-URL value lane in order_state[:, 2:]
                                 # (OrderingPolicy.url_lane — opic_url)
    coord: "object" = None       # resolved from cfg.coordination
                                 # (repro.coordination registry — the
                                 # dispatch-time foreign-URL policy)


class StepCarry(NamedTuple):
    """Intra-step dataflow between stages (one shard's view)."""
    shard: jax.Array             # () int32 — this shard's mesh index
    alive: jax.Array             # () bool
    urls: jax.Array              # (r, k) URLs popped this step
    sel: jax.Array               # (r, k) actually-fetched mask
    true_dom: jax.Array          # (r, k) analyzer's domain (fetch_analyze)
    link_cash: jax.Array         # (r, k, O) per-outlink value to piggyback on
                                 # dispatch (ordering policies fill it; zeros
                                 # otherwise)
    links: Optional[jax.Array] = None
                                 # (r, k, O) cached outlink parse — a stage
                                 # that parses (e.g. OPIC's update) stores it
                                 # so extract_stage doesn't re-parse
    url_cash: Optional[jax.Array] = None
                                 # (r, k) cash harvested from the popped
                                 # URLs' frontier cells (url_lane orderings
                                 # only; None otherwise)


class FetchReport(NamedTuple):
    """Per-step observables the benchmarks consume (host-side analysis)."""
    fetched_urls: jax.Array      # (n_slots, k_row) uint32  (0 = none)
    fetched_mask: jax.Array      # (n_slots, k_row) bool


Stage = Callable[[StageContext, CrawlState, Optional[StepCarry]],
                 Tuple[CrawlState, StepCarry, StatsDelta]]


# ---------------------------------------------------------------------------
# state plumbing
# ---------------------------------------------------------------------------

def frontier_view(s: CrawlState) -> F.Frontier:
    return F.Frontier(s.f_url, s.f_pri, s.f_valid, s.f_arrival,
                      s.f_dropped, s.f_inserted, s.f_rebased)


def with_frontier(s: CrawlState, f: F.Frontier) -> CrawlState:
    return s._replace(f_url=f.url, f_pri=f.priority, f_valid=f.valid,
                      f_arrival=f.arrival, f_dropped=f.n_dropped,
                      f_inserted=f.n_inserted, f_rebased=f.n_rebased)


def _with_lane(order_state: jax.Array, table: jax.Array,
               refund: Optional[jax.Array] = None) -> jax.Array:
    """Reassemble order_state from its slot columns + a new URL lane,
    optionally folding a per-row slot-cash refund into column 0 (column
    layout owned by repro/ordering/policies.py: ORD_URL0)."""
    out = jnp.concatenate([order_state[:, :ORD_URL0], table], axis=1)
    return out if refund is None else out.at[:, 0].add(refund)


def ledger_view(state: CrawlState) -> Dict[str, object]:
    """The telemetry snapshot hook (DESIGN.md §17): the shard-local state
    slices ``repro.obs.ledger.snapshot_local`` is allowed to read, named by
    role rather than by leaf. This module owns the CrawlState layout, so a
    state refactor updates this one mapping and every ledger metric keeps
    meaning what it says. The contract: every value is a read-only view of
    the LOCAL shard's slice (under shard_map), the snapshot derives pure
    reductions from them (no host callbacks — it runs inside the fused
    scan), and nothing here may mutate state."""
    return dict(
        frontier=frontier_view(state),      # local rows (r_local, C)
        stats=state.stats,                  # (1, NSTAT) this shard's counters
        staging_n=state.staging_n,          # (1,) outbound URL backlog
        staging_val=state.staging_val,      # (1, S) in-transit cash
        outbox_n=state.outbox_n,            # (1,) parked URL backlog
        outbox_val=state.outbox_val,        # (1, B) parked cash
        order_state=state.order_state,      # (r_local, ORD_WIDTH[+C])
        shard_alive=state.shard_alive,      # (n_shards,) replicated
        step=state.step,                    # () replicated
    )


def apply_delta(state: CrawlState, delta: StatsDelta) -> CrawlState:
    """Fold a stage's stat increments into the shard-local stats row."""
    stats = state.stats
    for name, val in delta.items():
        stats = stats.at[0, SIDX[name]].add(jnp.asarray(val).astype(jnp.int32))
    return state._replace(stats=stats)


def seed_bloom(cfg: CrawlConfig, f_url: jax.Array, f_valid: jax.Array
               ) -> jax.Array:
    """Fresh Bloom rows with the queued seed URLs registered. Without this a
    seed URL re-discovered via an outlink is re-inserted and crawled TWICE
    (the one C1 leak found by benchmarks/overlap.py at
    classify_accuracy=1.0). Row-local, so a sharded init builds each
    shard's filter rows on that shard."""
    bloom = DD.init_bloom(f_url.shape[0], cfg.bloom_bits_log2)
    _, bloom = DD.probe_insert(bloom, f_url, f_valid, k=cfg.bloom_hashes,
                               impl=cfg.kernel_impl)
    return bloom.bits


def init_state(cfg: CrawlConfig, n_shards: int, *,
               bloom: Callable = seed_bloom) -> CrawlState:
    """The initial crawl state. ``bloom`` builds the filter rows from the
    seeded frontier (``crawler.make_spmd_crawler`` passes a shard_mapped
    ``seed_bloom`` so no device ever holds the whole filter)."""
    assert cfg.n_domains % n_shards == 0, (cfg.n_domains, n_shards)
    assert cfg.n_slots % n_shards == 0
    f = PT.seed_frontier(cfg, n_shards)
    dm = PT.identity_map(cfg, n_shards)
    # the seeds fill the first columns of each row (an insert takes free
    # slots in column order), so those columns hold every valid cell
    n0 = min(cfg.seed_urls_per_domain, cfg.frontier_capacity)
    S = cfg.dispatch_capacity
    from repro.coordination.outbox import init_outbox
    from repro.ordering.policies import get_ordering
    return CrawlState(
        f_url=f.url, f_pri=f.priority, f_valid=f.valid, f_arrival=f.arrival,
        f_dropped=f.n_dropped, f_inserted=f.n_inserted, f_rebased=f.n_rebased,
        bloom_bits=bloom(cfg, f.url[:, :n0], f.valid[:, :n0]),
        slot_domain=dm.domain_of_slot,
        order_state=get_ordering(cfg.ordering).init_state(cfg, n_shards),
        staging_url=jnp.zeros((n_shards, S), jnp.uint32),
        staging_src=jnp.zeros((n_shards, S), jnp.int32),
        staging_val=jnp.zeros((n_shards, S), jnp.float32),
        staging_n=jnp.zeros((n_shards,), jnp.int32),
        **init_outbox(cfg, n_shards),
        stats=jnp.zeros((n_shards, NSTAT), jnp.int32),
        slot_of_domain=dm.slot_of_domain,
        shard_alive=dm.shard_alive,
        step=jnp.zeros((), jnp.int32),
    )


def state_specs(axes) -> CrawlState:
    """PartitionSpecs for every leaf (axes = crawler mesh axis name(s))."""
    row = P(axes)
    return CrawlState(
        f_url=row, f_pri=row, f_valid=row, f_arrival=row, f_dropped=row,
        f_inserted=row, f_rebased=row, bloom_bits=row, slot_domain=row,
        order_state=row,
        staging_url=row, staging_src=row, staging_val=row, staging_n=row,
        outbox_url=row, outbox_src=row, outbox_val=row, outbox_n=row,
        stats=row,
        slot_of_domain=P(), shard_alive=P(), step=P(),
    )


def make_context(cfg: CrawlConfig, *, n_shards: int, axes,
                 score_fn: Optional[Callable] = None,
                 classify_accuracy: float) -> StageContext:
    """``score_fn`` override (legacy ``(urls, cfg)`` signature, e.g. a learned
    scorer) wins over the registry; by default ``cfg.ordering`` names the
    :class:`repro.ordering.OrderingPolicy` that produces the scorer."""
    from repro.coordination import get_coordination
    from repro.ordering.policies import as_score_fn, get_ordering
    axes_t = axes if isinstance(axes, tuple) else (axes,)
    r_local = cfg.n_slots // n_shards
    S = cfg.dispatch_capacity
    ordering = get_ordering(cfg.ordering)
    score = (as_score_fn(score_fn) if score_fn is not None else
             ordering.make_score_fn(cfg, n_shards=n_shards, axes=axes_t))
    return StageContext(
        cfg=cfg, n_shards=n_shards, axes=axes_t, score_fn=score,
        classify_accuracy=classify_accuracy, cumw=W.zipf_cumweights(cfg),
        k_row=max(1, cfg.fetch_batch // r_local), S=S,
        cap_ex=max(8, -(-S // n_shards) * 2), impl=cfg.kernel_impl,
        policy=PT.get_policy(cfg.partitioning), ordering=ordering,
        url_lane=bool(getattr(ordering, "url_lane", False)),
        coord=get_coordination(cfg.coordination))


# ---------------------------------------------------------------------------
# the four core stages
# ---------------------------------------------------------------------------

def allocate(ctx: StageContext, state: CrawlState,
             carry: Optional[StepCarry] = None
             ) -> Tuple[CrawlState, StepCarry, StatsDelta]:
    """URL allocator: pop the top-k of each local domain queue, then enforce
    the per-process fetch budget (the downloader has ``fetch_batch`` threads —
    paper §IV.B.2). Candidates beyond the budget go back to their queues; a
    dead shard's pops are all given back so no URL is lost between failure
    and rebalance (C4)."""
    cfg = ctx.cfg
    shard = lax.axis_index(ctx.axes).astype(jnp.int32)
    alive = state.shard_alive[shard]
    fr = frontier_view(state)

    url_cash, table, order_state = None, None, state.order_state
    if ctx.url_lane and cfg.fused_dispatch:
        # fused pop + harvest (DESIGN.md §15): one select_harvest launch
        # pops the top-k, gathers each popped cell's cash, and zeroes the
        # cell in the same VMEM residency — no separate full-table gather
        # and rewrite. Targeted zeroing matches the unfused full invalid-
        # cell mask because invalid cells already hold exactly 0.
        urls, pri, pre_sel, fr, idx, url_cash, table = F.select_harvest(
            fr, order_state[:, ORD_URL0:], ctx.k_row, impl=ctx.impl)
    elif ctx.url_lane:
        # per-URL cash lane, unfused: the select reports which cells it
        # popped (the extended frontier_select contract) and the harvest is
        # a separate gather + whole-table rewrite
        urls, pri, pre_sel, fr, idx = F.select(fr, ctx.k_row, impl=ctx.impl,
                                               return_idx=True)
        table = order_state[:, ORD_URL0:]
        url_cash = jnp.where(pre_sel,
                             jnp.take_along_axis(table, idx, axis=1), 0.0)
        # popped cells zero out (invalid cells already hold exactly 0)
        table = jnp.where(fr.valid, table, 0.0)
    else:
        urls, pri, pre_sel, fr = F.select(fr, ctx.k_row, impl=ctx.impl)
    r_local = urls.shape[0]

    def give_back(fr, table, order_state, url_cash, mask):
        """Return popped URLs (and, on the url lane, their cash) to the
        frontier; insert-overflow refunds to the row's slot cash."""
        if not ctx.url_lane:
            fr = F.insert(fr, urls, ctx.score_fn(urls, cfg, state), mask,
                          n_buckets=cfg.n_priority_buckets)
            return fr, table, order_state, url_cash
        scores = ctx.score_fn(urls, cfg, state, val=url_cash)
        fr, table, refund = F.insert_valued(
            fr, table, urls, scores, mask, jnp.where(mask, url_cash, 0.0),
            n_buckets=cfg.n_priority_buckets, impl=ctx.impl)
        return (fr, table, order_state.at[:, 0].add(refund),
                jnp.where(mask, 0.0, url_cash))

    if r_local * ctx.k_row > cfg.fetch_batch:
        flat_pri = jnp.where(pre_sel, pri, F.NEG).reshape(-1)
        kth = lax.top_k(flat_pri, cfg.fetch_batch)[0][-1]
        budget = (flat_pri >= kth).reshape(pre_sel.shape)
        # ties at the threshold could exceed the budget by a few URLs —
        # acceptable (threads block briefly); give back the rest
        over = pre_sel & ~budget
        fr, table, order_state, url_cash = give_back(
            fr, table, order_state, url_cash, over)
        pre_sel = pre_sel & budget
    sel = pre_sel & alive
    dead_gb = pre_sel & ~alive
    fr, table, order_state, url_cash = give_back(
        fr, table, order_state, url_cash, dead_gb)

    if ctx.url_lane:
        state = state._replace(order_state=_with_lane(order_state, table))
    carry = StepCarry(shard=shard, alive=alive, urls=urls, sel=sel,
                      true_dom=jnp.zeros(urls.shape, jnp.int32),
                      link_cash=jnp.zeros(
                          urls.shape + (cfg.outlinks_per_page,), jnp.float32),
                      url_cash=url_cash)
    return with_frontier(state, fr), carry, {"revived": dead_gb.sum()}


def fetch_analyze(ctx: StageContext, state: CrawlState, carry: StepCarry
                  ) -> Tuple[CrawlState, StepCarry, StatsDelta]:
    """Document loader (simulated fetch) + page analyzer: recover each fetched
    page's true topical domain and split own- vs foreign-partition fetches."""
    cfg = ctx.cfg
    sel = carry.sel
    true_dom = CLS.page_domain(carry.urls, cfg)            # (r, k)
    own, foreign = ctx.policy.split_ownership(cfg, state, true_dom, sel)
    delta = {"fetched": sel.sum(), "fetch_own": own.sum(),
             "fetch_foreign": foreign.sum()}
    return state, carry._replace(true_dom=true_dom), delta


def extract_stage(ctx: StageContext, state: CrawlState, carry: StepCarry
                  ) -> Tuple[CrawlState, StepCarry, StatsDelta]:
    """Parser + URL database: extract outlinks, canonicalize (C2), exact-dedup
    the batch, and append to the staging buffer awaiting the next exchange."""
    cfg = ctx.cfg
    S = ctx.S
    links = (W.outlinks(carry.urls, cfg, ctx.cumw)         # (r, k, O)
             if carry.links is None else carry.links)
    lmask = jnp.broadcast_to(carry.sel[..., None], links.shape)
    lsrc = jnp.broadcast_to(carry.true_dom[..., None], links.shape)
    lrow = jnp.broadcast_to(
        jnp.arange(links.shape[0], dtype=jnp.int32)[:, None, None],
        links.shape)                                       # source frontier row
    flat_u = links.reshape(-1)
    flat_m = lmask.reshape(-1)
    flat_s = lsrc.reshape(-1)
    flat_v = carry.link_cash.reshape(-1)                   # piggybacked value
    flat_r = lrow.reshape(-1)
    discovered = flat_m.sum()

    # dispatcher (local half): canonicalize + exact dedup
    if ctx.policy.canonicalize:
        flat_u = W.canonical(flat_u, cfg)   # content-informed alias fold
    before = flat_m.sum()
    flat_m = DD.exact_dedup(flat_u[None], flat_m[None])[0]
    dedup_exact = before - flat_m.sum()

    # stage into the URL database (batched exchange buffer)
    n0 = state.staging_n[0]
    order = jnp.cumsum(flat_m.astype(jnp.int32)) - 1
    pos = n0 + order
    fits = flat_m & (pos < S)
    pos_safe = jnp.where(fits, pos, S)
    su = jnp.concatenate([state.staging_url[0], jnp.zeros((1,), jnp.uint32)])
    ss = jnp.concatenate([state.staging_src[0], jnp.zeros((1,), jnp.int32)])
    sv = jnp.concatenate([state.staging_val[0], jnp.zeros((1,), jnp.float32)])
    su = su.at[pos_safe].set(jnp.where(fits, flat_u, 0))[None, :S]
    ss = ss.at[pos_safe].set(jnp.where(fits, flat_s, 0))[None, :S]
    sv = sv.at[pos_safe].set(jnp.where(fits, flat_v, 0.0))[None, :S]
    sn = (n0 + fits.sum()).astype(jnp.int32)[None]

    # value-channel conservation: links dropped here (batch dedup or staging
    # overflow) REFUND their value to the source row's order_state instead of
    # losing it (a no-op for stateless orderings — link_cash is zeros)
    lost = lmask.reshape(-1) & ~fits
    r_slots = state.order_state.shape[0]
    order_state = state.order_state.at[
        jnp.where(lost, flat_r, r_slots), 0].add(
        jnp.where(lost, flat_v, 0.0), mode="drop")

    state = state._replace(staging_url=su, staging_src=ss, staging_val=sv,
                           staging_n=sn, order_state=order_state)
    delta = {"discovered": discovered, "dedup_exact": dedup_exact,
             "staging_drop": (flat_m & ~fits).sum()}
    return state, carry, delta


def _entry_scores(ctx: StageContext, state: CrawlState, rb: jax.Array,
                  rbf: Optional[jax.Array], val: Optional[jax.Array] = None
                  ) -> jax.Array:
    """Entry scores for received URLs about to enter the frontier, shared
    by the url-lane and plain insert paths. ``rbf`` marks crossover's
    kept-foreign URLs: those enter at the lowest priority bucket — fetched
    only once the local frontier runs dry (the mode's entry discipline;
    a url-lane rescore may later re-rank them with the rest of the queue)."""
    scores = (ctx.score_fn(rb, ctx.cfg, state, val=val) if val is not None
              else ctx.score_fn(rb, ctx.cfg, state))
    if rbf is not None:
        scores = jnp.where(rbf, 0.0, scores)
    return scores


def dispatch_exchange(ctx: StageContext, state: CrawlState, carry: StepCarry
                      ) -> Tuple[CrawlState, StepCarry, StatsDelta]:
    """URL dispatcher (C5): predict each staged URL's owner, let the
    COORDINATION policy (``ctx.coord``, repro/coordination, DESIGN.md §14)
    assign every candidate a fate — ship through the all_to_all, keep
    locally without communicating, defer to the outbox, or drop — then
    dedup what arrived (exact + Bloom) and insert the survivors into the
    local frontier rows. Under the default ``exchange`` mode everything
    staged ships, bit-for-bit the original dispatcher."""
    cfg = ctx.cfg
    S, n_shards = ctx.S, ctx.n_shards
    shard = carry.shard
    coord = ctx.coord
    su, ss, n = state.staging_url[0], state.staging_src[0], state.staging_n[0]
    sv = state.staging_val[0]
    r_slots = state.slot_domain.shape[0]               # local row count

    # the candidate pool: this interval's staging batch, preceded by the
    # parked outbox for modes that carry one (batched retries age first)
    staged = jnp.arange(S) < n
    if coord.uses_outbox:
        from repro.coordination import outbox as OB
        u, src, val, staged, _parked = OB.merge_pool(state, su, ss, sv,
                                                     staged)
    else:
        u, src, val = su, ss, sv
    # a dead process sends nothing (its staged URLs are lost — the cost
    # of failure the paper's rebalancing bounds; the batched mode instead
    # parks them for a post-revive retry)
    valid = staged & state.shard_alive[shard]

    # predict destination domain / shard (routing is the partitioning
    # policy's call; outbox retries re-route through the LIVE domain map,
    # which is how parked URLs follow a C4 rebalance)
    pred = CLS.predict_domain(u, src, cfg, step=state.step,
                              accuracy=ctx.classify_accuracy)
    dest = ctx.policy.route(cfg, state, n_shards, u, pred, state.step)

    # the coordination decision: ship / keep / defer / drop per item
    plan = coord.plan(ctx, state, shard, u, src, val, dest, staged, valid)
    delta = {"dispatch_sent": plan.ship.sum(),
             "dispatch_rounds": jnp.ones((), jnp.int32),
             "coord_dropped": plan.drop.sum()}

    parked_ok = jnp.zeros_like(staged)
    outbox_leaves = {}
    if coord.uses_outbox:
        outbox_leaves, parked_ok = OB.park(u, src, val, plan.defer,
                                           OB.outbox_capacity(cfg))
        delta["coord_deferred"] = parked_ok.sum()
        delta["coord_dropped"] = (delta["coord_dropped"]
                                  + (plan.defer & ~parked_ok).sum())

    if coord.communicates:
        payload = jnp.stack([u, pred.astype(jnp.uint32),
                             plan.ship.astype(jnp.uint32),
                             lax.bitcast_convert_type(val, jnp.uint32)],
                            axis=-1)                      # (N, 4)
        buckets, bmask, dropped, sent = RT.pack_buckets(
            payload, dest, n_shards, ctx.cap_ex, valid=plan.ship,
            return_keep=True)
        delta["staging_drop"] = dropped
        recv = RT.exchange(buckets, ctx.axes)          # (n_shards, cap_ex, 4)
        r_u = recv[..., 0].reshape(-1)
        r_pred = recv[..., 1].reshape(-1).astype(jnp.int32)
        r_has = recv[..., 2].reshape(-1) > 0
        r_val = lax.bitcast_convert_type(recv[..., 3], jnp.float32
                                         ).reshape(-1)
        r_foreign = jnp.zeros_like(r_has)
    else:
        # zero-communication modes: the "received" set is the kept slice of
        # the local pool — no collective appears in this mode's HLO
        sent = jnp.zeros_like(staged)
        r_u = jnp.where(plan.keep, u, 0)
        r_pred = jnp.where(plan.keep, pred, 0)
        r_has = plan.keep
        r_val = jnp.where(plan.keep, val, 0.0)
        r_foreign = plan.foreign

    # value-channel conservation (sender half): anything staged that was
    # neither sent (dead shard, bucket overflow) nor kept, parked, or
    # already counted refunds its value to the source page's own row rather
    # than vanishing with the URL — firewall's foreign drops land here too
    leftover = staged & ~sent & ~plan.keep & ~parked_ok
    own_slot = state.slot_of_domain[jnp.clip(src, 0, cfg.n_domains - 1)]
    own_row = jnp.clip(own_slot - shard * r_slots, 0, r_slots - 1)
    order_state = state.order_state.at[
        jnp.where(leftover, own_row, r_slots), 0].add(
        jnp.where(leftover, val, 0.0), mode="drop")

    r_m = r_has
    delta["dispatch_recv"] = r_m.sum()

    # exact dedup across everything received this round
    before = r_m.sum()
    r_m = DD.exact_dedup(r_u[None], r_m[None])[0]
    delta["dedup_exact"] = before - r_m.sum()

    # local row for each received URL (the policy's placement decision)
    row, ok = ctx.policy.local_row(cfg, state, shard, r_slots, r_u, r_pred)
    if coord.keeps_foreign:
        # crossover: a kept-foreign URL has no local owner row — park it in
        # a hashed local row instead of rejecting it
        hrow = (W.hash2(r_u, 63) % jnp.uint32(r_slots)).astype(jnp.int32)
        row = jnp.where(r_foreign & ~ok, hrow, row)
        ok = ok | (r_foreign & r_has)
    r_m = r_m & ok

    M = min(r_u.shape[0], cfg.frontier_capacity)
    if ctx.url_lane:
        # per-URL delivery: the value must land in the exact cell its URL
        # wins in the frontier, so it travels THROUGH the per-row bucketing;
        # items that never reach a bucket (exact-dup, unowned, bucket
        # overflow) refund to the receiving row's slot cash here
        lanes = [r_u, lax.bitcast_convert_type(r_val, jnp.uint32)]
        if coord.keeps_foreign:
            lanes.append(r_foreign.astype(jnp.uint32))
        rbp, rbmask, rdrop, rkeep = RT.pack_buckets(
            jnp.stack(lanes, axis=-1),
            row, r_slots, M, valid=r_m, return_keep=True)
        rb = rbp[..., 0]                               # (r_slots, M)
        rv = lax.bitcast_convert_type(rbp[..., 1], jnp.float32)
        rbf = rbp[..., 2] > 0 if coord.keeps_foreign else None
        lost = r_has & ~rkeep
        order_state = order_state.at[
            jnp.where(lost, row, r_slots), 0].add(
            jnp.where(lost, r_val, 0.0), mode="drop")
    else:
        # value-channel conservation (receiver half): deliver every received
        # URL's value to its row BEFORE dedup — the value (e.g. OPIC cash)
        # accrues to the page whether or not the URL itself is fresh
        order_state = order_state.at[
            jnp.where(r_has, row, r_slots), 0].add(
            jnp.where(r_has, r_val, 0.0), mode="drop")

        # bucket per local row, Bloom-dedup, insert into the frontier
        lanes = ([r_u, r_foreign.astype(jnp.uint32)] if coord.keeps_foreign
                 else [r_u])
        rbp, rbmask, rdrop = RT.pack_buckets(
            jnp.stack(lanes, axis=-1), row, r_slots, M, valid=r_m)
        rb = rbp[..., 0]                               # (r_slots, M)
        rbf = rbp[..., 1] > 0 if coord.keeps_foreign else None
    delta["frontier_drop"] = rdrop

    fr = frontier_view(state)
    if ctx.url_lane and cfg.fused_dispatch:
        # fused dedup+deposit (DESIGN.md §15): one kernel pass probes the
        # Bloom row, matches dup'd arrivals against the URLs still QUEUED
        # in the row (tile-by-tile in VMEM — the (r_slots, M, C) twin
        # tensor of the unfused path never materializes), accumulates each
        # twin's cash into its cell, and sums the no-twin refunds
        from repro.kernels.dedup_deposit.ops import dedup_deposit
        seen, bbits, table, dup_refund = dedup_deposit(
            state.bloom_bits, rb, rbmask, rv, fr.url, fr.valid,
            order_state[:, ORD_URL0:], k=cfg.bloom_hashes, impl=ctx.impl)
        bloom = DD.Bloom(bbits, cfg.bloom_bits_log2)
        fresh = rbmask & ~seen
        delta["dedup_bloom"] = (rbmask & seen).sum()
        # placeholder-priority insert: the whole-queue rescore below is the
        # ONLY scoring pass (the rescore fold — unfused insert-time
        # priorities are never observed before that rescore overwrites
        # them, so skipping the per-item score pass is bit-identical; the
        # crossover lowest-bucket clamp is subsumed the same way)
        fr, table, ins_refund = F.place_valued(
            fr, table, rb, fresh, jnp.where(fresh, rv, 0.0), impl=ctx.impl)
        order_state = _with_lane(order_state, table, dup_refund + ins_refund)
        fr = F.rescore(fr, ctx.score_fn(fr.url, cfg, state,
                                        val=order_state[:, ORD_URL0:]),
                       n_buckets=cfg.n_priority_buckets)
    else:
        bloom = DD.Bloom(state.bloom_bits, cfg.bloom_bits_log2)
        seen, bloom = DD.probe_insert(bloom, rb, rbmask, k=cfg.bloom_hashes,
                                      impl=ctx.impl)
        fresh = rbmask & ~seen
        delta["dedup_bloom"] = (rbmask & seen).sum()

        if ctx.url_lane:
            from repro.kernels.opic_update.ops import scatter_cash_cells
            C = fr.url.shape[1]
            # a Bloom-dup'd arrival is usually a URL still QUEUED in this
            # row: find its cell and accumulate the cash there (classic
            # OPIC — a page's cash grows with its in-link rate); only
            # arrivals with no queued twin (already fetched, or a Bloom
            # false positive) refund to the receiving row's slot cash
            dupm = rbmask & ~fresh
            twin = (rb[:, :, None] == fr.url[:, None, :]) \
                & fr.valid[:, None, :] & dupm[:, :, None]  # (r_slots, M, C)
            hit = twin.any(-1)
            cell = jnp.argmax(twin, axis=-1).astype(jnp.int32)
            rowix = jnp.broadcast_to(
                jnp.arange(r_slots, dtype=jnp.int32)[:, None], rb.shape)
            table = scatter_cash_cells(
                order_state[:, ORD_URL0:], rowix, jnp.where(hit, cell, C),
                rv, hit, impl=ctx.impl)
            dup_refund = jnp.where(dupm & ~hit, rv, 0.0).sum(axis=1)
            # fresh survivors' cash is deposited at the cell the insert
            # assigns (scatter_cash_cells inside insert_valued); frontier-
            # overflow drops are refunded by insert_valued itself
            scores = _entry_scores(ctx, state, rb, rbf, val=rv)
            fr, table, ins_refund = F.insert_valued(
                fr, table, rb, scores, fresh, jnp.where(fresh, rv, 0.0),
                n_buckets=cfg.n_priority_buckets, impl=ctx.impl)
            order_state = _with_lane(order_state, table,
                                     dup_refund + ins_refund)
            # re-prioritize the whole queue from the CURRENT cell cash:
            # in-link cash accumulated since insert re-ranks queued URLs
            # once per exchange (the bounded-cost point to refresh every
            # queue at once)
            fr = F.rescore(fr, ctx.score_fn(fr.url, cfg, state,
                                            val=order_state[:, ORD_URL0:]),
                           n_buckets=cfg.n_priority_buckets)
        else:
            scores = _entry_scores(ctx, state, rb, rbf)
            fr = F.insert(fr, rb, scores, fresh,
                          n_buckets=cfg.n_priority_buckets)

    state = with_frontier(state, fr)._replace(
        bloom_bits=bloom.bits, order_state=order_state,
        staging_url=jnp.zeros_like(state.staging_url),
        staging_src=jnp.zeros_like(state.staging_src),
        staging_val=jnp.zeros_like(state.staging_val),
        staging_n=jnp.zeros_like(state.staging_n),
        **outbox_leaves)
    return state, carry, delta


DEFAULT_PIPELINE: Tuple[Stage, ...] = (allocate, fetch_analyze, extract_stage)


def assemble_pipeline(ctx: StageContext,
                      extra_stages: Sequence[Stage] = ()) -> Tuple[Stage, ...]:
    """Compose the per-step pipeline around the core three stages:

        allocate -> [post_allocate extras] -> fetch_analyze
                 -> [post_fetch extras] -> [ordering update] -> extract

    ``extra_stages`` slot in by their ``placement`` attribute
    (``"post_allocate"`` or the default ``"post_fetch"``) in given order;
    the ordering policy's update stage (e.g. OPIC's cash distribution) runs
    last before extract so the value channel is filled when links stage."""
    post_alloc = [s for s in extra_stages
                  if getattr(s, "placement", "post_fetch") == "post_allocate"]
    post_fetch = [s for s in extra_stages
                  if getattr(s, "placement", "post_fetch") != "post_allocate"]
    upd = ctx.ordering.update_stage
    return tuple([allocate, *post_alloc, fetch_analyze, *post_fetch,
                  *([] if upd is None else [upd]), extract_stage])


# ---------------------------------------------------------------------------
# scenario stages — insertable without touching the core four
# ---------------------------------------------------------------------------

def make_politeness_stage(max_per_row: int) -> Stage:
    """Per-domain politeness budget: cap fetches per domain queue per step at
    ``max_per_row``; the overflow re-enters the frontier at its original
    score (a per-host rate limit — insert after ``allocate``)."""

    def politeness(ctx: StageContext, state: CrawlState, carry: StepCarry
                   ) -> Tuple[CrawlState, StepCarry, StatsDelta]:
        order = jnp.cumsum(carry.sel.astype(jnp.int32), axis=1) - 1
        over = carry.sel & (order >= max_per_row)
        if carry.url_cash is None:
            fr = F.insert(frontier_view(state), carry.urls,
                          ctx.score_fn(carry.urls, ctx.cfg, state), over,
                          n_buckets=ctx.cfg.n_priority_buckets)
            state = with_frontier(state, fr)
        else:
            # deferred URLs keep their cash: it re-enters the frontier cell
            # with them (overflow refunds to the row's slot cash)
            scores = ctx.score_fn(carry.urls, ctx.cfg, state,
                                  val=carry.url_cash)
            fr, table, refund = F.insert_valued(
                frontier_view(state), state.order_state[:, ORD_URL0:], carry.urls,
                scores, over, jnp.where(over, carry.url_cash, 0.0),
                n_buckets=ctx.cfg.n_priority_buckets, impl=ctx.impl)
            state = with_frontier(state, fr)._replace(
                order_state=_with_lane(state.order_state, table, refund))
            carry = carry._replace(
                url_cash=jnp.where(over, 0.0, carry.url_cash))
        return (state, carry._replace(sel=carry.sel & ~over),
                {"politeness_deferred": over.sum()})

    politeness.placement = "post_allocate"
    return politeness


def make_revisit_stage(age_steps: int = 32) -> Stage:
    """Freshness-driven revisits (core/freshness.py): fetched URLs re-enter
    their domain queue with an age-discounted score so the allocator
    interleaves revisits with discovery (insert after ``fetch_analyze``).
    Revisited URLs bypass the Bloom filter by design — C1's "never crawl
    twice" applies to discovery, not to deliberate change detection."""

    def revisit(ctx: StageContext, state: CrawlState, carry: StepCarry
                ) -> Tuple[CrawlState, StepCarry, StatsDelta]:
        age = jnp.full(carry.urls.shape, age_steps, jnp.int32)
        fr = FR.reenqueue(frontier_view(state), carry.urls, carry.sel, age,
                          ctx.cfg)
        return (with_frontier(state, fr), carry,
                {"revisit_enqueued": carry.sel.sum()})

    revisit.placement = "post_fetch"
    return revisit
