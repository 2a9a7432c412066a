"""The parallel crawler — WebParF Phase II as an SPMD program.

One ``crawl_step`` = what every C-proc does per cycle, shard_mapped over the
crawler mesh axes (each shard of the ``data``/(``pod``,``data``) axes is one
crawling process). The step itself is a PIPELINE of typed stages
(core/stages.py, DESIGN.md §10):

  allocate (URL allocator) -> fetch_analyze (document loader + page
  analyzer) -> extract_stage (parser + URL database) -> every
  ``dispatch_interval`` steps: dispatch_exchange (batched all_to_all +
  dedup + frontier insert — the URL dispatcher).

Batching the exchange is the paper's C5 claim; the interval is a config knob
and the dispatch is a SEPARATE jitted variant (`step_dispatch`) so the
collective only appears in the HLO of the steps that actually exchange —
and only when the COORDINATION mode communicates at all: what the dispatch
does with foreign URLs (ship / drop / keep / park under a bandwidth quota)
is the fourth registry, ``repro.coordination``, resolved from
``CrawlConfig.coordination`` (DESIGN.md §14).

Three partitioning policies run through the same step (DESIGN.md §9):
  webparf  — domain-partitioned, content-informed canonicalization + routing
  url_hash — URL-oriented partitioning (hash of raw URL -> shard)
  random   — independent crawlers strawman (unstable destination)

This module is the slim composer: it owns pipeline assembly, failure
injection, rebalancing, and the shard_map wrapper. Stage bodies, the state
types, and the stats plumbing live in core/stages.py; both F.select and the
Bloom probe route through kernels/registry.py per ``cfg.kernel_impl``.

API layering (DESIGN.md §11): this module — ``make_crawl_step`` /
``make_spmd_crawler`` plus the re-export block below — is the STABLE
KERNEL-FACING API: what you compose when building a custom driver, stage
set, or dry-run cell. Drivers (examples, launch/crawl.py, benchmarks)
should sit one level up on ``repro.api.CrawlSession``, which owns the loop,
the step counter, and the fused-scan execution path.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.configs.base import CrawlConfig
from repro.core import classifier as CLS
from repro.core import partitioner as PT
from repro.core import stages as ST
# Re-exported state/stat types: together with make_crawl_step /
# make_spmd_crawler below, this block IS the stable kernel-facing API
# surface (consumers wanting the driver loop use repro.api instead).
from repro.core.stages import (CrawlState, FetchReport, NSTAT, SIDX, STATS,
                               Stage, frontier_view, init_state, state_specs,
                               with_frontier)

__all__ = [
    "CrawlState", "FetchReport", "NSTAT", "SIDX", "STATS", "Stage",
    "frontier_view", "with_frontier", "init_state", "state_specs",
    "make_crawl_step", "make_spmd_crawler", "mark_dead", "apply_rebalance",
]


def make_crawl_step(cfg: CrawlConfig, *, n_shards: int, axes,
                    score_fn: Optional[Callable] = None,
                    classify_accuracy: float = CLS.DEFAULT_ACCURACY,
                    stages: Optional[Sequence[Stage]] = None,
                    extra_stages: Sequence[Stage] = (),
                    dispatch_stage: Stage = ST.dispatch_exchange):
    """Build the shard-local step. Returns fn(state_local, dispatch: bool).

    ``score_fn`` (legacy ``(urls, cfg)`` signature) overrides the ordering
    registry's scorer; by default ``cfg.ordering`` decides. ``extra_stages``
    slot scenario stages (politeness, revisit, ...) into the assembled
    pipeline by their ``placement`` attribute; ``stages`` replaces the
    WHOLE per-step pipeline verbatim (expert mode — the first stage must
    create the StepCarry, as ``stages.allocate`` does, and a stateful
    ordering's update stage must be included by hand). ``dispatch_stage``
    runs only on exchange steps.

    Each stage's operations carry a named scope (``stage/allocate``,
    ``stage/fetch_analyze``, ``stage/extract``, ``stage/dispatch``, with
    ``stage/dispatch/exchange`` and ``stage/dispatch/rescore`` inside it;
    other stages ``stage/<function name>``): trace-time metadata that costs
    nothing at run time and lets a device profile split the step."""
    ctx = ST.make_context(cfg, n_shards=n_shards, axes=axes,
                          score_fn=score_fn,
                          classify_accuracy=classify_accuracy)
    if stages is None:
        pipeline = ST.assemble_pipeline(ctx, extra_stages)
    else:
        assert not extra_stages, "pass either stages= or extra_stages=, not both"
        pipeline = tuple(stages)
    assert pipeline, "crawl pipeline needs at least one stage"

    def local_step(state: CrawlState, *, dispatch: bool
                   ) -> Tuple[CrawlState, FetchReport]:
        carry = None
        for stage in pipeline:
            with jax.named_scope(ST.scope_name(stage)):
                state, carry, delta = stage(ctx, state, carry)
                state = ST.apply_delta(state, delta)
        if dispatch:
            with jax.named_scope("stage/dispatch"):
                state, carry, delta = dispatch_stage(ctx, state, carry)
                state = ST.apply_delta(state, delta)
        state = state._replace(step=state.step + 1)
        return state, FetchReport(jnp.where(carry.sel, carry.urls, 0),
                                  carry.sel)

    return local_step


def mark_dead(state: CrawlState, shard_ids) -> CrawlState:
    """Simulate the failure of one or more crawl processes."""
    alive = state.shard_alive
    for s in shard_ids:
        alive = alive.at[s].set(False)
    return state._replace(shard_alive=alive)


# the row-indexed CrawlState leaves a remap migrates (everything whose
# leading axis is a frontier SLOT); named explicitly so migrate_rows never
# guesses by shape
MIGRATED_ROWS = ("f_url", "f_pri", "f_valid", "f_arrival", "f_dropped",
                 "f_inserted", "f_rebased", "bloom_bits", "order_state")


def apply_rebalance(state: CrawlState, cfg: CrawlConfig,
                    new_dm: "PT.DomainMap") -> CrawlState:
    """Migrate frontier/bloom rows to their new owners after a remap — the
    shared mechanism under both C4 heals (dead->live) and load-driven
    elastic moves (live->live, DESIGN.md §18).

    Jittable; under pjit the row permutation is a cross-shard gather — the
    real migration traffic a production system would pay."""
    old_dm = PT.DomainMap(state.slot_of_domain, state.slot_domain,
                          state.shard_alive)
    moved = PT.migrate_rows(
        {k: getattr(state, k) for k in MIGRATED_ROWS},
        old_dm, new_dm, rows=MIGRATED_ROWS)
    # migrate_rows is a gather, so a moved domain's row survives as a stale
    # COPY at its old (now unmapped) slot. Frontier rows there are inert
    # (the old slot belongs to a dead shard), but order_state carries
    # CONSERVED ordering cash (repro/ordering/opic.py) — scrub the duplicate
    # so total cash stays exact across a C4 rebalance.
    slots = jnp.arange(state.order_state.shape[0])
    old_dom = old_dm.domain_of_slot
    dup = ((new_dm.domain_of_slot < 0) & (old_dom >= 0) &
           (new_dm.slot_of_domain[jnp.clip(old_dom, 0)] != slots))
    moved["order_state"] = jnp.where(dup[:, None], 0.0, moved["order_state"])
    # the gather's other hazard: a migration TARGET slot OVERWRITES whatever
    # row sat there. Under webparf those spare rows are structurally empty,
    # but url_hash routing populates every row — destroying the displaced
    # row would leak its cash (slot col 0 + the opic_url URL lane, cols
    # ORD_WIDTH:), so refund it into the incoming row's slot pool
    # (tests/test_invariants.py caught exactly this under url_hash heal).
    from repro.ordering.policies import ORD_WIDTH
    src = jnp.where(new_dm.domain_of_slot >= 0,
                    old_dm.slot_of_domain[jnp.clip(new_dm.domain_of_slot, 0)],
                    slots)
    displaced = src != slots
    old_os = state.order_state
    refund = jnp.where(displaced,
                       old_os[:, 0] + old_os[:, ORD_WIDTH:].sum(axis=1), 0.0)
    moved["order_state"] = moved["order_state"].at[:, 0].add(refund)
    # rebalance's MERGE fallback (no free slot anywhere): the domain maps
    # into an OCCUPIED slot, so no new slot claims it, migrate_rows never
    # copies its row, and the dup scrub above would destroy the ONLY copy
    # of its cash. Refund it into the sharing slot's pool instead.
    tgt = new_dm.slot_of_domain[jnp.clip(old_dom, 0)]
    merged = dup & (new_dm.domain_of_slot[tgt] != old_dom)
    merge_cash = jnp.where(
        merged, old_os[:, 0] + old_os[:, ORD_WIDTH:].sum(axis=1), 0.0)
    moved["order_state"] = moved["order_state"].at[
        jnp.where(merged, tgt, slots.shape[0]), 0].add(
        merge_cash, mode="drop")
    # live->live moves leave the stale source copy on a shard that KEEPS
    # crawling: the old owner would fetch the twin queue again (C1
    # duplication) and its event counters would double-count. Clear every
    # vacated row whose shard is alive in the new map; the moved copy at the
    # new slot is now the only one. Dead-shard vacated rows stay untouched
    # (inert until a future rebalance overwrites them), so C4 heals are
    # bit-identical to before this branch existed. order_state at these
    # slots is already dup-scrubbed above, so cash stays exact.
    n_shards = new_dm.shard_alive.shape[0]
    vacated_live = dup & new_dm.shard_alive[
        PT.shard_of_slot(slots, slots.shape[0], n_shards)]
    for k in MIGRATED_ROWS:
        if k == "order_state":
            continue
        a = moved[k]
        mask = vacated_live.reshape((-1,) + (1,) * (a.ndim - 1))
        moved[k] = jnp.where(mask, jnp.zeros_like(a), a)
    return state._replace(
        **moved, slot_domain=new_dm.domain_of_slot,
        slot_of_domain=new_dm.slot_of_domain, shard_alive=new_dm.shard_alive)


def make_spmd_crawler(cfg: CrawlConfig, mesh, axes=("data",),
                      **kw):
    """Shard_map the local step over the crawler axes of a mesh. Returns
    (init_fn, step_fn(state, dispatch: bool) jitted)."""
    n_shards = int(math.prod(mesh.shape[a] for a in
                             (axes if isinstance(axes, tuple) else (axes,))))
    axes_t = axes if isinstance(axes, tuple) else (axes,)
    local = make_crawl_step(cfg, n_shards=n_shards, axes=axes_t, **kw)
    specs = state_specs(axes_t)
    rep_specs = FetchReport(P(axes_t), P(axes_t))

    def step(state, *, dispatch: bool):
        fn = shard_map(
            partial(local, dispatch=dispatch), mesh=mesh,
            in_specs=(specs,), out_specs=(specs, rep_specs))
        return fn(state)

    # the initial state is built straight into its shardings: the row leaves
    # land on their own shards, and each shard seeds its own Bloom rows
    local_bloom = shard_map(partial(ST.seed_bloom, cfg), mesh=mesh,
                            in_specs=(P(axes_t), P(axes_t)),
                            out_specs=P(axes_t))
    init = jax.jit(
        lambda: init_state(cfg, n_shards,
                           bloom=lambda _, url, valid: local_bloom(url, valid)),
        out_shardings=jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                   is_leaf=lambda s: isinstance(s, P)))
    step_fetch = jax.jit(partial(step, dispatch=False))
    step_dispatch = jax.jit(partial(step, dispatch=True))
    return init, step_fetch, step_dispatch
