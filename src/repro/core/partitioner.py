"""Phase I — creating the partitioned Global URL Frontier, plus the
control-plane maps that make the system elastic (C3) and fault-tolerant (C4),
plus the PARTITIONING-POLICY REGISTRY the crawl stages resolve through.

The domain <-> slot indirection is the key mechanism: frontier/bloom rows are
indexed by SLOT; ``slot_of_domain`` says where each domain currently lives.
Rebalancing a dead shard = remapping its domains' slots and migrating rows
(a permutation gather over the sharded row axis — the real migration cost
shows up as collective traffic, as it would on hardware).

``CrawlConfig.partitioning`` names a registered :class:`PartitionPolicy`
(mirroring kernels/registry.py): the three policy decisions a crawl step
makes — who owns a fetched page, which shard a discovered URL is routed to,
and which local frontier row a received URL lands in — live together here as
one named object instead of ``if cfg.partitioning == ...`` branches scattered
through the stages. Third-party policies register with
:func:`register_policy` and become selectable by config string.
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import CrawlConfig
from repro.core import frontier as F
from repro.core import ranker
from repro.core import webgraph as W
from repro.core.dedup import Bloom, init_bloom


class DomainMap(NamedTuple):
    slot_of_domain: jax.Array    # (n_domains,) int32
    domain_of_slot: jax.Array    # (n_slots,) int32 (-1 = empty slot)
    shard_alive: jax.Array       # (n_shards,) bool


def identity_map(cfg: CrawlConfig, n_shards: int) -> DomainMap:
    """Initial layout: shard s hosts domains [s*d, (s+1)*d) in its first d
    slots; the remaining (slot_factor-1)*d slots per shard are spare, so C4
    rebalancing always finds a free slot and never merges queues."""
    n, ns = cfg.n_domains, cfg.n_slots
    per_dom = n // n_shards
    per_slot = ns // n_shards
    dom = np.arange(n)
    shard = dom // per_dom
    slot = shard * per_slot + dom % per_dom
    domain_of_slot = np.full(ns, -1, np.int32)
    domain_of_slot[slot] = dom
    return DomainMap(
        slot_of_domain=jnp.asarray(slot, jnp.int32),
        domain_of_slot=jnp.asarray(domain_of_slot),
        shard_alive=jnp.ones((n_shards,), bool),
    )


def shard_of_slot(slot: jax.Array, n_slots: int, n_shards: int) -> jax.Array:
    return (slot // (n_slots // n_shards)).astype(jnp.int32)


def seed_frontier(cfg: CrawlConfig, n_shards: int) -> F.Frontier:
    """Gather hub seeds per domain (the classification-hierarchy method) and
    build the initial prioritized queues at each domain's slot."""
    dm = identity_map(cfg, n_shards)
    # the barrier keeps the compiler from constant-folding the insert's
    # FIFO-rebase sorts over the all-constant empty frontier, which takes
    # minutes at deployment sizes
    f = jax.lax.optimization_barrier(
        F.init_frontier(cfg.n_slots, cfg.frontier_capacity))
    seeds = W.hub_seeds(cfg)                              # (n_domains, N)
    # the candidate window can hash-collide: dedup per domain or the same
    # seed URL is queued (and crawled) twice — C1 leak #2 found by
    # benchmarks/overlap.py at classify_accuracy=1.0
    from repro.core.dedup import exact_dedup
    seed_mask = exact_dedup(seeds, jnp.ones(seeds.shape, bool))
    by_slot = jnp.zeros((cfg.n_slots, seeds.shape[1]), seeds.dtype)
    by_slot = by_slot.at[dm.slot_of_domain].set(seeds)
    mask = jnp.zeros((cfg.n_slots, seeds.shape[1]), bool)
    mask = mask.at[dm.slot_of_domain].set(seed_mask)
    scores = ranker.score_urls(by_slot, cfg)
    return F.insert(f, by_slot, scores, mask, n_buckets=cfg.n_priority_buckets)


def _free_slot(domain_of_slot: np.ndarray, shard: int, per: int) -> int:
    """First free slot on ``shard``; -1 if the shard is full."""
    for tslot in range(shard * per, (shard + 1) * per):
        if domain_of_slot[tslot] < 0:
            return tslot
    return -1


def rebalance(dm: DomainMap, dead_shards: Sequence[int], *,
              loads: np.ndarray | None = None,
              domain_loads: np.ndarray | None = None) -> DomainMap:
    """C4: redistribute a dead shard's domains over surviving shards,
    balanced by current load (least-loaded first). Host-side control plane —
    this is a scheduler decision, not device compute.

    ``loads`` is the current per-shard load in whatever unit the caller
    balances by (frontier depth for heals). ``domain_loads`` is the
    per-domain estimate in the SAME unit: each placement credits the placed
    domain's own weight to its target, so successive placements spread.
    Without it every placement credits +1 — correct only when ``loads``
    count domains, and the unit mix used to pile every orphan of a hot
    shard onto the single least-loaded survivor."""
    slot_of_domain = np.asarray(dm.slot_of_domain).copy()
    domain_of_slot = np.asarray(dm.domain_of_slot).copy()
    alive = np.asarray(dm.shard_alive).copy()
    n_slots = len(domain_of_slot)
    n_shards = len(alive)
    per = n_slots // n_shards
    alive[list(dead_shards)] = False
    live = np.where(alive)[0]
    if len(live) == 0:
        raise ValueError("rebalance: no live shards remain")
    if loads is None:
        loads = np.zeros(n_shards)
    loads = loads.astype(np.float64).copy()

    def credit(d):
        return 1.0 if domain_loads is None else float(domain_loads[d])

    for s in dead_shards:
        for slot in range(s * per, (s + 1) * per):
            d = domain_of_slot[slot]
            if d < 0:
                continue
            # find a free slot on the least-loaded live shard
            order = live[np.argsort(loads[live], kind="stable")]
            placed = False
            for tgt_shard in order:
                tslot = _free_slot(domain_of_slot, tgt_shard, per)
                if tslot >= 0:
                    domain_of_slot[tslot] = d
                    domain_of_slot[slot] = -1
                    slot_of_domain[d] = tslot
                    loads[tgt_shard] += credit(d)
                    placed = True
                    break
            if not placed:
                # no free slots: merge into the least-loaded shard's matching
                # slot (domain shares a row — tracked by slot_of_domain)
                tgt_shard = order[0]
                tslot = tgt_shard * per + (d % per)
                slot_of_domain[d] = tslot
                domain_of_slot[slot] = -1
                loads[tgt_shard] += credit(d)
    return DomainMap(jnp.asarray(slot_of_domain), jnp.asarray(domain_of_slot),
                     jnp.asarray(alive))


def move_domain(dm: DomainMap, domain: int, target_slot: int) -> DomainMap:
    """Elementary live->live move: remap one domain into a FREE slot (same
    shard allowed — slot defrag). The row migration itself happens in
    ``crawler.apply_rebalance``; this only rewrites the maps."""
    slot_of_domain = np.asarray(dm.slot_of_domain).copy()
    domain_of_slot = np.asarray(dm.domain_of_slot).copy()
    slot = int(slot_of_domain[domain])
    if domain_of_slot[slot] != domain:
        raise ValueError(f"move_domain: domain {domain} shares slot {slot} "
                         f"(merged) — cannot move it independently")
    if domain_of_slot[target_slot] >= 0:
        raise ValueError(f"move_domain: target slot {target_slot} is "
                         f"occupied by domain {int(domain_of_slot[target_slot])}")
    domain_of_slot[target_slot] = domain
    domain_of_slot[slot] = -1
    slot_of_domain[domain] = target_slot
    return DomainMap(jnp.asarray(slot_of_domain), jnp.asarray(domain_of_slot),
                     dm.shard_alive)


def migrate_domains(dm: DomainMap, domains: Sequence[int], *,
                    loads: np.ndarray,
                    domain_loads: np.ndarray | None = None,
                    limit: int | None = None,
                    improve_only: bool = False
                    ) -> Tuple[DomainMap, list]:
    """Live->live elastic migration (DESIGN.md §18): move each candidate
    domain, in the given order, to the least-loaded OTHER live shard with a
    free slot. Unlike :func:`rebalance` there is never a merge fallback — a
    load-driven move that finds no free slot is simply skipped (merging
    queues is a fault necessity, not a load optimization).

    ``loads`` — (n_shards,) current load; ``domain_loads`` — (n_domains,)
    per-domain weight in the same unit (each move debits the source and
    credits the target so successive moves spread). ``improve_only`` skips
    moves that would not strictly lower the source/target pair's peak.
    Returns ``(new_map, moves)`` with ``moves = [(domain, src_shard,
    dst_shard), ...]``; shard liveness is unchanged."""
    slot_of_domain = np.asarray(dm.slot_of_domain).copy()
    domain_of_slot = np.asarray(dm.domain_of_slot).copy()
    alive = np.asarray(dm.shard_alive)
    n_slots = len(domain_of_slot)
    n_shards = len(alive)
    per = n_slots // n_shards
    live = np.where(alive)[0]
    loads = np.asarray(loads, np.float64).copy()
    moves: list = []
    if len(live) < 2:
        return dm, moves
    for d in domains:
        if limit is not None and len(moves) >= limit:
            break
        d = int(d)
        slot = int(slot_of_domain[d])
        if domain_of_slot[slot] != d:
            continue                   # merged domain shares a row: skip
        src_shard = slot // per
        w = 1.0 if domain_loads is None else float(domain_loads[d])
        placed = None
        for tgt_shard in live[np.argsort(loads[live], kind="stable")]:
            if tgt_shard == src_shard:
                continue
            tslot = _free_slot(domain_of_slot, tgt_shard, per)
            if tslot >= 0:
                placed = (int(tgt_shard), tslot)
                break
        if placed is None:
            continue
        tgt_shard, tslot = placed
        if improve_only and loads[tgt_shard] + w >= loads[src_shard]:
            continue                   # the move would just relocate the peak
        domain_of_slot[tslot] = d
        domain_of_slot[slot] = -1
        slot_of_domain[d] = tslot
        loads[tgt_shard] += w
        loads[src_shard] -= w
        moves.append((d, src_shard, tgt_shard))
    if not moves:
        return dm, moves
    return DomainMap(jnp.asarray(slot_of_domain), jnp.asarray(domain_of_slot),
                     dm.shard_alive), moves


def migrate_rows(arrs, old_map: DomainMap, new_map: DomainMap, *,
                 rows: Sequence[str] | None = None):
    """Permute row-indexed state (frontier/bloom leaves) after a remap.

    For every new slot, pull the row of the slot its domain used to occupy.
    jittable — under pjit this is a gather across the sharded row axis (real
    migration traffic).

    ``rows`` names the dict keys that are row-indexed (leading axis =
    n_slots) and should be permuted; every other entry passes through
    untouched. With ``rows=None`` (dict or any pytree) EVERY leaf must be
    row-indexed — a leaf whose leading axis merely happens to equal
    ``n_slots`` would otherwise be silently scrambled, so a non-row leaf
    raises instead of guessing."""
    n_slots = old_map.domain_of_slot.shape[0]
    dom = new_map.domain_of_slot                          # (n_slots,)
    src = jnp.where(dom >= 0,
                    old_map.slot_of_domain[jnp.clip(dom, 0)],
                    jnp.arange(n_slots))
    if rows is not None:
        out = dict(arrs)
        for k in rows:
            a = out[k]
            if a.ndim < 1 or a.shape[0] != n_slots:
                raise ValueError(
                    f"migrate_rows: leaf {k!r} has shape {a.shape}, not "
                    f"row-indexed by n_slots={n_slots}")
            out[k] = a[src]
        return out

    def gather(a):
        if a.ndim < 1 or a.shape[0] != n_slots:
            raise ValueError(
                f"migrate_rows: leaf of shape {a.shape} is not row-indexed "
                f"by n_slots={n_slots}; pass rows=(...) to name the "
                f"row-indexed subset explicitly")
        return a[src]

    return jax.tree.map(gather, arrs)


# ---------------------------------------------------------------------------
# partitioning-policy registry (DESIGN.md §9) — the crawl stages' one lookup
# ---------------------------------------------------------------------------

class PartitionPolicy(NamedTuple):
    """The three per-step decisions a partitioning scheme owns.

    All callables are traced inside the shard-mapped crawl step, so they must
    be jittable; the policy object itself is static (resolved at build/trace
    time from ``cfg.partitioning``).

      canonicalize     — fold URL aliases before dispatch (C2)? webparf does;
                         URL-oriented baselines ship raw URLs.
      split_ownership  — (cfg, state, true_dom, sel) -> (own, foreign) masks:
                         which fetched pages belong to this shard's partition.
      route            — (cfg, state, n_shards, urls, pred_dom, step) -> dest
                         shard (int32) for each staged URL at dispatch time.
      local_row        — (cfg, state, shard, r_slots, urls, pred_dom) ->
                         (row, ok): local frontier row for each received URL
                         and a mask of URLs this shard actually owns.
    """
    name: str
    canonicalize: bool
    split_ownership: Callable
    route: Callable
    local_row: Callable


_POLICIES: Dict[str, PartitionPolicy] = {}


def register_policy(policy: PartitionPolicy) -> PartitionPolicy:
    """Register a policy under ``policy.name`` (error on conflicting re-use)."""
    if policy.name in _POLICIES and _POLICIES[policy.name] is not policy:
        raise ValueError(f"partitioning policy {policy.name!r} registered twice")
    _POLICIES[policy.name] = policy
    return policy


def policies() -> Tuple[str, ...]:
    return tuple(sorted(_POLICIES))


def get_policy(name: str) -> PartitionPolicy:
    """Resolve a ``cfg.partitioning`` string to its registered policy."""
    if name not in _POLICIES:
        raise KeyError(f"unknown partitioning policy {name!r}; "
                       f"registered: {policies()}")
    return _POLICIES[name]


def _webparf_split(cfg, state, true_dom, sel):
    own = (true_dom == state.slot_domain[:, None]) & sel
    return own, sel & ~own


def _webparf_route(cfg, state, n_shards, urls, pred_dom, step):
    slot = state.slot_of_domain[jnp.clip(pred_dom, 0, cfg.n_domains - 1)]
    return shard_of_slot(slot, cfg.n_slots, n_shards)


def _webparf_row(cfg, state, shard, r_slots, urls, pred_dom):
    slot = state.slot_of_domain[jnp.clip(pred_dom, 0, cfg.n_domains - 1)]
    row = slot - shard * r_slots
    ok = (row >= 0) & (row < r_slots)
    return jnp.clip(row, 0, r_slots - 1), ok


def _all_own(cfg, state, true_dom, sel):
    return sel, jnp.zeros_like(sel)


def _hash_route(cfg, state, n_shards, urls, pred_dom, step):
    return (W.hash2(urls, 61) % jnp.uint32(n_shards)).astype(jnp.int32)


def _random_route(cfg, state, n_shards, urls, pred_dom, step):
    # unstable destination: re-keyed every dispatch round
    return (W.hash2(urls, jnp.asarray(step, jnp.uint32) + 62)
            % jnp.uint32(n_shards)).astype(jnp.int32)


def _hash_row(cfg, state, shard, r_slots, urls, pred_dom):
    row = (W.hash2(urls, 63) % jnp.uint32(r_slots)).astype(jnp.int32)
    return row, jnp.ones(urls.shape, bool)


# the paper's scheme + its two baselines (DESIGN.md §9)
WEBPARF = register_policy(PartitionPolicy(
    "webparf", True, _webparf_split, _webparf_route, _webparf_row))
URL_HASH = register_policy(PartitionPolicy(
    "url_hash", False, _all_own, _hash_route, _hash_row))
RANDOM = register_policy(PartitionPolicy(
    "random", False, _all_own, _random_route, _hash_row))


def split_domains(cfg: CrawlConfig) -> CrawlConfig:
    """C3 elasticity: split every domain into two sub-domains (doubling the
    partition count). URL ids are stable — one more bit of the local space
    becomes part of the domain id."""
    import dataclasses
    assert cfg.url_space_log2 > int(np.log2(cfg.n_domains)) + 1
    return dataclasses.replace(cfg, n_domains=cfg.n_domains * 2)
