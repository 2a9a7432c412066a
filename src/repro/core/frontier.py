"""The Global URL Frontier — Phase I's partitioned, prioritized URL queues.

One row per domain (row index = the domain's *slot*; partitioner.py owns the
domain<->slot maps so rows can migrate on rebalance). Each row is a fixed-
capacity priority queue: ``priority`` encodes (priority bucket, FIFO arrival)
exactly like the paper's Fig. 5 structure — URLs with the same relevance
bucket form a FIFO list, higher buckets first.

All operations are vectorized over rows and jittable; under shard_map the row
axis is sharded over the crawler (data) mesh axes.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG = jnp.float32(-3e38)
_FIFO_RANGE = 1 << 20          # max arrivals distinguishable within a bucket


class Frontier(NamedTuple):
    url: jax.Array          # (R, C) uint32
    priority: jax.Array     # (R, C) f32; NEG when slot invalid
    valid: jax.Array        # (R, C) bool
    arrival: jax.Array      # (R,) int32 — per-row arrival counter (FIFO order)
    n_dropped: jax.Array    # (R,) int32 — overflow drops (reported, C3/C5)
    n_inserted: jax.Array   # (R,) int32
    n_rebased: jax.Array    # (R,) int32 — FIFO tie-break rebase events


def init_frontier(n_rows: int, capacity: int) -> Frontier:
    return Frontier(
        url=jnp.zeros((n_rows, capacity), jnp.uint32),
        priority=jnp.full((n_rows, capacity), NEG, jnp.float32),
        valid=jnp.zeros((n_rows, capacity), bool),
        arrival=jnp.zeros((n_rows,), jnp.int32),
        n_dropped=jnp.zeros((n_rows,), jnp.int32),
        n_inserted=jnp.zeros((n_rows,), jnp.int32),
        n_rebased=jnp.zeros((n_rows,), jnp.int32),
    )


def encode_priority(score: jax.Array, arrival_seq: jax.Array,
                    n_buckets: int) -> jax.Array:
    """score in [0,1) -> bucketed priority with FIFO tie-break (Fig. 5):
    higher bucket wins; within a bucket, earlier arrival wins.

    bucket * _FIFO_RANGE must stay below 2^24 (f32 integer-exact range) or
    distinct arrivals collapse to the same float — ``insert`` rebases the
    arrival sequence before it can saturate the clamp here."""
    bucket = jnp.clip((score * n_buckets).astype(jnp.int32), 0, n_buckets - 1)
    return (bucket.astype(jnp.float32) * _FIFO_RANGE
            - jnp.minimum(arrival_seq, _FIFO_RANGE - 1).astype(jnp.float32))


def _decode_arrival(priority: jax.Array) -> jax.Array:
    """Invert encode_priority for valid slots: pri = b*RANGE - a, a in
    [0, RANGE) -> b = ceil(pri / RANGE), a = b*RANGE - pri. Exact in f32
    because all encoded values are integers < 2^24."""
    b = jnp.ceil(priority / _FIFO_RANGE)
    return b * _FIFO_RANGE - priority


def _rebase_fifo(f: Frontier, incoming: jax.Array) -> Frontier:
    """Compact each row's FIFO arrival sequence to live RANKS when the
    counter nears ``_FIFO_RANGE`` (long crawls: the counter grows by the
    full batch size on every insert, drops included, so it saturates far
    earlier than 2^20 *live* URLs). Same-bucket ordering after the old
    clamp was silently arbitrary; rank compaction is exact — live arrivals
    map to 0..n_live-1 preserving their strict order (stable argsort; all
    values are f32 integers < 2^24, so encode/decode round-trips bit-for-
    bit) — and the counter restarts at n_live <= capacity, guaranteeing
    headroom no matter how a long-lived low-arrival entry pins the range.
    The O(C log C) sort is behind a ``lax.cond``, so the common no-rebase
    insert keeps its O(C) cost. Events are counted in ``n_rebased``
    (surfaced as the ``fifo_rebase`` stat)."""
    need = (f.arrival + incoming) >= (_FIFO_RANGE - 1)              # (R,)

    def compact(fr: Frontier) -> Frontier:
        arr = _decode_arrival(fr.priority)                          # (R, C)
        key = jnp.where(fr.valid, arr, jnp.float32(_FIFO_RANGE))
        order = jnp.argsort(key, axis=1, stable=True)
        rank = jnp.argsort(order, axis=1, stable=True).astype(jnp.float32)
        bucket = jnp.ceil(fr.priority / _FIFO_RANGE)
        pri = jnp.where(fr.valid & need[:, None],
                        bucket * _FIFO_RANGE - rank, fr.priority)
        n_live = fr.valid.sum(axis=1).astype(jnp.int32)
        return fr._replace(
            priority=pri,
            arrival=jnp.where(need, n_live, fr.arrival),
            n_rebased=fr.n_rebased + need.astype(jnp.int32))

    return lax.cond(need.any(), compact, lambda fr: fr, f)


def bucket_occupancy(priority: jax.Array, valid: jax.Array,
                     n_buckets: int) -> jax.Array:
    """Valid-URL count per priority bucket, summed over rows -> (n_buckets,)
    f32. Inverts ``encode_priority``'s bucket half (pri = b*RANGE - a with
    a in [0, RANGE) means ceil(pri/RANGE) recovers b exactly). This is the
    queue-occupancy read of the telemetry ledger (repro/obs/ledger.py,
    DESIGN.md §17) — a pure reduction over the row arrays, safe to trace
    inside the fused scan. One-hot compare + sum rather than scatter-add:
    XLA CPU serializes scatters, and this runs every step of the fused
    chunk (benchmarks/obs_overhead.py prices it)."""
    b = jnp.ceil(priority / _FIFO_RANGE).astype(jnp.int32)
    b = jnp.clip(b, 0, n_buckets - 1)
    b = jnp.where(valid, b, -1).reshape(-1)
    hot = b[:, None] == jnp.arange(n_buckets, dtype=jnp.int32)[None, :]
    return hot.sum(0).astype(jnp.float32)


def select_arrays(url: jax.Array, priority: jax.Array, valid: jax.Array,
                  *, k: int, return_idx: bool = False) -> Tuple[jax.Array, ...]:
    """Pure-XLA top-k pop on raw row arrays — the "ref" implementation the
    kernel registry dispatches to (kernels/frontier_select registers it).

    Returns (urls (R,k), priorities (R,k), mask (R,k), priority', valid');
    with ``return_idx`` also the popped cell indices (R,k) int32 — the
    column each pop came from, which url-lane orderings need to harvest the
    cell-aligned value table without recomputing the top-k (DESIGN.md §13).
    Indices in masked-out lanes point at whatever NEG cell the top-k
    surfaced — callers must gate on the mask."""
    masked = jnp.where(valid, priority, NEG)
    pri, idx = lax.top_k(masked, k)                      # (R, k)
    got = jnp.take_along_axis(url, idx, axis=1)
    mask = pri > NEG * 0.5
    # invalidate selected slots
    rows = jnp.arange(url.shape[0])[:, None]
    new_valid = valid.at[rows, idx].set(
        jnp.where(mask, False, jnp.take_along_axis(valid, idx, axis=1)))
    new_pri = priority.at[rows, idx].set(jnp.where(mask, NEG, pri))
    if return_idx:
        return got, pri, mask, new_pri, new_valid, idx.astype(jnp.int32)
    return got, pri, mask, new_pri, new_valid


def select(f: Frontier, k: int, *, impl: str = "ref",
           return_idx: bool = False):
    """Pop the top-k URLs of every row (the URL allocator's read).

    ``impl`` picks the implementation via the kernel registry ("ref" |
    "pallas" | "interpret" | "auto" — kernels/registry.py). Returns
    (urls (R,k), priorities (R,k), mask (R,k), new frontier); with
    ``return_idx`` also the popped cell indices (see ``select_arrays`` —
    ops.select recomputes them outside the kernel for implementations that
    don't surface them natively)."""
    from repro.kernels.frontier_select.ops import select as _kernel_select
    out = _kernel_select(f.url, f.priority, f.valid, k=k, impl=impl,
                         return_idx=return_idx)
    got, pri, mask, new_pri, new_valid = out[:5]
    fr = f._replace(valid=new_valid, priority=new_pri)
    if return_idx:
        return got, pri, mask, fr, out[5]
    return got, pri, mask, fr


def select_harvest(f: Frontier, table: jax.Array, k: int, *,
                   impl: str = "ref"):
    """Fused pop + url-lane cash harvest (DESIGN.md §15): one kernel launch
    pops the top-k of every row, gathers each popped cell's value from
    ``table`` (R, C), and zeroes the popped cells in the same pass.

    Returns (urls (R,k), priorities (R,k), mask (R,k), new frontier,
    idx (R,k) int32, cash (R,k) f32, table'). Because the url lane keeps
    invalid cells at exactly 0.0 (the lane invariant, tests/test_invariants),
    the targeted popped-cell zeroing is bit-identical to the unfused path's
    full ``where(valid, table, 0)`` mask."""
    from repro.kernels.frontier_select.ops import select_harvest as _kern
    got, pri, mask, new_pri, new_valid, idx, cash, table2 = _kern(
        f.url, f.priority, f.valid, table, k=k, impl=impl)
    return (got, pri, mask, f._replace(valid=new_valid, priority=new_pri),
            idx, cash, table2)


def _free_slots_scatter(valid: jax.Array, mask: jax.Array,
                        order: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """General free-slot targeting: the o-th incoming item goes to the o-th
    invalid slot (in column order). Each free slot's column index is
    scattered at its rank among free slots — ranks are unique per row, so
    the scatter is collision-free. On the v5e that scatter serialises its
    R*C updates (about 18 ms at 512 x 4096), so M = 1 takes
    ``_first_free_slot`` instead. Returns (tgt_safe, fits), both (R, M)."""
    R, C = valid.shape
    free = ~valid
    rank = jnp.cumsum(free.astype(jnp.int32), axis=1) - free        # exclusive
    n_free = free.sum(axis=1)                                       # (R,)
    rows = jnp.arange(R)[:, None]
    iota_c = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (R, C))
    free_idx = jnp.full((R, C), C, jnp.int32).at[
        rows, jnp.where(free, rank, C)].min(iota_c, mode="drop")    # (R, C)
    fits = mask & (order < n_free[:, None])
    tgt = jnp.take_along_axis(
        free_idx, jnp.clip(order, 0, C - 1), axis=1)                # (R, M)
    # dropped items scatter into a trash column (index C) so they can never
    # collide with a legitimate write — duplicate-index scatter order is
    # undefined in XLA, so collisions must be structurally impossible
    return jnp.where(fits, tgt, C), fits


def _first_free_slot(valid: jax.Array, mask: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Free-slot targeting for one incoming item a row (M = 1): the item's
    slot is the row's first free column, one min-reduction over the grid.
    Same (tgt_safe, fits) as ``_free_slots_scatter``; C is the trash
    column of a dropped item."""
    C = valid.shape[1]
    first = jnp.min(jnp.where(valid, C, jnp.arange(C, dtype=jnp.int32)),
                    axis=1, keepdims=True)                          # (R, 1)
    fits = mask & (first < C)
    return jnp.where(fits, first, C), fits


def _free_slots(valid: jax.Array, mask: jax.Array, order: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Each incoming item's destination column: (tgt_safe, fits), (R, M).
    The form follows the static width M of the incoming batch."""
    if mask.shape[1] == 1:
        return _first_free_slot(valid, mask)
    return _free_slots_scatter(valid, mask, order)


def _plan_insert(f: Frontier, urls: jax.Array, scores: jax.Array,
                 mask: jax.Array, *, n_buckets: int):
    """Shared insert core: FIFO rebase, priority encoding, and free-slot
    targeting. Returns (rebased frontier, pri, fits, tgt_safe, incoming)
    where ``tgt_safe`` (R, M) is each item's destination column (C for
    dropped items — the trash column)."""
    incoming = mask.sum(axis=1).astype(jnp.int32)                   # (R,)
    f = _rebase_fifo(f, incoming)
    # FIFO arrival sequence for the incoming batch
    order = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1          # (R, M)
    pri = encode_priority(scores, f.arrival[:, None] + order, n_buckets)

    with jax.named_scope("frontier/free_slots"):
        tgt_safe, fits = _free_slots(f.valid, mask, order)
    return f, pri, fits, tgt_safe, incoming


def _apply_insert(f: Frontier, urls: jax.Array, pri: jax.Array,
                  mask: jax.Array, fits: jax.Array, tgt_safe: jax.Array,
                  incoming: jax.Array) -> Frontier:
    R, C = f.url.shape
    rows = jnp.arange(R)[:, None]

    def put(arr, vals, fill):
        ext = jnp.concatenate(
            [arr, jnp.full((R, 1), fill, arr.dtype)], axis=1)
        ext = ext.at[rows, tgt_safe].set(jnp.where(fits, vals, fill).astype(arr.dtype))
        return ext[:, :C]

    url2 = put(f.url, urls, 0)
    pri2 = put(f.priority, pri, NEG)
    val2 = put(f.valid, fits, False) | f.valid
    return Frontier(
        url=url2, priority=pri2, valid=val2,
        arrival=f.arrival + incoming,
        n_dropped=f.n_dropped + (mask & ~fits).sum(axis=1).astype(jnp.int32),
        n_inserted=f.n_inserted + fits.sum(axis=1).astype(jnp.int32),
        n_rebased=f.n_rebased,
    )


def insert(f: Frontier, urls: jax.Array, scores: jax.Array,
           mask: jax.Array, *, n_buckets: int) -> Frontier:
    """Insert up to M URLs per row into free slots (dispatcher's write).

    urls/scores/mask: (R, M). Items beyond the row's free capacity are
    dropped and counted (bounded queues — DESIGN.md §2)."""
    f, pri, fits, tgt_safe, incoming = _plan_insert(
        f, urls, scores, mask, n_buckets=n_buckets)
    return _apply_insert(f, urls, pri, mask, fits, tgt_safe, incoming)


def insert_valued(f: Frontier, table: jax.Array, urls: jax.Array,
                  scores: jax.Array, mask: jax.Array, values: jax.Array,
                  *, n_buckets: int, impl: str = "ref"
                  ) -> Tuple[Frontier, jax.Array, jax.Array]:
    """Value-carrying insert: each inserted URL's ``values`` entry lands in
    ``table`` (R, C) at the SAME cell the URL occupies in the frontier — the
    per-URL cash lane of the ``opic_url`` ordering (DESIGN.md §13). The cell
    write goes through the ``opic_update`` kernel family's cell scatter
    (``impl`` selects ref | pallas | interpret). Dropped items REFUND their
    value per row instead of losing it (the lane's bounded-memory rule).

    Returns (frontier', table', refund (R,))."""
    R, C = f.url.shape
    f2, pri, fits, tgt_safe, incoming = _plan_insert(
        f, urls, scores, mask, n_buckets=n_buckets)
    out = _apply_insert(f2, urls, pri, mask, fits, tgt_safe, incoming)
    from repro.kernels.opic_update.ops import scatter_cash_cells
    rows = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[:, None],
                            tgt_safe.shape)
    table2 = scatter_cash_cells(table, rows, tgt_safe, values, fits,
                                impl=impl)
    refund = jnp.where(mask & ~fits, values, 0.0).sum(axis=1)
    return out, table2, refund


def place_valued(f: Frontier, table: jax.Array, urls: jax.Array,
                 mask: jax.Array, values: jax.Array, *, impl: str = "ref"
                 ) -> Tuple[Frontier, jax.Array, jax.Array]:
    """Valued insert with PLACEHOLDER priorities — the rescore fold
    (DESIGN.md §15). Items land in bucket 0 (pri = -arrival, which
    ``_decode_arrival`` inverts exactly: both terms are f32 integers
    < 2^20), so slot targeting, drops, and refunds are identical to
    ``insert_valued`` while the per-item score pass is skipped entirely.
    The caller MUST ``rescore`` the queue before its priorities are next
    observed — dispatch's whole-queue re-prioritization is that rescore,
    making it the single scoring pass of the fused dispatch path."""
    zero = jnp.zeros(urls.shape, jnp.float32)
    return insert_valued(f, table, urls, zero, mask, values, n_buckets=1,
                         impl=impl)


def rescore(f: Frontier, scores: jax.Array, *, n_buckets: int) -> Frontier:
    """Re-bucket every queued URL from fresh ``scores`` (R, C), preserving
    each URL's FIFO arrival stamp — the periodic queue re-prioritization a
    stateful ordering needs once importance estimates move after insert
    (opic_url runs this at every dispatch). Invalid cells keep NEG."""
    arr = _decode_arrival(f.priority)          # exact for valid cells
    pri = encode_priority(scores, arr, n_buckets)
    return f._replace(priority=jnp.where(f.valid, pri, f.priority))


def occupancy(f: Frontier) -> jax.Array:
    return f.valid.sum(axis=1)
