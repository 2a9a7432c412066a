"""Frontier invariants — unit + hypothesis property tests."""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.core import frontier as F


def mk(R=2, C=16):
    return F.init_frontier(R, C)


def test_insert_then_select_ordering():
    f = mk(1, 16)
    urls = jnp.asarray([[10, 11, 12, 13]], jnp.uint32)
    scores = jnp.asarray([[0.1, 0.9, 0.5, 0.95]], jnp.float32)
    f = F.insert(f, urls, scores, jnp.ones((1, 4), bool), n_buckets=8)
    got, pri, mask, f = F.select(f, 4)
    got = np.asarray(got)[0]
    assert mask.all()
    # bucketed priority: 0.9/0.95 share the top bucket -> FIFO: 11 before 13
    assert list(got) == [11, 13, 12, 10]


def test_fifo_within_bucket():
    f = mk(1, 16)
    urls = jnp.asarray([[1, 2, 3]], jnp.uint32)
    scores = jnp.full((1, 3), 0.5)          # same bucket
    f = F.insert(f, urls, scores, jnp.ones((1, 3), bool), n_buckets=4)
    got, _, mask, _ = F.select(f, 3)
    assert list(np.asarray(got)[0]) == [1, 2, 3]


def test_capacity_overflow_counted():
    f = mk(1, 4)
    urls = jnp.arange(8, dtype=jnp.uint32)[None]
    f = F.insert(f, urls, jnp.full((1, 8), 0.5), jnp.ones((1, 8), bool),
                 n_buckets=4)
    assert int(f.n_dropped[0]) == 4
    assert int(f.valid.sum()) == 4


def test_select_empties_row():
    f = mk(1, 8)
    f = F.insert(f, jnp.arange(3, dtype=jnp.uint32)[None],
                 jnp.full((1, 3), 0.5), jnp.ones((1, 3), bool), n_buckets=4)
    _, _, m1, f = F.select(f, 8)
    assert int(m1.sum()) == 3
    _, _, m2, _ = F.select(f, 8)
    assert int(m2.sum()) == 0


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2 ** 20),
                          st.floats(0.0, 0.96875, width=32)),
                min_size=0, max_size=24),
       st.integers(1, 8))
def test_property_conservation(items, k):
    """inserted = selectable + dropped; no URL invented or lost."""
    C = 12
    f = mk(1, C)
    if items:
        urls = jnp.asarray([[u for u, _ in items]], jnp.uint32)
        scores = jnp.asarray([[s for _, s in items]], jnp.float32)
        f = F.insert(f, urls, scores, jnp.ones((1, len(items)), bool),
                     n_buckets=8)
    kept = int(f.valid.sum())
    dropped = int(f.n_dropped[0])
    assert kept + dropped == len(items)
    assert kept <= C
    got, pri, mask, f2 = F.select(f, k)
    n_sel = int(mask.sum())
    assert n_sel == min(k, kept)
    # selected URLs were actually inserted
    inserted = {u for u, _ in items}
    for u, m in zip(np.asarray(got)[0], np.asarray(mask)[0]):
        if m:
            assert int(u) in inserted
    # selection removed exactly n_sel
    assert int(f2.valid.sum()) == kept - n_sel


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10), st.integers(0, 10))
def test_property_priority_monotone(n_hi, n_lo):
    """High-bucket URLs always pop before low-bucket ones."""
    f = mk(1, 32)
    urls_hi = jnp.arange(100, 100 + n_hi, dtype=jnp.uint32)[None]
    urls_lo = jnp.arange(200, 200 + n_lo, dtype=jnp.uint32)[None]
    if n_lo:
        f = F.insert(f, urls_lo, jnp.full((1, n_lo), 0.1),
                     jnp.ones((1, n_lo), bool), n_buckets=8)
    if n_hi:
        f = F.insert(f, urls_hi, jnp.full((1, n_hi), 0.9),
                     jnp.ones((1, n_hi), bool), n_buckets=8)
    got, _, mask, _ = F.select(f, n_hi + n_lo + 2)
    got = [int(u) for u, m in zip(np.asarray(got)[0], np.asarray(mask)[0]) if m]
    assert got == list(range(100, 100 + n_hi)) + list(range(200, 200 + n_lo))


def test_fifo_tiebreak_survives_arrival_saturation():
    """Regression: the arrival counter used to clamp at _FIFO_RANGE - 1,
    silently making same-bucket ordering arbitrary on long crawls. insert
    now rebases the sequence (counted in n_rebased) so FIFO order holds
    across the old saturation point."""
    def ins(f, u):
        return F.insert(f, jnp.asarray([[u]], jnp.uint32),
                        jnp.full((1, 1), 0.5), jnp.ones((1, 1), bool),
                        n_buckets=4)

    f = mk(1, 8)
    # a long crawl's counter, one insert away from the old clamp
    f = f._replace(arrival=jnp.asarray([F._FIFO_RANGE - 1], jnp.int32))
    f = ins(f, 1)
    f = ins(f, 2)
    got, _, mask, f = F.select(f, 1)         # pop 1 -> its slot frees up
    assert int(np.asarray(got)[0, 0]) == 1
    f = ins(f, 3)                            # lands in the freed slot 0
    # pre-fix: 2 and 3 tie at the clamp and pop in SLOT order (3 before 2)
    got, _, mask, f = F.select(f, 2)
    assert mask.all()
    assert list(np.asarray(got)[0]) == [2, 3]
    assert int(f.n_rebased[0]) >= 1


def test_fifo_rebase_not_pinned_by_long_lived_entry():
    """A live low-bucket URL from arrival ~0 must not pin the rebase: rank
    compaction restores headroom regardless, so later same-bucket inserts
    still encode distinct priorities and pop in FIFO order."""
    f = mk(1, 8)
    # ancient low-bucket resident (arrival 0), counter about to saturate
    f = F.insert(f, jnp.asarray([[99]], jnp.uint32), jnp.full((1, 1), 0.05),
                 jnp.ones((1, 1), bool), n_buckets=4)
    f = f._replace(arrival=jnp.asarray([F._FIFO_RANGE - 2], jnp.int32))
    for u in (1, 2, 3):
        f = F.insert(f, jnp.asarray([[u]], jnp.uint32),
                     jnp.full((1, 1), 0.5), jnp.ones((1, 1), bool),
                     n_buckets=4)
    assert int(f.n_rebased[0]) >= 1
    assert int(f.arrival[0]) < 64               # headroom actually restored
    got, _, mask, _ = F.select(f, 4)
    assert list(np.asarray(got)[0]) == [1, 2, 3, 99]   # FIFO kept, 99 last


def test_fifo_rebase_no_op_on_short_crawls():
    """Far from saturation the rebase must not fire (bit-stability of the
    existing trajectories)."""
    f = mk(2, 8)
    urls = jnp.asarray([[1, 2], [3, 4]], jnp.uint32)
    f = F.insert(f, urls, jnp.full((2, 2), 0.5), jnp.ones((2, 2), bool),
                 n_buckets=4)
    assert int(f.n_rebased.sum()) == 0


def test_fifo_rebase_counter_drain_refill():
    """Counter inflation via drops (arrival grows by the FULL batch, drops
    included) still rebases cleanly: order stays FIFO per batch."""
    f = mk(1, 4)
    f = f._replace(arrival=jnp.asarray([F._FIFO_RANGE - 5], jnp.int32))
    urls = jnp.arange(1, 9, dtype=jnp.uint32)[None]      # 8 into capacity 4
    f = F.insert(f, urls, jnp.full((1, 8), 0.5), jnp.ones((1, 8), bool),
                 n_buckets=4)
    assert int(f.n_rebased[0]) == 1
    assert int(f.arrival[0]) == 8                        # rebased to 0 + 8
    got, _, mask, _ = F.select(f, 4)
    assert list(np.asarray(got)[0]) == [1, 2, 3, 4]


def test_multi_row_independence():
    f = mk(3, 8)
    urls = jnp.asarray([[1], [2], [3]], jnp.uint32)
    f = F.insert(f, urls, jnp.full((3, 1), 0.5), jnp.ones((3, 1), bool),
                 n_buckets=4)
    got, _, mask, _ = F.select(f, 1)
    assert list(np.asarray(got)[:, 0]) == [1, 2, 3]
    assert mask.all()


# ---------------------------------------------------------------------------
# free-slot targeting: the M = 1 reduction against the general scatter
# ---------------------------------------------------------------------------

def _grid(case, R=64, C=512, seed=0):
    """(valid (R, C), mask (R, 1)) for one fill pattern of the grid."""
    rng = np.random.default_rng(seed)
    mask = rng.random((R, 1)) < 0.8
    if case == "empty":
        valid = np.zeros((R, C), bool)
    elif case == "full":
        valid = np.ones((R, C), bool)
    elif case == "last_free":
        valid = np.ones((R, C), bool)
        valid[:, -1] = False
    elif case == "mask_off":
        valid = rng.random((R, C)) < 0.5
        mask[:] = False
    else:                                   # "fill_<share>"
        valid = rng.random((R, C)) < float(case.split("_")[1])
        valid[0] = True                     # one full row
        valid[1] = True
        valid[1, -1] = False                # one row free at C - 1 alone
    return jnp.asarray(valid), jnp.asarray(mask)


def _filled(valid, seed=0):
    """A frontier and url-lane table holding ``valid`` (the lane invariant:
    invalid cells hold exactly 0)."""
    rng = np.random.default_rng(seed + 1)
    R, C = valid.shape
    f = F.init_frontier(R, C)
    pri = F.encode_priority(jnp.asarray(rng.random((R, C)), jnp.float32),
                            jnp.asarray(rng.integers(0, 1000, (R, C))), 8)
    f = f._replace(valid=valid, priority=jnp.where(valid, pri, F.NEG),
                   url=jnp.where(valid, jnp.asarray(
                       rng.integers(1, 1 << 30, (R, C)), jnp.uint32), 0),
                   arrival=jnp.full((R,), 1000, jnp.int32))
    table = jnp.where(valid, jnp.asarray(rng.random((R, C)), jnp.float32), 0.0)
    return f, table


FILL_CASES = ["empty", "full", "last_free", "mask_off",
              "fill_0.1", "fill_0.9", "fill_0.999"]


@pytest.mark.parametrize("case", FILL_CASES)
def test_first_free_slot_matches_scatter_targets(case):
    """One item a row: the row's first free column is the general path's
    target for order 0, and the drops agree, bit for bit."""
    valid, mask = _grid(case)
    order = jnp.cumsum(mask.astype(jnp.int32), axis=1) - 1
    want_tgt, want_fits = F._free_slots_scatter(valid, mask, order)
    tgt, fits = F._first_free_slot(valid, mask)
    np.testing.assert_array_equal(np.asarray(tgt), np.asarray(want_tgt))
    np.testing.assert_array_equal(np.asarray(fits), np.asarray(want_fits))
    got_tgt, got_fits = F._free_slots(valid, mask, order)
    np.testing.assert_array_equal(np.asarray(got_tgt), np.asarray(tgt))
    np.testing.assert_array_equal(np.asarray(got_fits), np.asarray(fits))


@pytest.mark.parametrize("case", FILL_CASES)
def test_insert_valued_m1_matches_scatter_path(case, monkeypatch):
    """The whole value-carrying insert of one item a row (the allocator's
    give-back) is the same frontier, table and refund on either path."""
    valid, mask = _grid(case)
    f, table = _filled(valid)
    R = valid.shape[0]
    rng = np.random.default_rng(7)
    urls = jnp.asarray(rng.integers(1 << 30, 1 << 31, (R, 1)), jnp.uint32)
    scores = jnp.asarray(rng.random((R, 1)), jnp.float32)
    vals = jnp.where(mask, jnp.asarray(rng.random((R, 1)), jnp.float32), 0.0)

    def run():
        return F.insert_valued(f, table, urls, scores, mask, vals,
                               n_buckets=8)

    got = run()
    monkeypatch.setattr(F, "_free_slots",
                        lambda v, m, o: F._free_slots_scatter(v, m, o))
    want = run()
    for name, a, b in zip(F.Frontier._fields, got[0], want[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"Frontier.{name}")
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


def _scatter_update_sizes(hlo: str) -> list:
    """Element counts of the updates operand of every scatter in ``hlo``
    (HLO text, as ``Lowered.as_text(dialect="hlo")`` prints it)."""
    shape = dict(re.findall(r"([\w.\-]+) = \w+\[([\d,]*)\]", hlo))
    sizes = []
    for m in re.finditer(r" scatter\(([^)]*)\)", hlo):
        upd = m.group(1).split(", ")[-1]
        sizes.append(math.prod(int(d) for d in shape[upd].split(",") if d))
    return sizes


@pytest.mark.parametrize("M", [1, 4])
def test_m1_insert_lowers_without_grid_scatter(M):
    """An (R, C) = (64, 512) insert of one item a row lowers with no
    scatter whose updates span the grid; a wider batch keeps the general
    path's rank scatter of R * C updates."""
    R, C = 64, 512
    f, table = _filled(jnp.zeros((R, C), bool))
    args = (jnp.zeros((R, M), jnp.uint32), jnp.zeros((R, M), jnp.float32),
            jnp.zeros((R, M), bool), jnp.zeros((R, M), jnp.float32))
    hlo = jax.jit(lambda f, t, u, s, m, v: F.insert_valued(
        f, t, u, s, m, v, n_buckets=8)).lower(f, table, *args).as_text(
            dialect="hlo")
    sizes = _scatter_update_sizes(hlo)
    assert sizes, "no scatter found in the insert"
    assert (R * C in sizes) == (M > 1), sizes
