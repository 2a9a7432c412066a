"""Fused dispatch hot path vs the unfused composition (DESIGN.md §15).

``CrawlConfig.fused_dispatch`` swaps three compositions for fused kernel
launches: select+harvest in allocate, dedup+deposit in dispatch_exchange,
and the placeholder-priority insert whose whole-queue rescore is the single
scoring pass (the rescore fold). The unfused path is kept as the semantics
oracle — these tests pin the CrawlState trajectories BIT-IDENTICAL between
the two, across the coordination modes that exercise every fused branch
(exchange = the plain deliver path, crossover = kept-foreign entries whose
lowest-bucket clamp the rescore fold subsumes, batched = outbox-carried
value ahead of the staged pool).

Per-kernel bit-identity matrices live in tests/test_kernels.py; cash
conservation with the fused kernels runs in tests/test_invariants.py
(REPRO_FUSED_DISPATCH gates the CI matrix cell).
"""
import jax
import numpy as np
import pytest

from repro.api import CrawlSession
from repro.configs import get_reduced
from repro.configs.base import scaled
from repro.core import crawler as CR
from repro.core import stages as ST
from repro.launch.mesh import make_host_mesh


@pytest.fixture(scope="module")
def base_cfg():
    return scaled(get_reduced("webparf"), ordering="opic_url",
                  link_pop_bias=1.0)


def crawl_trajectory(cfg, steps):
    mesh = make_host_mesh()
    init, step_f, step_d = CR.make_spmd_crawler(cfg, mesh)
    state = init()
    out = []
    for t in range(steps):
        fn = step_d if (t + 1) % cfg.dispatch_interval == 0 else step_f
        state, rep = fn(state)
        out.append((jax.device_get(state), jax.device_get(rep)))
    return out


def assert_trajectories_equal(a, b, label):
    for t, ((s_a, r_a), (s_b, r_b)) in enumerate(zip(a, b)):
        for name, x, y in zip(ST.CrawlState._fields, s_a, s_b):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"{label} step {t}: CrawlState.{name} diverged")
        for name, x, y in zip(ST.FetchReport._fields, r_a, r_b):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"{label} step {t}: FetchReport.{name} diverged")


# counters of the dedup_deposit walk, which only the fused path runs
WALK = [ST.SIDX["dedup_tiles"], ST.SIDX["dedup_dense_tiles"]]


def walk_counters(traj):
    return np.asarray(traj[-1][0].stats)[:, WALK].sum(0)


def without_walk_counters(traj):
    return [(s._replace(stats=np.delete(np.asarray(s.stats), WALK, axis=1)),
             r) for s, r in traj]


@pytest.mark.parametrize("coordination", ["exchange", "crossover", "batched"])
def test_fused_matches_unfused_trajectory(base_cfg, coordination):
    """The fused path must reproduce the unfused CrawlState trajectory
    bit-for-bit over 2 dispatch intervals (same kernel impl on both
    sides; the per-impl fused matrices live in test_kernels.py). Only the
    fused path walks dedup_deposit's tiles, and a crawl's sparse
    arrivals fit one compacted pass per tile."""
    cfg = scaled(base_cfg, coordination=coordination,
                 comm_quota=6 if coordination == "batched" else -1)
    steps = 2 * cfg.dispatch_interval
    fused = crawl_trajectory(scaled(cfg, fused_dispatch=True), steps)
    plain = crawl_trajectory(scaled(cfg, fused_dispatch=False), steps)
    tiles, dense = walk_counters(fused)
    assert tiles >= 1 and dense == 0, (tiles, dense)
    assert (walk_counters(plain) == 0).all()
    assert_trajectories_equal(without_walk_counters(fused),
                              without_walk_counters(plain), coordination)


def test_fused_interpret_matches_ref(base_cfg):
    """ref <-> interpret bit-identity holds THROUGH the fused kernels too:
    the interpret registrations of dedup_deposit and select_harvest must
    reproduce the fused ref trajectory exactly."""
    cfg = scaled(base_cfg, fused_dispatch=True)
    steps = 2 * cfg.dispatch_interval
    ref = crawl_trajectory(scaled(cfg, kernel_impl="ref"), steps)
    got = crawl_trajectory(scaled(cfg, kernel_impl="interpret"), steps)
    assert_trajectories_equal(ref, got, "ref<->interpret")


def test_fused_flag_is_noop_without_url_lane(base_cfg):
    """Non-url-lane orderings never take the fused branches: flipping the
    flag must not change the trajectory (same program either way)."""
    cfg = scaled(base_cfg, ordering="opic")
    steps = cfg.dispatch_interval
    on = crawl_trajectory(scaled(cfg, fused_dispatch=True), steps)
    off = crawl_trajectory(scaled(cfg, fused_dispatch=False), steps)
    assert_trajectories_equal(on, off, "no-url-lane")


def chunk_trajectory(cfg, chunks, *, fail_at=None):
    """The state after each fused chunk (``chunk_program``: the scanned
    fetch steps, then the dispatch step) and the chunk's reports; shard 0
    dies before chunk ``fail_at``, so from then on every pop goes back."""
    sess = CrawlSession(cfg)
    out = []
    for c in range(chunks):
        if c == fail_at:
            sess.inject_failure(0)
        rep = sess.run_chunk()
        out.append((jax.device_get(sess.state), jax.device_get(rep)))
    return out


@pytest.mark.parametrize("fetch_batch,k_row", [(8, 1), (32, 2)])
def test_chunk_pins_hold_for_give_back_width(base_cfg, fetch_batch, k_row):
    """The allocator's give-backs insert k_row items a row: k_row = 1 (rows
    >= fetch_batch, as in deployment A) targets each row's first free
    column by a reduction, k_row > 1 keeps the rank scatter. Either way the
    fused chunk matches the unfused one, and interpret matches ref, over a
    live chunk (over-budget give-back) and a dead one (all pops back)."""
    cfg = scaled(base_cfg, fetch_batch=fetch_batch, kernel_impl="ref")
    ctx = ST.make_context(cfg, n_shards=1, axes=("data",),
                          classify_accuracy=1.0)
    assert ctx.k_row == k_row
    fused = chunk_trajectory(scaled(cfg, fused_dispatch=True), 2, fail_at=1)
    plain = chunk_trajectory(scaled(cfg, fused_dispatch=False), 2, fail_at=1)
    assert np.asarray(fused[1][0].stats)[:, ST.SIDX["revived"]].sum() > 0
    assert_trajectories_equal(without_walk_counters(fused),
                              without_walk_counters(plain), "fused<->unfused")
    got = chunk_trajectory(scaled(cfg, fused_dispatch=True,
                                  kernel_impl="interpret"), 2, fail_at=1)
    assert_trajectories_equal(fused, got, "ref<->interpret")
