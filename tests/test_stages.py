"""Stage pipeline + kernel registry: per-stage units, registry resolution,
and ref<->interpret bit-equivalence driven through the real crawl step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import scaled
from repro.core import crawler as CR
from repro.core import stages as ST
from repro.kernels import registry
from repro.launch.mesh import make_host_mesh

# importing the ops modules registers every implementation
import repro.kernels.bloom.ops  # noqa: F401
import repro.kernels.flash_attention.ops  # noqa: F401
import repro.kernels.frontier_select.ops  # noqa: F401


@pytest.fixture(scope="module")
def cfg():
    return get_reduced("webparf")


# ---------------------------------------------------------------------------
# registry resolution
# ---------------------------------------------------------------------------

def test_registry_lists_all_kernels():
    assert set(registry.kernels()) >= {"frontier_select", "bloom",
                                       "flash_attention"}
    for kern in ("frontier_select", "bloom"):
        assert set(registry.available(kern)) == {"ref", "pallas", "interpret"}
    assert "xla" in registry.available("flash_attention")


def test_registry_auto_resolves_per_backend():
    # the suite runs on CPU: auto must pick each kernel's CPU default
    assert jax.default_backend() != "tpu"
    assert registry.resolve_impl("frontier_select", "auto") == "ref"
    assert registry.resolve_impl("bloom", "auto") == "ref"
    assert registry.resolve_impl("flash_attention", "auto") == "xla"
    # explicit impls resolve to themselves
    assert registry.resolve_impl("bloom", "interpret") == "interpret"


@pytest.mark.parametrize("kern,want", [
    ("frontier_select", "pallas"), ("select_harvest", "pallas"),
    ("bloom", "ref"), ("dedup_deposit", "ref"), ("opic_update", "ref")])
def test_registry_tpu_defaults(kern, want, monkeypatch):
    """On a TPU, auto runs the kernels Mosaic compiles (the select family)
    and the ref path for the families it refuses (tests/
    test_chip_compile.py compiles the former for a described v5e)."""
    monkeypatch.setattr(registry.jax, "default_backend", lambda: "tpu")
    assert registry.resolve_impl(kern, "auto") == want


def test_registry_rejects_unknown():
    with pytest.raises(KeyError):
        registry.available("no_such_kernel")
    with pytest.raises(ValueError):
        registry.resolve_impl("bloom", "cuda")


def test_no_impl_chains_left_in_ops():
    """Acceptance guard: every ops.py dispatches via the registry, none
    carries its own `if impl ==` chain."""
    import pathlib

    import repro.kernels as K
    root = pathlib.Path(K.__file__).parent
    for ops in root.glob("*/ops.py"):
        text = ops.read_text()
        assert "if impl ==" not in text, f"{ops} still hand-dispatches"
        assert "registry.dispatch" in text, f"{ops} bypasses the registry"


# ---------------------------------------------------------------------------
# per-stage units (outside shard_map: axis_index needs a bound axis, so we
# drive stages through a 1-shard shard_map harness)
# ---------------------------------------------------------------------------

def run_stage_pipeline(cfg, state, stage_list, *, dispatch=False):
    mesh = make_host_mesh()
    _, step_f, step_d = CR.make_spmd_crawler(cfg, mesh, stages=stage_list)
    return (step_d if dispatch else step_f)(state)


def mk_state(cfg):
    mesh = make_host_mesh()
    init, _, _ = CR.make_spmd_crawler(cfg, mesh)
    return init()


def stats_of(state):
    s = np.asarray(state.stats).sum(0)
    return {n: int(v) for n, v in zip(ST.STATS, s)}


def test_allocate_respects_fetch_budget(cfg):
    state = mk_state(cfg)
    state, rep = run_stage_pipeline(cfg, state, [ST.allocate])
    assert int(np.asarray(rep.fetched_mask).sum()) <= cfg.fetch_batch
    assert stats_of(state)["fetched"] == 0      # fetch_analyze didn't run


def test_allocate_pops_are_removed_from_frontier(cfg):
    state = mk_state(cfg)
    occ0 = int(np.asarray(state.f_valid).sum())
    state, rep = run_stage_pipeline(cfg, state, [ST.allocate])
    n = int(np.asarray(rep.fetched_mask).sum())
    assert n > 0
    assert int(np.asarray(state.f_valid).sum()) == occ0 - n


def test_fetch_analyze_counts_fetches(cfg):
    state = mk_state(cfg)
    state, rep = run_stage_pipeline(cfg, state, [ST.allocate, ST.fetch_analyze])
    s = stats_of(state)
    n = int(np.asarray(rep.fetched_mask).sum())
    assert s["fetched"] == n
    assert s["fetch_own"] + s["fetch_foreign"] == n
    assert s["discovered"] == 0                 # extract_stage didn't run


def test_extract_stage_fills_staging(cfg):
    state = mk_state(cfg)
    state, _ = run_stage_pipeline(cfg, state, list(ST.DEFAULT_PIPELINE))
    s = stats_of(state)
    staged = int(np.asarray(state.staging_n).sum())
    assert s["discovered"] > 0
    assert staged > 0
    assert staged + s["dedup_exact"] + s["staging_drop"] == s["discovered"]


def test_dispatch_exchange_drains_staging(cfg):
    state = mk_state(cfg)
    state, _ = run_stage_pipeline(cfg, state, list(ST.DEFAULT_PIPELINE),
                                  dispatch=True)
    s = stats_of(state)
    assert s["dispatch_rounds"] >= 1
    assert s["dispatch_sent"] == s["dispatch_recv"] > 0
    assert int(np.asarray(state.staging_n).sum()) == 0


def test_politeness_stage_defers_overflow(cfg):
    # per-row budget of 0 defers EVERY pop; the frontier gets them all back
    pipeline = [ST.allocate, ST.make_politeness_stage(0),
                ST.fetch_analyze, ST.extract_stage]
    state = mk_state(cfg)
    occ0 = int(np.asarray(state.f_valid).sum())
    state, rep = run_stage_pipeline(cfg, state, pipeline)
    s = stats_of(state)
    assert s["politeness_deferred"] > 0
    assert s["fetched"] == 0
    assert int(np.asarray(rep.fetched_mask).sum()) == 0
    assert int(np.asarray(state.f_valid).sum()) == occ0


def test_revisit_stage_reenqueues_fetched(cfg):
    pipeline = [ST.allocate, ST.fetch_analyze, ST.make_revisit_stage(16),
                ST.extract_stage]
    state = mk_state(cfg)
    occ0 = int(np.asarray(state.f_valid).sum())
    state, rep = run_stage_pipeline(cfg, state, pipeline)
    s = stats_of(state)
    n = int(np.asarray(rep.fetched_mask).sum())
    assert s["revisit_enqueued"] == n > 0
    # every fetched URL went back into some queue (plus possible drops)
    assert int(np.asarray(state.f_valid).sum()) == occ0
    assert s["fetched"] == n


# ---------------------------------------------------------------------------
# ref <-> interpret equivalence through the real crawl step
# ---------------------------------------------------------------------------

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


@pytest.mark.parametrize("kernel", ["frontier_select", "bloom", "both"])
def test_ref_interpret_bit_identical_trajectories(cfg, kernel):
    """kernel_impl="interpret" must reproduce the "ref" CrawlState trajectory
    BIT-IDENTICALLY over 3 dispatch intervals of the reduced config.

    The single-kernel cases isolate each Pallas kernel by registering the ref
    implementation under a temporary name for the other one — both kernels
    share the `kernel_impl` knob, so mixing is done at the registry level."""
    steps = 3 * cfg.dispatch_interval
    ref = crawl_trajectory(scaled(cfg, kernel_impl="ref"), steps)

    if kernel == "both":
        got = crawl_trajectory(scaled(cfg, kernel_impl="interpret"), steps)
    else:
        # temporarily swap the OTHER kernel's interpret impl for ref
        other = {"frontier_select": "bloom", "bloom": "frontier_select"}[kernel]
        saved = registry._REGISTRY[other]["interpret"]
        registry._REGISTRY[other]["interpret"] = registry._REGISTRY[other]["ref"]
        try:
            got = crawl_trajectory(scaled(cfg, kernel_impl="interpret"), steps)
        finally:
            registry._REGISTRY[other]["interpret"] = saved

    for t, ((s_ref, r_ref), (s_got, r_got)) in enumerate(zip(ref, got)):
        for name, a, b in zip(ST.CrawlState._fields, s_ref, s_got):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"step {t}: CrawlState.{name} diverged")
        for name, a, b in zip(ST.FetchReport._fields, r_ref, r_got):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"step {t}: FetchReport.{name} diverged")


def test_kernel_impl_threads_from_config(cfg):
    """An invalid impl must surface at trace time — proof the knob reaches
    the registry from CrawlConfig."""
    bad = scaled(cfg, kernel_impl="cuda")
    mesh = make_host_mesh()
    with pytest.raises(ValueError, match="no impl"):
        init, step_f, _ = CR.make_spmd_crawler(bad, mesh)
        step_f(init())


# ---------------------------------------------------------------------------
# vectorized frontier insert (argsort-free free-slot search)
# ---------------------------------------------------------------------------

def test_insert_free_slot_targets_match_argsort():
    from repro.core import frontier as F
    rng = np.random.default_rng(3)
    R, C, M = 8, 32, 16
    f = F.init_frontier(R, C)
    # random pre-occupancy
    occ = jnp.asarray(rng.random((R, C)) < 0.4)
    f = f._replace(valid=occ,
                   priority=jnp.where(occ, 0.5, F.NEG),
                   url=jnp.asarray(rng.integers(1, 1 << 20, (R, C)),
                                   jnp.uint32))
    urls = jnp.asarray(rng.integers(1 << 20, 1 << 21, (R, M)), jnp.uint32)
    scores = jnp.asarray(rng.random((R, M)), jnp.float32)
    mask = jnp.asarray(rng.random((R, M)) < 0.8)
    f2 = F.insert(f, urls, scores, mask, n_buckets=8)

    # oracle: stable argsort free-slot assignment (the seed implementation)
    valid = np.asarray(occ)
    free_idx = np.argsort(valid, axis=1, kind="stable")
    url_np, pri_np = np.asarray(f.url).copy(), np.asarray(f.priority).copy()
    val_np = valid.copy()
    for r in range(R):
        o = 0
        n_free = int((~valid[r]).sum())
        arr0 = int(np.asarray(f.arrival)[r])
        for m in range(M):
            if not np.asarray(mask)[r, m]:
                continue
            if o < n_free:
                c = free_idx[r, o]
                url_np[r, c] = np.asarray(urls)[r, m]
                pri_np[r, c] = np.asarray(F.encode_priority(
                    scores[r, m], jnp.int32(arr0 + o), 8))
                val_np[r, c] = True
            o += 1
    np.testing.assert_array_equal(np.asarray(f2.valid), val_np)
    np.testing.assert_array_equal(np.asarray(f2.url), url_np)
    np.testing.assert_allclose(np.asarray(f2.priority), pri_np)
