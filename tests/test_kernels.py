"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bloom.ops import probe_insert
from repro.kernels.flash_attention.ops import attention
from repro.kernels.frontier_select.ops import select

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,S,hd", [
    (1, 2, 2, 128, 32),
    (2, 4, 2, 128, 64),
    (1, 8, 1, 256, 64),     # MQA
    (2, 6, 2, 192, 32),     # group=3, non-pow2 S
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Hq, Hkv, S, hd, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * S + hd), 3)
    q = jax.random.normal(ks[0], (B, Hq, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    ref = attention(q, k, v, causal=causal, impl="ref")
    out = attention(q, k, v, causal=causal, impl="interpret",
                    block_q=64, block_k=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_block_size_invariance():
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 32))
    k = jax.random.normal(ks[1], (1, 2, 256, 32))
    v = jax.random.normal(ks[2], (1, 2, 256, 32))
    a = attention(q, k, v, causal=True, impl="interpret", block_q=64, block_k=64)
    b = attention(q, k, v, causal=True, impl="interpret", block_q=128, block_k=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# bloom
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,M,b,k", [
    (1, 256, 10, 2), (4, 256, 12, 4), (2, 512, 14, 3), (8, 512, 11, 5),
])
def test_bloom_sweep(R, M, b, k):
    bits = jnp.zeros((R, 1 << b), jnp.uint8)
    urls = jnp.asarray(RNG.integers(0, 1 << 24, (R, M)), jnp.uint32)
    mask = jnp.asarray(RNG.random((R, M)) < 0.7)
    s_ref, b_ref = probe_insert(bits, urls, mask, k=k, impl="ref")
    s_pal, b_pal = probe_insert(bits, urls, mask, k=k, impl="interpret")
    assert (np.asarray(s_ref) == np.asarray(s_pal)).all()
    assert (np.asarray(b_ref) == np.asarray(b_pal)).all()


def test_bloom_incremental_matches_batch():
    """Inserting in two batches == inserting once (state composition)."""
    bits = jnp.zeros((1, 1 << 12), jnp.uint8)
    u = jnp.asarray(RNG.integers(0, 1 << 20, (1, 128)), jnp.uint32)
    m = jnp.ones((1, 128), bool)
    _, b_once = probe_insert(bits, u, m, k=3, impl="interpret")
    _, b1 = probe_insert(bits, u[:, :64], m[:, :64], k=3, impl="interpret")
    _, b2 = probe_insert(b1, u[:, 64:], m[:, 64:], k=3, impl="interpret")
    assert (np.asarray(b_once) == np.asarray(b2)).all()


# ---------------------------------------------------------------------------
# frontier_select
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,C,k", [(1, 32, 1), (4, 64, 4), (2, 128, 8),
                                   (8, 256, 16)])
def test_frontier_select_sweep(R, C, k):
    url = jnp.asarray(RNG.integers(0, 1 << 24, (R, C)), jnp.uint32)
    pri = jnp.asarray(RNG.normal(size=(R, C)) * 50, jnp.float32)
    valid = jnp.asarray(RNG.random((R, C)) < 0.5)
    ref = select(url, pri, valid, k=k, impl="ref")
    pal = select(url, pri, valid, k=k, impl="interpret")
    # priorities, masks, and post-state valid/priority must agree exactly
    # (ties may select different equal-priority URLs)
    np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(pal[1]))
    np.testing.assert_array_equal(np.asarray(ref[2]), np.asarray(pal[2]))
    assert int(ref[4].sum()) == int(pal[4].sum())
    # selected priorities are the true top-k of valid entries, descending
    masked = np.where(np.asarray(valid), np.asarray(pri), -np.inf)
    want = -np.sort(-masked, axis=1)[:, :k]
    got = np.where(np.asarray(pal[2]), np.asarray(pal[1]), -np.inf)
    np.testing.assert_allclose(np.where(np.isfinite(want), want, -3e38), got,
                               rtol=1e-6)


def test_frontier_select_pop_semantics():
    url = jnp.asarray([[1, 2, 3, 4]], jnp.uint32)
    pri = jnp.asarray([[4.0, 3.0, 2.0, 1.0]])
    valid = jnp.ones((1, 4), bool)
    _, p1, m1, pri2, valid2 = select(url, pri, valid, k=2, impl="interpret")
    _, p2, m2, _, _ = select(url, pri2, valid2, k=2, impl="interpret")
    assert list(np.asarray(p1)[0]) == [4.0, 3.0]
    assert list(np.asarray(p2)[0]) == [2.0, 1.0]


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("R,C,k", [(4, 64, 4), (2, 128, 8)])
def test_frontier_select_return_idx(R, C, k, impl):
    """Extended contract: the popped cell indices name exactly the cells the
    pop invalidated, in selection order (unique priorities make the popped
    set deterministic across implementations)."""
    url = jnp.asarray(RNG.integers(0, 1 << 24, (R, C)), jnp.uint32)
    pri = jnp.asarray(RNG.permutation(R * C).reshape(R, C), jnp.float32)
    valid = jnp.asarray(RNG.random((R, C)) < 0.5)
    base = select(url, pri, valid, k=k, impl=impl)
    got, p, mask, pri2, valid2, idx = select(url, pri, valid, k=k, impl=impl,
                                             return_idx=True)
    # the 5-output prefix is unchanged by asking for indices
    for a, b in zip(base, (got, p, mask, pri2, valid2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    idx, mask = np.asarray(idx), np.asarray(mask)
    rows = np.arange(R)[:, None]
    # each masked lane's index points at the cell that was invalidated and
    # whose url/priority the pop returned
    assert ((idx >= 0) & (idx < C)).all()
    np.testing.assert_array_equal(
        np.asarray(valid)[rows, idx] & mask, mask)
    assert not (np.asarray(valid2)[rows, idx] & mask).any()
    np.testing.assert_array_equal(
        np.where(mask, np.asarray(url)[rows, idx], 0),
        np.where(mask, np.asarray(got), 0))
    # ref and interpret agree on the popped cells (unique priorities)
    other = select(url, pri, valid, k=k,
                   impl="interpret" if impl == "ref" else "ref",
                   return_idx=True)[5]
    np.testing.assert_array_equal(np.where(mask, idx, -1),
                                  np.where(mask, np.asarray(other), -1))


# ---------------------------------------------------------------------------
# packed bloom variant (8x VMEM density)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,M,b,k", [(2, 256, 12, 4), (4, 512, 11, 3)])
def test_bloom_packed_matches_bytewise(R, M, b, k):
    from repro.kernels.bloom.bloom import (bloom_probe_insert,
                                           bloom_probe_insert_packed,
                                           pack_bits, unpack_bits)
    bits = jnp.zeros((R, 1 << b), jnp.uint8)
    urls = jnp.asarray(RNG.integers(0, 1 << 24, (R, M)), jnp.uint32)
    mask = jnp.asarray(RNG.random((R, M)) < 0.7)
    s1, b1 = bloom_probe_insert(bits, urls, mask, k=k, interpret=True)
    s2, w2 = bloom_probe_insert_packed(pack_bits(bits), urls, mask, k=k,
                                       interpret=True)
    assert (np.asarray(s1) == np.asarray(s2)).all()
    assert (np.asarray(unpack_bits(w2)) == np.asarray(b1)).all()


def test_pack_unpack_roundtrip():
    from repro.kernels.bloom.bloom import pack_bits, unpack_bits
    bits = jnp.asarray(RNG.integers(0, 2, (3, 1 << 10)), jnp.uint8)
    assert (np.asarray(unpack_bits(pack_bits(bits))) == np.asarray(bits)).all()


# ---------------------------------------------------------------------------
# fused dedup+deposit (Bloom probe + queued-twin match + cash deposit)
# ---------------------------------------------------------------------------

def _dedup_inputs(R, M, C, b, *, queue_fill=0.7, dup_frac=0.5, seed=0):
    """Adversarial fixture: ~dup_frac of the arrivals are URLs already in
    the Bloom filter — half of those still queued (twin deposits), half
    fetched-and-gone (refunds) — the rest fresh; plus whatever false
    positives the filter produces on its own."""
    rng = np.random.default_rng(seed)
    f_url = jnp.asarray(rng.integers(1, 1 << 20, (R, C)), jnp.uint32)
    f_valid = jnp.asarray(rng.random((R, C)) < queue_fill)
    table = jnp.asarray(rng.random((R, C)), jnp.float32) * f_valid
    gone = jnp.asarray(rng.integers(1 << 20, 1 << 21, (R, M)), jnp.uint32)
    fresh = jnp.asarray(rng.integers(1 << 21, 1 << 22, (R, M)), jnp.uint32)
    pick = rng.random((R, M))
    urls = jnp.where(pick < dup_frac / 2, f_url[:, :M] if C >= M else
                     jnp.tile(f_url, (1, -(-M // C)))[:, :M],
                     jnp.where(pick < dup_frac, gone, fresh))
    mask = jnp.asarray(rng.random((R, M)) < 0.8)
    val = jnp.asarray(rng.random((R, M)), jnp.float32)
    # filter state: queued + gone URLs inserted up front
    bits = jnp.zeros((R, 1 << b), jnp.uint8)
    from repro.kernels.bloom.ops import probe_insert
    _, bits = probe_insert(bits, f_url, f_valid, k=3, impl="ref")
    _, bits = probe_insert(bits, gone, jnp.ones_like(mask), k=3, impl="ref")
    return bits, urls, mask, val, f_url, f_valid, table


@pytest.mark.parametrize("impl", ["interpret", "interpret_packed"])
@pytest.mark.parametrize("R,M,C,b", [(1, 64, 32, 10), (4, 96, 64, 12),
                                     (2, 256, 128, 11)])
def test_dedup_deposit_bit_identical(R, M, C, b, impl):
    from repro.kernels.dedup_deposit.ops import dedup_deposit
    args = _dedup_inputs(R, M, C, b, seed=R * M + C)
    ref = dedup_deposit(*args, k=3, impl="ref", url_tile=32)
    got = dedup_deposit(*args, k=3, impl=impl, url_tile=32)
    for name, a, g in zip(("seen", "bits", "table", "refund"), ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(g),
                                      err_msg=f"{impl}: {name} diverged")


@pytest.mark.parametrize("queue_fill", [0.0, 1.0])
def test_dedup_deposit_queue_edges(queue_fill):
    """Empty queues: every dup refunds (no twins). Full queues: every
    queued dup deposits."""
    from repro.kernels.dedup_deposit.ops import dedup_deposit
    args = _dedup_inputs(2, 64, 32, 10, queue_fill=queue_fill, seed=5)
    ref = dedup_deposit(*args, k=3, impl="ref", url_tile=32)
    got = dedup_deposit(*args, k=3, impl="interpret", url_tile=32)
    for a, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(g))
    seen, _, table2, refund = ref
    bits, urls, mask, val, f_url, f_valid, table = args
    if queue_fill == 0.0:
        # no queued twins: the table is untouched, all seen value refunds
        np.testing.assert_array_equal(np.asarray(table2), np.asarray(table))
        np.testing.assert_allclose(
            np.asarray(refund),
            np.where(np.asarray(seen), np.asarray(val), 0.0).sum(1),
            rtol=1e-6)
    else:
        assert float(np.asarray(seen).sum()) > 0
        # conservation: deposited + refunded == total seen value
        dep = (np.asarray(table2) - np.asarray(table)).sum(1)
        np.testing.assert_allclose(
            dep + np.asarray(refund),
            np.where(np.asarray(seen), np.asarray(val), 0.0).sum(1),
            rtol=1e-5)


def test_dedup_deposit_matches_unfused_composition():
    """The fused kernel must reproduce the unfused dispatch composition
    (probe_insert -> (R, M, C) twin match -> cell scatter) bit-for-bit on
    distinct arrivals — the exact-dedup upstream contract."""
    from repro.kernels.bloom.ops import probe_insert
    from repro.kernels.dedup_deposit.ops import dedup_deposit
    args = _dedup_inputs(4, 128, 64, 12, seed=9)
    bits, urls, mask, val, f_url, f_valid, table = args
    # make arrivals distinct per row (exact_dedup upstream guarantee)
    u = np.asarray(urls).copy()
    m = np.asarray(mask).copy()
    for r in range(u.shape[0]):
        _, first = np.unique(u[r], return_index=True)
        keep = np.zeros(u.shape[1], bool)
        keep[first] = True
        m[r] &= keep
    urls, mask = jnp.asarray(u), jnp.asarray(m)
    got = dedup_deposit(bits, urls, mask, val, f_url, f_valid, table, k=3,
                        impl="ref")
    _assert_matches_unfused(got, bits, urls, mask, val, f_url, f_valid,
                            table)


def _assert_matches_unfused(got, bits, urls, mask, val, f_url, f_valid,
                            table, url_tile=256):
    """``got`` (seen, bits', table', refund) against the unfused dispatch
    composition: probe_insert -> (R, M, C) twin match -> cell scatter."""
    u = np.asarray(urls)
    seen_u, bits_u = probe_insert(bits, urls, mask, k=3, impl="ref",
                                  url_tile=url_tile)
    seen_u = np.asarray(seen_u) & np.asarray(mask)
    twin = (u[:, :, None] == np.asarray(f_url)[:, None, :]) \
        & np.asarray(f_valid)[:, None, :] & seen_u[:, :, None]
    hit = twin.any(-1)
    cell = twin.argmax(-1)
    tab = np.asarray(table).copy()
    rows, cols = np.nonzero(hit)
    tab[rows, cell[rows, cols]] += np.asarray(val)[rows, cols]
    refund_u = np.where(seen_u & ~hit, np.asarray(val), 0.0).sum(1)
    seen, bits2, table2, refund = got
    np.testing.assert_array_equal(np.asarray(seen), seen_u)
    np.testing.assert_array_equal(np.asarray(bits2), np.asarray(bits_u))
    np.testing.assert_array_equal(np.asarray(table2), tab)
    np.testing.assert_allclose(np.asarray(refund), refund_u, rtol=1e-6)


def _crawl_dedup_inputs(R, M, C, b, *, per_row, long_row, seed):
    """Crawl-shaped arrivals: each row's bucket filled as a prefix (as the
    dispatch's per-row bucketing fills it) at about ``per_row`` of M, one
    row holding ``long_row``; URLs distinct within a row (the exact-dedup
    upstream), a third of them queued twins, a third fetched-and-gone,
    a third fresh."""
    rng = np.random.default_rng(seed)
    f_url = np.stack([rng.choice(np.arange(1, 1 << 20), C, replace=False)
                      for _ in range(R)]).astype(np.uint32)
    f_valid = rng.random((R, C)) < 0.7
    table = rng.random((R, C)).astype(np.float32) * f_valid
    n = rng.binomial(2 * per_row, 0.5, R)
    n[R // 2] = long_row
    mask = np.arange(M)[None, :] < n[:, None]
    gone = np.stack([rng.choice(np.arange(1 << 20, 1 << 21), M,
                                replace=False) for _ in range(R)])
    fresh = np.stack([rng.choice(np.arange(1 << 21, 1 << 22), M,
                                 replace=False) for _ in range(R)])
    queued = f_url[:, rng.permutation(np.arange(M) % C)]
    pick = rng.integers(0, 3, (R, M))
    urls = np.where(pick == 0, queued, np.where(pick == 1, gone, fresh))
    for r in range(R):          # queued twins drawn twice: keep the first
        _, first = np.unique(urls[r], return_index=True)
        keep = np.zeros(M, bool)
        keep[first] = True
        urls[r] = np.where(keep, urls[r], fresh[r])
    val = rng.random((R, M)).astype(np.float32)
    bits = jnp.zeros((R, 1 << b), jnp.uint8)
    _, bits = probe_insert(bits, jnp.asarray(f_url), jnp.asarray(f_valid),
                           k=3, impl="ref")
    _, bits = probe_insert(bits, jnp.asarray(gone, jnp.uint32),
                           jnp.ones((R, M), bool), k=3, impl="ref")
    return (bits, jnp.asarray(urls, jnp.uint32), jnp.asarray(mask),
            jnp.asarray(val), jnp.asarray(f_url), jnp.asarray(f_valid),
            jnp.asarray(table))


@pytest.mark.parametrize("impl", ["interpret", "unfused"])
def test_dedup_deposit_crawl_shaped_sparse(impl):
    """About 1% of the padded (R, M) grid holds an arrival, each row a
    prefix, one row past ``url_tile``: the ref walk visits two of four
    tiles in one compacted pass each and matches the dense walk (the
    interpret kernel) and the unfused composition bit for bit."""
    from repro.kernels.dedup_deposit.ops import dedup_deposit
    from repro.kernels.dedup_deposit.ref import tile_walk
    args = _crawl_dedup_inputs(32, 1024, 128, 12, per_row=8, long_row=300,
                               seed=14)
    mask = args[2]
    assert 0.005 < float(mask.mean()) < 0.02
    assert tuple(int(x) for x in tile_walk(mask)) == (2, 0)
    ref = dedup_deposit(*args, k=3, impl="ref")
    assert int(np.asarray(ref[0]).sum()) > 0
    if impl == "unfused":
        _assert_matches_unfused(ref, *args)
        return
    got = dedup_deposit(*args, k=3, impl=impl)
    for name, a, g in zip(("seen", "bits", "table", "refund"), ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(g),
                                      err_msg=f"{impl}: {name} diverged")


def test_dedup_deposit_tile_over_k_matches_one_pass():
    """K = min(R * url_tile, M). The same arrivals in an M = 64 grid (K =
    64: the first tile's ~200 arrivals take four passes) and in the grid
    widened to M = 256 by empty tiles (K = 256: one pass) give identical
    outputs, which are the dense walk's (interpret) and the unfused
    composition's."""
    from repro.kernels.dedup_deposit.ops import dedup_deposit
    from repro.kernels.dedup_deposit.ref import tile_walk
    R, M, tile = 8, 64, 32
    bits, urls, mask, val, f_url, f_valid, table = _crawl_dedup_inputs(
        R, M, 96, 11, per_row=M // 2, long_row=M, seed=3)
    over = np.asarray(mask).copy()
    over[:, :tile] = RNG.random((R, tile)) < 0.8       # tile 0: ~205 > 64
    mask = jnp.asarray(over)
    wide = [jnp.pad(a, ((0, 0), (0, 256 - M))) for a in (urls, mask, val)]
    assert tuple(int(x) for x in tile_walk(mask, tile)) == (2, 1)
    assert tuple(int(x) for x in tile_walk(wide[1], tile)) == (2, 0)
    narrow = dedup_deposit(bits, urls, mask, val, f_url, f_valid, table, k=3,
                           impl="ref", url_tile=tile)
    one = dedup_deposit(bits, *wide, f_url, f_valid, table, k=3, impl="ref",
                        url_tile=tile)
    dense = dedup_deposit(bits, urls, mask, val, f_url, f_valid, table, k=3,
                          impl="interpret", url_tile=tile)
    assert not np.asarray(one[0])[:, M:].any()
    for name, a, b, d in zip(("seen", "bits", "table", "refund"), narrow,
                             (one[0][:, :M],) + tuple(one[1:]), dense):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"one pass: {name} diverged")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(d),
                                      err_msg=f"dense: {name} diverged")
    _assert_matches_unfused(narrow, bits, urls, mask, val, f_url, f_valid,
                            table, url_tile=tile)


# ---------------------------------------------------------------------------
# fused select+harvest (pop + url-lane cash gather + cell zeroing)
# ---------------------------------------------------------------------------

def _harvest_inputs(R, C, *, fill=0.6, seed=0):
    """Crawl-realistic rows: invalid cells hold NEG priority and exactly
    0.0 cash (the lane invariant select_harvest's targeted zeroing relies
    on), priorities unique per row (the FIFO tie-break)."""
    from repro.core.frontier import NEG
    rng = np.random.default_rng(seed)
    url = jnp.asarray(rng.integers(1, 1 << 24, (R, C)), jnp.uint32)
    valid = jnp.asarray(rng.random((R, C)) < fill)
    pri = jnp.where(valid,
                    jnp.asarray(rng.permutation(R * C).reshape(R, C),
                                jnp.float32), NEG)
    table = jnp.asarray(rng.random((R, C)), jnp.float32) * valid
    return url, pri, valid, table


@pytest.mark.parametrize("fill", [0.0, 0.6, 1.0])
@pytest.mark.parametrize("R,C,k", [(4, 64, 4), (2, 128, 8)])
def test_select_harvest_bit_identical(R, C, k, fill):
    from repro.kernels.frontier_select.ops import select_harvest
    args = _harvest_inputs(R, C, fill=fill, seed=R * C + k)
    ref = select_harvest(*args, k=k, impl="ref")
    got = select_harvest(*args, k=k, impl="interpret")
    names = ("sel_url", "sel_pri", "sel_mask", "pri2", "valid2", "idx",
             "cash", "table2")
    # masked selection lanes are unspecified by the family contract (same
    # as plain frontier_select) — canonicalize them before comparing; the
    # post-state planes and the harvested cash must agree everywhere
    sm = np.asarray(ref[2])
    lane = {"sel_url", "sel_pri", "idx"}
    for name, a, g in zip(names, ref, got):
        a, g = np.asarray(a), np.asarray(g)
        if name in lane:
            a, g = np.where(sm, a, 0), np.where(sm, g, 0)
        np.testing.assert_array_equal(a, g, err_msg=f"{name} diverged")


def test_select_harvest_matches_unfused_composition():
    """select(return_idx) + gather + invalid-cell mask == select_harvest."""
    from repro.kernels.frontier_select.ops import select, select_harvest
    url, pri, valid, table = _harvest_inputs(4, 64, seed=3)
    k = 6
    su, sp, sm, pri2, valid2, idx = select(url, pri, valid, k=k, impl="ref",
                                           return_idx=True)
    cash_u = np.where(np.asarray(sm),
                      np.take_along_axis(np.asarray(table), np.asarray(idx),
                                         axis=1), 0.0)
    table_u = np.where(np.asarray(valid2), np.asarray(table), 0.0)
    out = select_harvest(url, pri, valid, table, k=k, impl="ref")
    np.testing.assert_array_equal(np.asarray(out[6]), cash_u)
    np.testing.assert_array_equal(np.asarray(out[7]), table_u)
    for a, b in zip((su, sp, sm, pri2, valid2), out[:5]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_select_pallas_return_idx_native():
    """The compiled-pallas select surfaces popped indices natively now
    (the ROADMAP sharp edge) — the registry must not fall back to the
    top_k recompute for any registered impl."""
    from repro.kernels.frontier_select.ops import _IDX_NATIVE
    from repro.kernels import registry
    assert set(registry.available("frontier_select")) <= set(_IDX_NATIVE)
    assert set(registry.available("select_harvest")) == \
        {"ref", "pallas", "interpret"}
