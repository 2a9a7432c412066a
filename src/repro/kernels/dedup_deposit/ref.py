"""Oracle for the dedup_deposit kernel.

Contract (mirrors the Pallas grid): URLs are processed in TILES of
``url_tile`` along the item axis, in ascending order; a tile probes the
Bloom filter AFTER all previous tiles inserted (the streaming contract
shared with kernels/bloom), and each tile's twin deposits scatter-add into
the cash table in one ``.at[].add`` before the next tile runs. Within the
crawl's dispatch the exact-dedup upstream guarantees a URL arrives at most
once per round, so cells never collide — but the tile walk still fixes the
f32 accumulation order, which is what makes ref <-> interpret bit-identity
testable on adversarial inputs too.

The walk costs what arrives, not the padded (R, M) bucket grid: a crawl's
dispatch fills well under 1% of it. Tiles with no arrival are skipped, and a tile's
arrivals are compacted, in row-major order, into passes of K =
``min(R * url_tile, M)`` (row, col) pairs; each pass probes and inserts
only its K * k Bloom bits and matches each arrival against its own row's
queue, a (K, C) compare. A tile with more than K arrivals takes several
passes: all of them probe before any inserts, so the result is the dense
walk's, bit for bit.
"""
import jax.numpy as jnp
from jax import lax

from repro.kernels.bloom.bloom import _bit_indices


def _plan(mask, url_tile: int):
    """Arrivals per tile (nt,) over all rows, and the pass size K."""
    R, M = mask.shape
    url_tile = min(url_tile, M)
    mask = jnp.pad(mask, ((0, 0), (0, -M % url_tile)))
    counts = mask.reshape(R, -1, url_tile).sum(axis=(0, 2), dtype=jnp.int32)
    return counts, min(R * url_tile, mask.shape[1])


def tile_walk(mask, url_tile: int = 256):
    """(tiles walked, tiles that take more than one pass) when
    ``dedup_deposit_ref`` walks ``mask`` (R, M) — the same rule the walk
    applies, for the dispatch's counters."""
    counts, K = _plan(mask, url_tile)
    return (counts > 0).sum(), (counts > K).sum()


def dedup_deposit_ref(bits, urls, mask, val, f_url, f_valid, table, *,
                      k: int, url_tile: int = 256):
    """bits (R, 2^b) u8; urls/mask/val (R, M); f_url/f_valid/table (R, C).
    Returns (seen (R, M), bits', table', refund (R, 1))."""
    bits_log2 = bits.shape[1].bit_length() - 1
    R, M = urls.shape
    C = f_url.shape[1]
    url_tile = min(url_tile, M)
    assert M % url_tile == 0, (M, url_tile)
    counts, K = _plan(mask, url_tile)
    nt = counts.shape[0]
    tiles = jnp.nonzero(counts > 0, size=nt, fill_value=0)[0]   # ascending

    def walk(i, carry):
        seen, bits, table, refund = carry
        t = tiles[i]
        o = t * url_tile
        u, m, v = (lax.dynamic_slice_in_dim(a, o, url_tile, axis=1)
                   for a in (urls, mask, val))
        n = counts[t]
        rank = jnp.cumsum(m.reshape(-1), dtype=jnp.int32)   # row-major

        def arrivals(p):
            """The tile's p-th K arrivals: rows, cols, and which are real
            (a pass past the last arrival points nowhere)."""
            want = p * K + jnp.arange(1, K + 1, dtype=jnp.int32)
            ok = want <= n
            flat = jnp.where(ok, jnp.searchsorted(rank, want), 0)
            return flat // url_tile, flat % url_tile, ok

        def probe(p, seen):
            r, c, ok = arrivals(p)
            idx = _bit_indices(u[r, c], k, bits_log2)            # (K, k)
            s = (bits[r[:, None], idx] == 1).all(axis=-1) & ok
            return seen.at[jnp.where(ok, r, R), o + c].set(s, mode="drop")

        def insert(p, carry):
            bits, table, gone = carry
            r, c, ok = arrivals(p)
            rd = jnp.where(ok, r, R)            # fill entries drop out
            uk, vk = u[r, c], v[r, c]
            s = seen[r, o + c] & ok
            idx = _bit_indices(uk, k, bits_log2)
            bits = bits.at[rd[:, None], idx].max(jnp.uint8(1), mode="drop")
            twin = (uk[:, None] == f_url[r]) & f_valid[r] & s[:, None]
            hit = twin.any(-1)                                   # (K, C)
            cell = jnp.argmax(twin, axis=-1).astype(jnp.int32)
            table = table.at[r, jnp.where(hit, cell, C)].add(
                jnp.where(hit, vk, 0.0), mode="drop")
            gone = gone.at[rd, c].set(jnp.where(s & ~hit, vk, 0.0),
                                      mode="drop")
            return bits, table, gone

        # every pass probes before any inserts: the tile sees the filter
        # as the earlier tiles left it
        passes = (n + K - 1) // K
        seen = lax.fori_loop(0, passes, probe, seen)
        bits, table, gone = lax.fori_loop(
            0, passes, insert,
            (bits, table, jnp.zeros((R, url_tile), jnp.float32)))
        # the tile's no-twin refunds, summed as the dense (R, tile) walk sums
        return seen, bits, table, refund + gone.sum(axis=1)

    seen, bits, table, refund = lax.fori_loop(
        0, (counts > 0).sum(), walk,
        (jnp.zeros((R, M), jnp.bool_), bits, table,
         jnp.zeros((R,), jnp.float32)))
    return seen, bits, table, refund[:, None]
