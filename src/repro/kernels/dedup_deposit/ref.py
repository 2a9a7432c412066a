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
"""
import jax.numpy as jnp
from jax import lax

from repro.core.dedup import probe_insert_arrays


def dedup_deposit_ref(bits, urls, mask, val, f_url, f_valid, table, *,
                      k: int, url_tile: int = 256):
    """bits (R, 2^b) u8; urls/mask/val (R, M); f_url/f_valid/table (R, C).
    Returns (seen (R, M), bits', table', refund (R, 1))."""
    bits_log2 = bits.shape[1].bit_length() - 1
    R, M = urls.shape
    C = f_url.shape[1]
    url_tile = min(url_tile, M)
    assert M % url_tile == 0, (M, url_tile)
    rows = jnp.arange(R)[:, None]

    def walk(t, carry):
        seen, bits, table, refund = carry
        u, m, v = (lax.dynamic_slice_in_dim(a, t * url_tile, url_tile, axis=1)
                   for a in (urls, mask, val))
        s, bits = probe_insert_arrays(bits, u, m, k=k, bits_log2=bits_log2)
        twin = (u[:, :, None] == f_url[:, None, :]) \
            & f_valid[:, None, :] & s[:, :, None]        # (R, tile, C)
        hit = twin.any(-1)
        cell = jnp.argmax(twin, axis=-1).astype(jnp.int32)
        table = table.at[rows, jnp.where(hit, cell, C)].add(
            jnp.where(hit, v, 0.0), mode="drop")
        refund = refund + jnp.where(s & ~hit, v, 0.0).sum(axis=1)
        seen = lax.dynamic_update_slice_in_dim(seen, s, t * url_tile, axis=1)
        return seen, bits, table, refund

    seen, bits, table, refund = lax.fori_loop(
        0, M // url_tile, walk,
        (jnp.zeros((R, M), jnp.bool_), bits, table,
         jnp.zeros((R,), jnp.float32)))
    return seen, bits, table, refund[:, None]
