"""Oracle for the bloom kernel.

Contract: URLs are processed in TILES of ``url_tile``; each tile probes the
filter state AFTER all previous tiles inserted (streaming dedup — a later
tile sees an earlier tile's URLs). core/dedup.probe_insert_arrays is the
whole-batch primitive; this tiles it to mirror the kernel's grid semantics.
"""
import jax.numpy as jnp
from jax import lax

from repro.core.dedup import probe_insert_arrays


def bloom_ref(bits, urls, mask, *, k, url_tile=256):
    bits_log2 = bits.shape[1].bit_length() - 1
    R, M = urls.shape
    url_tile = min(url_tile, M)
    assert M % url_tile == 0, (M, url_tile)

    def walk(t, carry):
        seen, bits = carry
        u, m = (lax.dynamic_slice_in_dim(a, t * url_tile, url_tile, axis=1)
                for a in (urls, mask))
        s, bits = probe_insert_arrays(bits, u, m, k=k, bits_log2=bits_log2)
        return (lax.dynamic_update_slice_in_dim(seen, s, t * url_tile,
                                                axis=1), bits)

    return lax.fori_loop(0, M // url_tile, walk,
                         (jnp.zeros((R, M), jnp.bool_), bits))
