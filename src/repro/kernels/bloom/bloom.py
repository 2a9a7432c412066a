"""Bloom-filter probe+insert Pallas TPU kernel — the dedup hot loop.

Every discovered URL probes k bit positions of its domain row's filter; the
whole batch then inserts its bits. On TPU the win is structural: the filter
row (2^b bytes, b<=20 -> <=1 MiB) streams HBM->VMEM ONCE per (row, url-tile)
grid step and all k probes + the scatter-update hit VMEM, where XLA's
gather/scatter lowering would issue per-element HBM transactions.

Layout: bits are byte-per-bit uint8 (matching core/dedup.py state). A packed
uint32 variant (8x VMEM density) is the §Perf follow-up noted in
EXPERIMENTS.md. Probe indices are mod-2^b so index arithmetic is shift/mask.

Validated with interpret=True only: Mosaic refuses both variants for a v5e
(the (1, N) blocks break its (8, 128) block rule, and the VMEM gather/
scatter has no TPU lowering), so ops.py registers ref as the TPU default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bit_indices(urls, k: int, bits_log2: int):
    # mirrors core.dedup._bit_indices (kept dependency-free for the kernel)
    def mix(x, salt):
        x = x.astype(jnp.uint32) ^ jnp.uint32((salt * 0x9E3779B9 + 0x85EBCA6B) & 0xFFFFFFFF)
        x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
        x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
        return x ^ (x >> 16)

    def h2(a, b, salt=0):
        return mix(a.astype(jnp.uint32) + mix(jnp.asarray(b, jnp.uint32), salt + 7), salt)

    h1 = h2(urls, 101)
    h2_ = h2(urls, 202) | jnp.uint32(1)
    i = jnp.arange(k, dtype=jnp.uint32)
    mask = jnp.uint32((1 << bits_log2) - 1)
    return ((h1[..., None] + i * h2_[..., None]) & mask).astype(jnp.int32)


def _kernel(urls_ref, mask_ref, bits_ref, seen_ref, bits_out_ref, *,
            k: int, bits_log2: int, n_url_tiles: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _copy():
        bits_out_ref[...] = bits_ref[...]

    urls = urls_ref[0]                                   # (tile,)
    mask = mask_ref[0]
    idx = _bit_indices(urls, k, bits_log2)               # (tile, k)
    row = bits_out_ref[0]                                # (2^b,) in VMEM
    got = row[idx]                                       # VMEM gather
    seen_ref[0] = (got == 1).all(axis=-1) & mask
    upd = jnp.broadcast_to(mask[:, None], idx.shape).astype(jnp.uint8)
    bits_out_ref[0] = row.at[idx].max(upd)               # VMEM scatter-OR


def bloom_probe_insert(bits: jax.Array, urls: jax.Array, mask: jax.Array, *,
                       k: int, url_tile: int = 256,
                       interpret: bool = False):
    """bits: (R, 2^b) uint8; urls/mask: (R, M). Returns (seen (R,M), bits')."""
    R, nbits = bits.shape
    bits_log2 = nbits.bit_length() - 1
    assert 1 << bits_log2 == nbits
    M = urls.shape[1]
    url_tile = min(url_tile, M)
    assert M % url_tile == 0
    nt = M // url_tile

    kernel = functools.partial(_kernel, k=k, bits_log2=bits_log2,
                               n_url_tiles=nt)
    seen, new_bits = pl.pallas_call(
        kernel,
        grid=(R, nt),
        in_specs=[
            pl.BlockSpec((1, url_tile), lambda r, t: (r, t)),
            pl.BlockSpec((1, url_tile), lambda r, t: (r, t)),
            pl.BlockSpec((1, nbits), lambda r, t: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, url_tile), lambda r, t: (r, t)),
            pl.BlockSpec((1, nbits), lambda r, t: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, M), jnp.bool_),
            jax.ShapeDtypeStruct((R, nbits), jnp.uint8),
        ],
        interpret=interpret,
    )(urls, mask, bits)
    return seen, new_bits


# ---------------------------------------------------------------------------
# Packed variant — uint32 words, 8x VMEM density (the §Perf follow-up):
# a 2^20-bit filter row is 128 KiB packed vs 1 MiB byte-per-bit, so rows 8x
# larger fit VMEM, or 8 rows stream per block. OR-insert is race-free here
# because the grid walks URL tiles sequentially per row.
# ---------------------------------------------------------------------------

def _packed_kernel(urls_ref, mask_ref, words_ref, seen_ref, words_out_ref, *,
                   k: int, bits_log2: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _copy():
        words_out_ref[...] = words_ref[...]

    urls = urls_ref[0]
    mask = mask_ref[0]
    idx = _bit_indices(urls, k, bits_log2)               # (tile, k) bit pos
    word_i = (idx >> 5).astype(jnp.int32)
    bitpos = (idx & 31).astype(jnp.uint32)
    bit = jnp.uint32(1) << bitpos
    row = words_out_ref[0]                               # (2^b / 32,) u32
    got = row[word_i]                                    # (tile, k)
    seen_ref[0] = (((got & bit) != 0).all(axis=-1)) & mask
    # scatter-OR, duplicate-safe: per bit plane, scatter a 0/1 hit mask
    # (idempotent under max even with colliding words), then fold the planes
    # back with shifts. A direct mixed-value scatter-max would drop bits.
    nwords = row.shape[0]
    flat_w = word_i.reshape(-1)
    flat_p = bitpos.reshape(-1)
    flat_m = jnp.broadcast_to(mask[:, None], word_i.shape).reshape(-1)
    acc = jnp.zeros((nwords,), jnp.uint32)
    for p in range(32):
        sel = flat_m & (flat_p == p)
        tgt = jnp.where(sel, flat_w, nwords)             # drop when unselected
        hit = jnp.zeros((nwords,), jnp.uint32).at[tgt].max(
            jnp.uint32(1), mode="drop")
        acc = acc | (hit << p)
    words_out_ref[0] = row | acc


def bloom_probe_insert_packed(words: jax.Array, urls: jax.Array,
                              mask: jax.Array, *, k: int, url_tile: int = 256,
                              interpret: bool = False):
    """words: (R, 2^b / 32) uint32 bit-packed filter rows."""
    R, nwords = words.shape
    bits_log2 = (nwords * 32).bit_length() - 1
    assert 1 << bits_log2 == nwords * 32
    M = urls.shape[1]
    url_tile = min(url_tile, M)
    assert M % url_tile == 0
    kernel = functools.partial(_packed_kernel, k=k, bits_log2=bits_log2)
    return pl.pallas_call(
        kernel,
        grid=(R, M // url_tile),
        in_specs=[
            pl.BlockSpec((1, url_tile), lambda r, t: (r, t)),
            pl.BlockSpec((1, url_tile), lambda r, t: (r, t)),
            pl.BlockSpec((1, nwords), lambda r, t: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, url_tile), lambda r, t: (r, t)),
            pl.BlockSpec((1, nwords), lambda r, t: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, M), jnp.bool_),
            jax.ShapeDtypeStruct((R, nwords), jnp.uint32),
        ],
        interpret=interpret,
    )(urls, mask, words)


def pack_bits(bits: jax.Array) -> jax.Array:
    """(R, 2^b) uint8 byte-per-bit -> (R, 2^b/32) uint32 packed."""
    R, n = bits.shape
    b = bits.reshape(R, n // 32, 32).astype(jnp.uint32)
    return (b << jnp.arange(32, dtype=jnp.uint32)).sum(axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array) -> jax.Array:
    R, w = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return ((words[..., None] >> shifts) & 1).astype(jnp.uint8).reshape(R, w * 32)
