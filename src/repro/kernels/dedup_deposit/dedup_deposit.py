"""Fused dedup+deposit Pallas TPU kernel — the dispatch hot path's sharp edge.

Per dispatch, every received URL (a) probes its domain row's Bloom filter
(k hashes) and inserts its bits, and (b) — when the probe says *seen* — is
matched against the URLs still QUEUED in its frontier row so its piggybacked
OPIC cash can accumulate into the queued twin's cell (classic OPIC: a page's
cash grows with its in-link rate). Unfused, (b) materializes a full
``(r_slots, M, C)`` boolean twin tensor in HBM before a separate cell
scatter (the pre-PR ``dispatch_exchange`` path, kept as the benchmark
baseline behind ``CrawlConfig.fused_dispatch=False``). Fused, the kernel
walks URL tiles per row with the Bloom row, the frontier row (urls+valid),
and the cash-table row ALL resident in VMEM: probe, twin match (a
``(tile, C)`` compare that never leaves VMEM), cell scatter-add, and the
no-twin refund accumulate in the same pass.

Grid is ``(R, M // tile)``; the grid walks URL tiles sequentially per row,
so a later tile probes the filter AFTER earlier tiles inserted (the same
streaming contract as kernels/bloom) and duplicate-cell accumulation order
is deterministic — ref.py replays the same tile walk, which is what the
bit-identity tests pin down.

Outputs per row: ``seen`` (R, M) — the Bloom verdict, already masked;
``bits'``; ``table'`` — the cash lane with twin deposits applied; and
``refund`` (R, 1) — the summed cash of *seen* arrivals with no queued twin
(already fetched, or a Bloom false positive), which the caller folds back
into the row's slot-cash pool (the value channel's deliver-or-refund rule).

The packed variant (``packed_kernel=True``) runs the same fusion over
bit-packed uint32 filter words (8x VMEM density — the bit-packed Bloom
variant absorbed into this family; cf. kernels/bloom's standalone packed
kernel): ops.py registers it as the ``pallas_packed`` / ``interpret_packed``
implementations, packing at the XLA boundary.

Validated with interpret=True on CPU only: Mosaic refuses it for a v5e (the
(1, N) blocks break its (8, 128) block rule, and scatter-add has no TPU
lowering), so ops.py registers ref as the TPU default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bloom.bloom import _bit_indices


def _kernel(urls_ref, mask_ref, val_ref, furl_ref, fvalid_ref, bits_ref,
            table_ref, seen_ref, bits_out_ref, table_out_ref, refund_ref, *,
            k: int, bits_log2: int, packed: bool):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        bits_out_ref[...] = bits_ref[...]
        table_out_ref[...] = table_ref[...]
        refund_ref[...] = jnp.zeros_like(refund_ref)

    urls = urls_ref[0]                                   # (tile,)
    mask = mask_ref[0]
    val = val_ref[0]
    idx = _bit_indices(urls, k, bits_log2)               # (tile, k) bit pos

    # --- Bloom probe + insert (VMEM-resident filter row) ---
    if packed:
        word_i = (idx >> 5).astype(jnp.int32)
        bit = jnp.uint32(1) << (idx & 31).astype(jnp.uint32)
        row = bits_out_ref[0]                            # (2^b / 32,) u32
        seen = (((row[word_i] & bit) != 0).all(axis=-1)) & mask
        # scatter-OR per bit plane (idempotent under colliding words; see
        # kernels/bloom._packed_kernel for the derivation)
        nwords = row.shape[0]
        flat_w = word_i.reshape(-1)
        flat_p = (idx & 31).reshape(-1)
        flat_m = jnp.broadcast_to(mask[:, None], word_i.shape).reshape(-1)
        acc = jnp.zeros((nwords,), jnp.uint32)
        for p in range(32):
            sel = flat_m & (flat_p == p)
            tgt = jnp.where(sel, flat_w, nwords)
            hitp = jnp.zeros((nwords,), jnp.uint32).at[tgt].max(
                jnp.uint32(1), mode="drop")
            acc = acc | (hitp << p)
        bits_out_ref[0] = row | acc
    else:
        row = bits_out_ref[0]                            # (2^b,) u8 in VMEM
        seen = (row[idx] == 1).all(axis=-1) & mask
        upd = jnp.broadcast_to(mask[:, None], idx.shape).astype(jnp.uint8)
        bits_out_ref[0] = row.at[idx].max(upd)
    seen_ref[0] = seen

    # --- queued-twin match + cash deposit ((tile, C), never leaves VMEM) ---
    furl = furl_ref[0]                                   # (C,)
    fvalid = fvalid_ref[0]
    C = furl.shape[0]
    twin = (urls[:, None] == furl[None, :]) & fvalid[None, :] & seen[:, None]
    hit = twin.any(axis=-1)
    cell = jnp.argmax(twin, axis=-1).astype(jnp.int32)
    tab = table_out_ref[0]                               # (C,) in VMEM
    table_out_ref[0] = tab.at[jnp.where(hit, cell, C)].add(
        jnp.where(hit, val, 0.0), mode="drop")
    refund_ref[0, 0] = refund_ref[0, 0] + jnp.where(seen & ~hit, val,
                                                    0.0).sum()


def dedup_deposit_kernel(bits, urls, mask, val, f_url, f_valid, table, *,
                         k: int, url_tile: int = 256, interpret: bool = False,
                         packed_kernel: bool = False):
    """bits (R, 2^b) u8 — or (R, 2^b/32) u32 when ``packed_kernel``;
    urls/mask/val (R, M); f_url/f_valid/table (R, C).
    Returns (seen (R, M), bits', table', refund (R, 1))."""
    R, nb = bits.shape
    bits_log2 = (nb * 32 if packed_kernel else nb).bit_length() - 1
    assert 1 << bits_log2 == (nb * 32 if packed_kernel else nb)
    M = urls.shape[1]
    C = f_url.shape[1]
    url_tile = min(url_tile, M)
    assert M % url_tile == 0
    nt = M // url_tile

    kernel = functools.partial(_kernel, k=k, bits_log2=bits_log2,
                               packed=packed_kernel)
    tile_spec = pl.BlockSpec((1, url_tile), lambda r, t: (r, t))
    row_c = pl.BlockSpec((1, C), lambda r, t: (r, 0))
    row_b = pl.BlockSpec((1, nb), lambda r, t: (r, 0))
    one = pl.BlockSpec((1, 1), lambda r, t: (r, 0))
    return pl.pallas_call(
        kernel,
        grid=(R, nt),
        in_specs=[tile_spec, tile_spec, tile_spec, row_c, row_c, row_b,
                  row_c],
        out_specs=[tile_spec, row_b, row_c, one],
        out_shape=[
            jax.ShapeDtypeStruct((R, M), jnp.bool_),
            jax.ShapeDtypeStruct((R, nb), bits.dtype),
            jax.ShapeDtypeStruct((R, C), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(urls, mask, val, f_url, f_valid, bits, table)
