"""Frontier top-k select Pallas TPU kernel — the URL allocator's hot loop.

Per domain row: find the k highest-priority valid URLs and invalidate their
slots (pop semantics). A block of rows' priority lanes (capacity x f32,
<=16 KiB per row) lives in VMEM; selection is k rounds of masked
max+argmax — for the small k of a fetch batch this beats a full sort (XLA's
top_k lowers to sort) and fuses the invalidation writeback into the same
VMEM residency.

The widened SELECT+HARVEST entry point (``select_harvest_kernel``,
DESIGN.md §15) additionally carries the url-lane cash table through the
same launch: each popped cell's cash is read into a (R, k) harvest and the
cell zeroed while the row is still VMEM-resident, so url-lane orderings
(opic_url) pop URLs AND collect their per-URL value in one kernel instead
of a pop followed by a full-table gather+rewrite.

Mosaic layout rules shape the body:
  * grid is (R // TR,) with TR = 8 rows per block (one sublane tile), or
    the whole row axis when R is not a multiple of 8;
  * each round reads its pick out of the block with a masked lane
    reduction and records it into (TR, k) accumulators, and every output
    is written as one whole block at the end (no per-element stores);
  * URLs travel as int32 (a bitcast at the wrapper): Mosaic has no
    unsigned reductions;
  * the k rounds run in a ``lax.fori_loop``, so large k does not unroll.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG = -3e38
_ROW_TILE = 8                      # rows per block: one f32 sublane tile
_I32_MIN = jnp.iinfo(jnp.int32).min


def _pop_block(pri, urls, tab, k: int):
    """k masked-argmax rounds over a (TR, C) block. Returns (pri', sel_url,
    sel_pri, idx, cash) — the last four (TR, k); ``cash`` is None without a
    table. A popped cell's priority becomes NEG; masked lanes (no valid
    cell left) carry url 0, cash 0 and priority NEG."""
    TR, C = pri.shape
    iota = lax.broadcasted_iota(jnp.int32, (TR, C), 1)
    col = lax.broadcasted_iota(jnp.int32, (TR, k), 1)

    def round_(j, carry):
        pri, s_url, s_pri, s_idx, s_cash = carry
        m = pri.max(axis=1, keepdims=True)                  # (TR, 1)
        # first index achieving the max
        idx = jnp.min(jnp.where(pri == m, iota, C), axis=1, keepdims=True)
        idx = jnp.minimum(idx, C - 1)
        ok = m > NEG * 0.5
        hit = (iota == idx) & ok
        url = jnp.max(jnp.where(hit, urls, _I32_MIN), axis=1, keepdims=True)
        at = col == j
        s_url = jnp.where(at, jnp.where(ok, url, 0), s_url)
        s_pri = jnp.where(at, m, s_pri)
        s_idx = jnp.where(at, idx, s_idx)
        if tab is not None:
            # harvest the popped cell's cash (max over one real value and
            # -inf fill is that value, bit for bit)
            cash = jnp.max(jnp.where(hit, tab, -jnp.inf), axis=1,
                           keepdims=True)
            s_cash = jnp.where(at, jnp.where(ok, cash, 0.0), s_cash)
        return jnp.where(hit, NEG, pri), s_url, s_pri, s_idx, s_cash

    init = (pri, jnp.zeros((TR, k), jnp.int32),
            jnp.zeros((TR, k), jnp.float32), jnp.zeros((TR, k), jnp.int32),
            None if tab is None else jnp.zeros((TR, k), jnp.float32))
    return lax.fori_loop(0, k, round_, init)


def _kernel(*refs, k: int, harvest: bool):
    n_in = 4 if harvest else 3
    url_ref, pri_ref, valid_ref = refs[:3]
    (sel_url_ref, sel_pri_ref, sel_mask_ref, pri_out_ref, valid_out_ref,
     *extra) = refs[n_in:]
    valid = valid_ref[...]
    pri0 = jnp.where(valid, pri_ref[...], NEG)              # (TR, C) f32
    tab = refs[3][...] if harvest else None
    pri, s_url, s_pri, s_idx, s_cash = _pop_block(pri0, url_ref[...], tab, k)
    # a round only ever overwrites a cell with priority > NEG/2 by NEG, so
    # the popped cells are exactly those whose priority changed
    popped = pri != pri0
    sel_url_ref[...] = s_url
    sel_pri_ref[...] = s_pri
    sel_mask_ref[...] = s_pri > NEG * 0.5
    pri_out_ref[...] = pri
    valid_out_ref[...] = valid & ~popped
    if extra:
        # popped cell index (extended contract; masked lanes are
        # unspecified by contract — the clamp keeps them gatherable)
        extra[0][...] = s_idx
    if harvest:
        _, cash_ref, table_out_ref = extra
        cash_ref[...] = s_cash
        table_out_ref[...] = jnp.where(popped, 0.0, tab)


def _row_tile(R: int) -> int:
    return _ROW_TILE if R % _ROW_TILE == 0 else R


def _call(arrays, *, k: int, harvest: bool, return_idx: bool,
          interpret: bool):
    url = arrays[0]
    R, C = url.shape
    tr = _row_tile(R)
    # sel_url, sel_pri, sel_mask, pri', valid' [, idx] [, cash, table']
    outs = [(k, jnp.int32), (k, jnp.float32), (k, jnp.bool_),
            (C, jnp.float32), (C, jnp.bool_)]
    if return_idx:
        outs.append((k, jnp.int32))
    if harvest:
        outs += [(k, jnp.float32), (C, jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_kernel, k=k, harvest=harvest),
        grid=(R // tr,),
        in_specs=[pl.BlockSpec((tr, C), lambda r: (r, 0))] * len(arrays),
        out_specs=[pl.BlockSpec((tr, w), lambda r: (r, 0)) for w, _ in outs],
        out_shape=[jax.ShapeDtypeStruct((R, w), dt) for w, dt in outs],
        interpret=interpret,
    )(lax.bitcast_convert_type(url, jnp.int32), *arrays[1:])
    return (lax.bitcast_convert_type(out[0], url.dtype), *out[1:])


def frontier_select(url, pri, valid, *, k: int, interpret: bool = False,
                    return_idx: bool = False):
    """url/pri/valid: (R, C). Returns (sel_url, sel_pri, sel_mask (R,k),
    pri', valid') — plus the popped cell indices (R, k) int32 when
    ``return_idx`` (the extended contract, compiled AND interpreted — the
    extra output block is part of the production pallas path now)."""
    return _call((url, pri, valid), k=k, harvest=False,
                 return_idx=return_idx, interpret=interpret)


def select_harvest_kernel(url, pri, valid, table, *, k: int,
                          interpret: bool = False):
    """url/pri/valid/table: (R, C). Returns (sel_url, sel_pri, sel_mask,
    pri', valid', idx, cash (R, k), table') — top-k pop fused with the
    url-lane cash harvest: each popped cell's cash lands in ``cash`` and
    the cell is zeroed in ``table'`` within the same VMEM residency."""
    return _call((url, pri, valid, table), k=k, harvest=True,
                 return_idx=True, interpret=interpret)
