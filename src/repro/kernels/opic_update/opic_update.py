"""OPIC cash scatter-add Pallas TPU kernel — the ordering subsystem's hot
loop (repro/ordering/opic.py).

Every fetched page distributes its cash share along its O extracted
outlinks; per step that is r_local * k * O contributions targeting the
shard's (r_slots,) cash vector. On TPU the win mirrors kernels/bloom: the
cash row (a few KiB) lives in VMEM for the whole grid walk and every
scatter-add hits VMEM, where XLA's scatter lowering would round-trip HBM
per element. The grid walks contribution tiles sequentially per batch row,
so duplicate-row accumulation order is deterministic — ref.py replays the
same tile walk, which is what the bit-identity tests pin down.

Validated with interpret=True on CPU only: Mosaic refuses it for a v5e
(scatter-add has no TPU lowering), so ops.py registers ref as the TPU
default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(rows_ref, contrib_ref, mask_ref, cash_ref, out_ref, *,
            n_rows: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _copy():
        out_ref[...] = cash_ref[...]

    rows = rows_ref[0]                                   # (tile,)
    contrib = contrib_ref[0]
    mask = mask_ref[0]
    acc = out_ref[0]                                     # (R,) in VMEM
    safe = jnp.where(mask, rows, n_rows)                 # masked -> dropped
    out_ref[0] = acc.at[safe].add(jnp.where(mask, contrib, 0.0), mode="drop")


def opic_scatter_add(cash: jax.Array, rows: jax.Array, contrib: jax.Array,
                     mask: jax.Array, *, tile: int = 256,
                     interpret: bool = False):
    """cash (B, R) f32; rows/contrib/mask (B, N). Returns cash'."""
    B, R = cash.shape
    N = rows.shape[1]
    tile = min(tile, N)
    assert N % tile == 0
    nt = N // tile

    kernel = functools.partial(_kernel, n_rows=R)
    return pl.pallas_call(
        kernel,
        grid=(B, nt),
        in_specs=[
            pl.BlockSpec((1, tile), lambda b, t: (b, t)),
            pl.BlockSpec((1, tile), lambda b, t: (b, t)),
            pl.BlockSpec((1, tile), lambda b, t: (b, t)),
            pl.BlockSpec((1, R), lambda b, t: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, R), lambda b, t: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, R), jnp.float32),
        interpret=interpret,
    )(rows, contrib, mask, cash)
