"""Public jit'd wrapper for the opic_update (cash scatter-add) kernel.

Dispatch goes through kernels/registry.py — this module only registers the
implementations and exposes the jitted entry point. The wrapper pads the
item axis up to a whole number of tiles (mask=False padding is a no-op for
the scatter) so callers aren't bound by the kernel's ``N % tile == 0`` grid
constraint.
"""
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.opic_update.opic_update import opic_scatter_add
from repro.kernels.opic_update.ref import opic_ref

# ref is the TPU default too: Mosaic refuses the compiled kernel (scatter-add
# has no TPU lowering)
registry.register("opic_update", "ref", opic_ref, cpu_default=True,
                  tpu_default=True)
registry.register("opic_update", "pallas",
                  partial(opic_scatter_add, interpret=False))
registry.register("opic_update", "interpret",
                  partial(opic_scatter_add, interpret=True))


@partial(jax.jit, static_argnames=("impl", "tile"))
def scatter_cash(cash, rows, contrib, mask, *, impl: str = "ref",
                 tile: int = 256):
    """cash (B, R) f32; rows/contrib/mask (B, N) -> cash' (B, R).

    Masked contributions scatter-add at their row; out-of-range rows drop."""
    N = rows.shape[1]
    if N == 0:
        return cash
    tile = min(tile, N)
    pad = -N % tile
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
        contrib = jnp.pad(contrib, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    return registry.dispatch("opic_update", impl, cash, rows, contrib, mask,
                             tile=tile)


@partial(jax.jit, static_argnames=("impl", "tile"))
def scatter_cash_cells(table, rows, cols, contrib, mask, *,
                       impl: str = "ref", tile: int = 256):
    """table (R, C) f32; rows/cols/contrib/mask: item arrays of any (equal)
    shape. Masked contributions scatter-add into their (row, col) CELL;
    out-of-range coordinates drop.

    The per-URL widening of :func:`scatter_cash` (the ``opic_url`` ordering's
    frontier-aligned cash lane): the cell grid is flattened to one (R*C,)
    cash row so the SAME registered kernel family (ref | pallas | interpret)
    performs the scatter with the SAME tile-walk accumulation order —
    bit-identity across implementations carries over unchanged."""
    R, C = table.shape
    r = rows.reshape(1, -1).astype(jnp.int32)
    c = cols.reshape(1, -1).astype(jnp.int32)
    v = contrib.reshape(1, -1)
    ok = mask.reshape(1, -1) & (r >= 0) & (r < R) & (c >= 0) & (c < C)
    # masked/out-of-range cells flatten to index R*C — past the lane, so the
    # underlying kernel's drop rule applies (never aliases a real cell)
    flat = jnp.where(ok, r * C + c, R * C)
    out = scatter_cash(table.reshape(1, R * C), flat, v, ok,
                       impl=impl, tile=tile)
    return out.reshape(R, C)
