"""Oracle for the opic_update kernel.

Contract: contributions are processed in TILES of ``tile`` along the item
axis, in ascending order; within a tile, one scatter-add applies all masked
contributions (duplicate rows accumulate in item order). Mirroring the
Pallas grid's tile walk keeps the f32 accumulation order identical, which is
what makes ref <-> interpret bit-identity testable (same contract as
kernels/bloom/ref.py).
"""
import jax.numpy as jnp
from jax import lax


def opic_ref(cash, rows, contrib, mask, *, tile=256):
    """cash (B, R) f32; rows/contrib/mask (B, N). Returns cash' with masked
    contributions scatter-added at their rows. The tile walk is a
    ``fori_loop``, so the program size does not grow with N."""
    B, R = cash.shape
    N = rows.shape[1]
    tile = min(tile, N)
    assert N % tile == 0, (N, tile)
    b_idx = jnp.arange(B)[:, None]

    def walk(t, cash):
        r, c, m = (lax.dynamic_slice_in_dim(a, t * tile, tile, axis=1)
                   for a in (rows, contrib, mask))
        return cash.at[b_idx, jnp.where(m, r, R)].add(
            jnp.where(m, c, 0.0), mode="drop")

    return lax.fori_loop(0, N // tile, walk, cash)
