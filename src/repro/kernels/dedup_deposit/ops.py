"""Public jit'd wrapper for the fused dedup+deposit kernel (DESIGN.md §15).

Dispatch goes through kernels/registry.py — this module only registers the
implementations and exposes the jitted entry point. Beyond the standard
``ref | pallas | interpret`` triple, the family absorbs the bit-packed
Bloom variant as ``pallas_packed`` / ``interpret_packed``: the same fused
body over uint32 filter words (8x VMEM density), with pack/unpack at the
XLA boundary so the byte-per-bit ``CrawlState.bloom_bits`` layout is
unchanged. All implementations are bit-identical (tests/test_kernels.py).

The wrapper pads the item axis up to a whole number of tiles (mask=False
padding is a no-op for the probe, the insert, and the deposit) so callers
aren't bound by the kernel's ``M % tile == 0`` grid constraint.
"""
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.bloom.bloom import pack_bits, unpack_bits
from repro.kernels.dedup_deposit.dedup_deposit import dedup_deposit_kernel
from repro.kernels.dedup_deposit.ref import dedup_deposit_ref


def _packed(interpret: bool):
    def run(bits, urls, mask, val, f_url, f_valid, table, *, k, url_tile=256):
        seen, words, table, refund = dedup_deposit_kernel(
            pack_bits(bits), urls, mask, val, f_url, f_valid, table, k=k,
            url_tile=url_tile, interpret=interpret, packed_kernel=True)
        return seen, unpack_bits(words), table, refund
    return run


# ref is the TPU default too: Mosaic refuses the compiled kernel (a (1, M)
# tile block breaks the (8, 128) block rule, and scatter-add has no TPU
# lowering)
registry.register("dedup_deposit", "ref", dedup_deposit_ref,
                  cpu_default=True, tpu_default=True)
registry.register("dedup_deposit", "pallas",
                  partial(dedup_deposit_kernel, interpret=False))
registry.register("dedup_deposit", "interpret",
                  partial(dedup_deposit_kernel, interpret=True))
registry.register("dedup_deposit", "pallas_packed", _packed(interpret=False))
registry.register("dedup_deposit", "interpret_packed",
                  _packed(interpret=True))


@partial(jax.jit, static_argnames=("k", "impl", "url_tile"))
def dedup_deposit(bits, urls, mask, val, f_url, f_valid, table, *, k: int,
                  impl: str = "ref", url_tile: int = 256):
    """bits (R, 2^b) u8; urls/mask/val (R, M); f_url/f_valid/table (R, C).

    Fused Bloom probe+insert, queued-twin match, and cash deposit. Returns
    ``(seen (R, M) bool, bits', table', refund (R,) f32)`` where ``seen``
    is the (masked) Bloom verdict, ``table'`` carries each seen arrival's
    value accumulated into its queued twin's cell, and ``refund`` sums the
    value of seen arrivals with no queued twin per row."""
    M = urls.shape[1]
    if M == 0:
        return (jnp.zeros(urls.shape, jnp.bool_), bits, table,
                jnp.zeros((bits.shape[0],), jnp.float32))
    url_tile = min(url_tile, M)
    pad = -M % url_tile
    if pad:
        urls = jnp.pad(urls, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        val = jnp.pad(val, ((0, 0), (0, pad)))
    seen, bits, table, refund = registry.dispatch(
        "dedup_deposit", impl, bits, urls, mask, val, f_url, f_valid, table,
        k=k, url_tile=url_tile)
    return (seen[:, :M] if pad else seen), bits, table, refund[:, 0]
