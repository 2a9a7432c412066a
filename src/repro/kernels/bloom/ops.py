"""Public jit'd wrapper for the bloom probe+insert kernel.

Dispatch goes through kernels/registry.py — this module only registers the
implementations and exposes the jitted entry point. The wrapper pads the URL
axis up to a whole number of tiles (mask=False padding is a no-op for both
the probe and the insert) so callers aren't bound by the kernel's
``M % url_tile == 0`` grid constraint.
"""
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.bloom.bloom import bloom_probe_insert
from repro.kernels.bloom.ref import bloom_ref

# ref is the TPU default too: Mosaic refuses the compiled kernel (a (1, 2^b)
# row block breaks the (8, 128) block rule, and the VMEM gather/scatter has
# no TPU lowering)
registry.register("bloom", "ref", bloom_ref, cpu_default=True,
                  tpu_default=True)
registry.register("bloom", "pallas",
                  partial(bloom_probe_insert, interpret=False))
registry.register("bloom", "interpret",
                  partial(bloom_probe_insert, interpret=True))


@partial(jax.jit, static_argnames=("k", "impl", "url_tile"))
def probe_insert(bits, urls, mask, *, k: int, impl: str = "ref",
                 url_tile: int = 256):
    """bits (R, 2^b) uint8, urls/mask (R, M) -> (seen (R, M) bool, bits')."""
    M = urls.shape[1]
    if M == 0:
        return jnp.zeros(urls.shape, jnp.bool_), bits
    url_tile = min(url_tile, M)
    pad = -M % url_tile
    if pad:
        urls = jnp.pad(urls, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    seen, bits = registry.dispatch("bloom", impl, bits, urls, mask, k=k,
                                   url_tile=url_tile)
    return (seen[:, :M] if pad else seen), bits
