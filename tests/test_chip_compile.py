"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed wherever libtpu is, and it compiles for a chip
that is described rather than attached — so these tests catch what the
interpret-mode tests cannot: block shapes Mosaic refuses, primitives it
cannot lower, and programs that do not fit the device's memory. Shapes are
deployment A's (ROADMAP): 512 frontier rows x 4096-deep queues, Bloom
filters cut to 2^23 bits (chip_smoke.py prints that cut).

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and every test
worker imports this file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

R, C = 512, 4096                  # deployment A: rows x queue depth
M = 4096                          # received URLs per dispatch, 1 chip
BLOOM_BITS_LOG2 = 23              # deployment A's Bloom cut


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip, with the persistent compile cache off (a
    compile for a described chip is written to it but cannot be read back
    without one)."""
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _scatter_updates(hlo: str, into: str) -> list:
    """Update counts of the scatters in ``hlo`` whose result is ``into``
    (an HLO shape such as ``u8[512,8388608]``)."""
    shape = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", hlo))
    counts = []
    pat = re.escape(f"= {into}") + r"\{[^}]*\} scatter\(([^)]*)\)"
    for m in re.finditer(pat, hlo):
        upd = m.group(1).split(", ")[-1].lstrip("%")
        counts.append(math.prod(int(d) for d in shape[upd].split(",") if d))
    return counts


def _fits(compiled, sharding) -> bool:
    from repro.launch.mesh import chip_peaks
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    kind = next(iter(sharding.device_set)).device_kind
    return need <= chip_peaks(kind)["hbm_bytes"]


@pytest.mark.parametrize("k", [1, 64])
def test_frontier_select_compiles_for_v5e(v5e, k):
    """k=1 is deployment A's k_row (fetch_batch 64 over 512 rows)."""
    from repro.kernels.frontier_select.frontier_select import frontier_select
    c = _compile(lambda u, p, v: frontier_select(u, p, v, k=k,
                                                 return_idx=True), v5e,
                 ((R, C), jnp.uint32), ((R, C), jnp.float32),
                 ((R, C), jnp.bool_))
    assert "tpu_custom_call" in c.as_text()
    assert _fits(c, v5e)


@pytest.mark.parametrize("k", [1, 64])
def test_select_harvest_compiles_for_v5e(v5e, k):
    from repro.kernels.frontier_select.frontier_select import (
        select_harvest_kernel)
    c = _compile(lambda u, p, v, t: select_harvest_kernel(u, p, v, t, k=k),
                 v5e, ((R, C), jnp.uint32), ((R, C), jnp.float32),
                 ((R, C), jnp.bool_), ((R, C), jnp.float32))
    assert "tpu_custom_call" in c.as_text()
    assert _fits(c, v5e)


def test_ref_dedup_deposit_fits_v5e_at_deployment_bloom(v5e):
    """The dispatch's Bloom + twin-deposit pass (the ref implementation the
    chip resolves to) at deployment A's filter size. Its Bloom insert
    scatters the compacted arrivals' bits, never the padded (R, url_tile)
    tile's R * 256 * k = 524288 (each masked slot a no-op update that still
    costs its time on the chip)."""
    from repro.kernels.dedup_deposit.ops import dedup_deposit
    c = _compile(lambda *a: dedup_deposit(*a, k=4, impl="ref"), v5e,
                 ((R, 1 << BLOOM_BITS_LOG2), jnp.uint8),
                 ((R, M), jnp.uint32), ((R, M), jnp.bool_),
                 ((R, M), jnp.float32), ((R, C), jnp.uint32),
                 ((R, C), jnp.bool_), ((R, C), jnp.float32))
    assert _fits(c, v5e)
    inserts = _scatter_updates(c.as_text(),
                               f"u8[{R},{1 << BLOOM_BITS_LOG2}]")
    assert inserts, "no scatter into the Bloom bits found"
    assert R * 256 * 4 not in inserts, inserts
