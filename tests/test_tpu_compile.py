"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what the Pallas interpreter accepts (block shapes
off the (8, 128) tiling, too much VMEM), so the support-count kernel and one
engine program are compiled here at the widths `chip_smoke.py` runs:

  hapmap_dom_20  W=22 words, 4 item tiles of 4096
  alz_rec_30     W=12 words, 64 item tiles of 4096
  mcf7           W=400 words, one 512-item tile

Nothing runs, so these say nothing about results or time.  The topology is
described inside a module fixture (only the worker given this file loads the
TPU library), and the persistent compilation cache is off around the
compiles: an entry written for a described chip cannot be read back here.
"""

import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.api import MinerSession, RuntimeConfig
from repro.api.dataset import DEFAULT_BUCKETS
from repro.kernels.support_count import autotune
from repro.kernels.support_count.ops import support_counts_tiled

B = 16  # RuntimeConfig.expand_batch: the engine's per-superstep batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tiles,m_tile,w", [
    (4, 4096, 22),    # hapmap_dom_20
    (64, 4096, 12),   # alz_rec_30
    (1, 512, 400),    # mcf7
])
def test_support_count_kernel_compiles(one_chip, tiles, m_tile, w):
    blocks = autotune.choose_blocks(B, m_tile, w, "pallas")
    fn = jax.jit(functools.partial(support_counts_tiled, impl="pallas",
                                   blocks=blocks))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((B, w), jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((tiles, m_tile, w), jnp.uint32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_count_program_compiles(topo):
    """The hapmap_dom_20 bucket's phase programs, through the session."""
    bucket = DEFAULT_BUCKETS.bucket_for(697, 105, 11914)
    assert bucket.n_tiles == 4 and bucket.tile == 4096
    session = MinerSession(devices=[topo.devices[0]],
                           runtime=RuntimeConfig(kernel_impl="pallas"))
    assert session.warmup(bucket) == 3  # lamp1, count, test
    programs = {key[0]: p for key, p in session._programs.items()}
    assert "tpu_custom_call" in programs["count"].compiled.as_text()
    assert programs["count"].compiled.memory_analysis().argument_size_in_bytes > 0
