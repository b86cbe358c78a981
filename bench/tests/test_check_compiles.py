"""The check's programs compiled for one TPU v5e at the size of a pool of
50,000 embeddings of width 512, budget 100, without a chip: each fits in
4 GB and none holds the group's whole (n, n) similarity.

A described ``v5e:2x2`` topology gives the devices to compile for.  The
topology is described inside a fixture, never at import: only one process
may load the TPU library at a time.
"""
import jax
import jax.numpy as jnp
import pytest

import reference

N = 50_000  # a pool of 50,000 points
D = 512  # embedding width
BUDGET = 100
LIMIT = 4 * 10**9  # bytes one program of the check may use


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# name -> lowering of one program at the pool's size: the replay the check
# runs, and the greedy of each control
PROGRAMS = {
    "replay-euclidean": lambda x, picks, rows: reference._replay.lower(x, picks, "euclidean", rows),
    "replay-cosine": lambda x, picks, rows: reference._replay.lower(x, picks, "cosine", rows),
    "greedy-high": lambda x, picks, rows: reference._greedy.lower(x, "euclidean", BUDGET, "high", rows),
    "greedy-bfloat16": lambda x, picks, rows: reference._greedy.lower(x, "euclidean", BUDGET, "bfloat16", rows),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_check_fits_one_chip_at_a_pool_of_50000(program, one_chip, no_persistent_cache):
    x = jax.ShapeDtypeStruct((N, D), jnp.float32, sharding=one_chip)
    picks = jax.ShapeDtypeStruct((BUDGET,), jnp.int32, sharding=one_chip)
    compiled = PROGRAMS[program](x, picks, reference.block_rows(N)).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    assert used < LIMIT, (program, used)
    assert f"f32[{N},{N}]" not in compiled.as_text()
