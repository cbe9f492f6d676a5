"""The TPU's own compiler, with no chip attached: the table dispatch a
four-chip v5e host runs for a 10k committee — the Pallas ladder per shard
under shard_map — compiles at the real size, keeps its module name, and
needs no collective.  What interpret mode on the CPU cannot show: that
Mosaic takes the kernel inside shard_map.  A compile that passes is not a
chip run.

The topology is described inside a fixture (one process may hold libtpu;
see the on-chip-measurement guide), and this is the one file that does so.
"""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache and
    cannot be read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def test_the_sharded_ladder_compiles_for_four_chips_at_10k(topo, no_compile_cache):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tendermint_tpu.crypto import batch_verifier as bv

    mesh = Mesh(np.array(topo.devices), ("batch",))
    assert mesh.size == 4
    whole, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("batch"))
    engine = bv.BatchVerifier(mesh=mesh)
    engine._pallas = True  # what a TPU backend resolves to; here JAX sees the CPU
    bucket = engine._bucket(9500)
    assert (bucket, engine._shards_for(bucket)) == (10240, 4)

    def arg(shape, dtype, sharding=split):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    fused = bv._shared_fused_jit(engine._inner(), mesh, "batch")
    lowered = fused.lower(
        arg((10000, 4, 20), jnp.int32, whole), arg((bucket,), jnp.int32),
        arg((bucket, 32), jnp.uint8), arg((bucket, 32), jnp.uint8),
        arg((bucket, 20), jnp.int16), arg((bucket,), jnp.uint8),
    )
    assert "module @jit_run " in lowered.as_text()[:200]  # benchmarks/kernels/indexed_run.json
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-gather", "all-reduce", "all-to-all", "collective-permute"):
        assert collective not in text, collective
