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


def test_the_ladders_lowering_does_not_depend_on_which_dispatch_traced_it_first(topo):
    """The ladder is traced once a process, by whichever of the warm-up (the
    flat dispatch) and the table build (the fused one) gets there first, and a
    Pallas kernel's body is lowered with its source locations: both dispatches
    must lower to the same module either way, or the same program has two
    compile-cache keys and a warm cache misses (PR 28, on the chip)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tendermint_tpu.crypto import batch_verifier as bv

    engine = bv.BatchVerifier()
    engine._pallas = True
    inner, bucket = engine._inner(), 512
    one = SingleDeviceSharding(topo.devices[0])

    def arg(*shape_dtype):
        return jax.ShapeDtypeStruct(*shape_dtype, sharding=one)

    rows = (arg((bucket, 20), jnp.int16), arg((bucket,), jnp.uint8))

    def flat():
        digits = arg((bucket, 64), jnp.uint8)
        return inner.func.lower(
            arg((bucket, 4, 20), jnp.int32), digits, digits, *rows, **inner.keywords
        ).as_text()

    def fused():
        packed = arg((bucket, 32), jnp.uint8)
        return bv._shared_fused_jit(inner).lower(
            arg((175, 4, 20), jnp.int32), arg((bucket,), jnp.int32), packed, packed, *rows
        ).as_text()

    jax.clear_caches()
    flat_first = (flat(), fused())
    jax.clear_caches()
    fused_first = (fused(), flat())[::-1]
    assert "tpu_custom_call" in flat_first[0]
    assert flat_first == fused_first
