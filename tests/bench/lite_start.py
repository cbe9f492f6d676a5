"""`start` callables for the lite rig (benchmarks/rigs/lite.py `run_cell(...,
start=)`): what the tests, the control and the builder's chip runs start the
process under test with.

Each calls the rig's own `program_start`, so the proxy is the program's, and
then sees to the engine.  The program as it stands starts a light client
without a verify engine (nothing in lite2/ installs one); `with_engine`
installs `BatchVerifier` + `TableCache` on the proxy's recorder the way
`Node.start` does, from outside: a stand-in for the `model_config` PR that
makes the program do it, and a no-op once it does.  It holds before and
after that PR.
"""

from benchmarks.rigs import lite


async def with_recorder(**settings):
    """The proxy as the program starts it, with a flight recorder where the
    program gave it none: what a run under the `stub_device` stand-in needs."""
    from tendermint_tpu.libs import tracing

    proxy = await lite.program_start(**settings)
    if getattr(proxy, "flight_recorder", None) is None:
        proxy.flight_recorder = tracing.FlightRecorder()
    if not hasattr(proxy, "batch_verifier"):
        proxy.batch_verifier = None
    return proxy


async def with_engine(**settings):
    """The proxy with the verify engine of the default `[tpu]` config, as
    Node.start sets it up (the mesh probe, the `verify.engine` event,
    BatchVerifier and TableCache installed as the process's hooks, warm-up
    mode on as AsyncBatchVerifier.start switches it for the node)."""
    from tendermint_tpu.config import TPUConfig
    from tendermint_tpu.crypto import backend
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier, TableCache

    proxy = await with_recorder(**settings)
    if proxy.batch_verifier is not None:
        return proxy
    cfg, recorder = TPUConfig(), proxy.flight_recorder
    mesh, shards, reason = backend.resolve_mesh(cfg.mesh, cfg.mesh_devices)
    recorder.record(
        "verify.engine", ok=not reason.startswith(backend.MESH_PROBE_FAILED), shards=shards,
        mesh=reason, host_tier=backend.active_tier(),
    )
    proxy.batch_verifier = BatchVerifier(
        mesh=mesh, min_device_batch=cfg.min_device_batch, recorder=recorder,
        chunk_size=cfg.chunk_size, chunk_depth=cfg.chunk_depth,
    ).install()
    proxy.table_cache = TableCache(proxy.batch_verifier).install()
    proxy.batch_verifier.start_warmup()
    return proxy


async def without_engine(**settings):
    """The proxy with no engine, whatever the program gave it."""
    proxy = await with_recorder(**settings)
    proxy.batch_verifier = proxy.table_cache = None
    return proxy
