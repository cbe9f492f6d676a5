"""The verify engine's set-up and tear-down, shared by every process that
checks commits: the full node (`Node.start`) and the light client's proxy
(`lite2.proxy.start_proxy`, `tendermint_tpu light`).

`start_engine` probes the mesh, says which engine runs (one `verify.engine`
event and one log line), and installs a `BatchVerifier` and its `TableCache`
as the process-wide hooks that `ValidatorSet.verify_commit*` reaches.
Warm-up mode (batches ride the host path while their bucket compiles on its
own thread) is switched on once, by whoever drives the verifier: the node's
`AsyncBatchVerifier` as it starts, the proxy itself.  `stop_engine` takes
the hooks down again.
"""

from __future__ import annotations

from typing import Optional, Tuple


def start_engine(tpu_cfg, recorder, metrics=None, log=None) -> Tuple[Optional[object], Optional[object]]:
    """(BatchVerifier, TableCache) installed from `tpu_cfg` (a
    `config.TPUConfig`), reporting to `recorder` (a FlightRecorder) and
    `metrics` (a `VerifyMetrics`, or None for none); (None, None) where
    `tpu_cfg.enabled` is off: no engine, every batch on the host."""
    if not tpu_cfg.enabled:
        return None, None
    from . import backend
    from .batch_verifier import BatchVerifier, TableCache

    # Mesh probe ([tpu] mesh = auto|on|off, mesh_devices caps the shard
    # count): sharding degrades to single-device, never a startup failure,
    # and the decision (with the jax backend and device kind it found) is
    # attributed next to the host-crypto tier, so an operator can read one
    # log line, and a tool one `verify.engine` event, and know which engine
    # this process runs.
    mesh, shards, reason = backend.resolve_mesh(tpu_cfg.mesh, tpu_cfg.mesh_devices)
    if metrics is not None:
        metrics.shards.set(shards)
    engine = dict(shards=shards, mesh=reason, host_tier=backend.active_tier())
    if log is not None:
        log.info("verify engine", **engine)
    recorder.record(
        "verify.engine", ok=not reason.startswith(backend.MESH_PROBE_FAILED), **engine
    )
    verifier = BatchVerifier(
        mesh=mesh,
        min_device_batch=tpu_cfg.min_device_batch,
        metrics=metrics,
        recorder=recorder,
        chunk_size=tpu_cfg.chunk_size,
        chunk_depth=tpu_cfg.chunk_depth,
    ).install()
    # steady-state commit path: per-valset device tables (HBM rows,
    # replicated across the mesh)
    table_cache = TableCache(verifier).install()
    return verifier, table_cache


def stop_engine(verifier, table_cache) -> None:
    """Take down the hooks `start_engine` installed, each only where it is
    still this engine's: another engine in the process may have installed
    its own meanwhile."""
    if verifier is None:
        return
    from . import batch as batch_hook

    if batch_hook.get_verifier() == verifier.verify:
        batch_hook.set_verifier(None)
    if table_cache is not None and batch_hook.get_indexed_verifier() == table_cache.verify_indexed:
        batch_hook.set_indexed_verifier(None)
