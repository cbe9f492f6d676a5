#!/usr/bin/env python
"""Mesh smoke: the sharded verify engine on a CI box — `make mesh-smoke`.

Self-provisions an N-device mesh (default 8) out of virtual host-CPU XLA
devices (`--xla_force_host_platform_device_count`), then proves two
things:

  1. engine — the sharded fused dispatch produces BIT-IDENTICAL verdicts
     to the single-device path on a mixed valid/invalid batch (liar on
     every shard), on ragged sizes, and through the chunked double-buffer;
     throughput of both paths is measured and reported as
     `sharded_sigs_per_sec` / `single_sigs_per_sec` / `mesh_scaling_ratio`
     (speedup ÷ shards — the dryrun acceptance gate is >= 0.7 on real
     multi-chip hardware; on an oversubscribed CI host the ratio is
     reported, not gated, because 8 virtual devices share ~2 cores).
  2. live node — a real solo-validator Node started with [tpu] mesh = "on"
     must route its commit verification through the sharded engine with
     ZERO call-site changes: the smoke waits for committed blocks and then
     asserts the flight recorder holds `verify.dispatch` events carrying
     `shards=N` on a device-side path.

FAILS on: mesh probe not yielding N shards, any verdict divergence, the
live node committing without a sharded device dispatch, or no blocks at
all.  With --json the last stdout line carries the numbers bench.py
reports (`sharded_sigs_per_sec`, `mesh_scaling_ratio`, `verify_shards`).
"""

import argparse
import asyncio
import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def _provision(n_devices: int) -> None:
    """Force n virtual host-CPU XLA devices — must run before jax (or any
    module importing it) initializes a backend."""
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        os.environ.get("XLA_FLAGS", ""),
    )
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n_devices}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"


def _mixed_batch(n_sigs: int, n_vals: int):
    """(pubkeys, idxs, msgs, sigs, expect): one invalid signature per shard
    position so liar attribution is exercised on every shard."""
    from tendermint_tpu.crypto.keys import Ed25519PrivKey

    keys = [Ed25519PrivKey.from_secret(b"mesh-smoke-%d" % i) for i in range(n_vals)]
    pks = [k.pub_key().bytes() for k in keys]
    idxs = [i % n_vals for i in range(n_sigs)]
    msgs = [b"mesh-smoke-msg-%d" % i for i in range(n_sigs)]
    sigs = [keys[idxs[i]].sign(msgs[i]) for i in range(n_sigs)]
    expect = [True] * n_sigs
    stride = max(1, n_sigs // 16)
    for j in range(0, n_sigs, stride):  # liars spread across every shard
        sigs[j] = bytes(64)
        expect[j] = False
    return pks, idxs, msgs, sigs, expect


def engine_phase(args) -> dict:
    import numpy as np  # noqa: F401

    from tendermint_tpu.crypto import backend
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier, PubkeyTable

    mesh, shards, reason = backend.resolve_mesh("on", args.devices)
    print(f"mesh probe: shards={shards} ({reason})", flush=True)
    assert shards == args.devices, f"expected {args.devices} shards: {reason}"

    pks, idxs, msgs, sigs, expect = _mixed_batch(args.batch, 16)

    tab_mesh = PubkeyTable(pks, BatchVerifier(mesh=mesh))
    tab_one = PubkeyTable(pks, BatchVerifier())

    t0 = time.perf_counter()
    out_mesh = tab_mesh.verify_indexed(idxs, msgs, sigs)
    print(f"sharded cold dispatch (compile): {time.perf_counter() - t0:.1f}s", flush=True)
    out_one = tab_one.verify_indexed(idxs, msgs, sigs)
    assert out_mesh == expect, "sharded verdicts wrong vs ground truth"
    assert out_mesh == out_one, "sharded verdicts diverge from single-device"

    # ragged sizes (not divisible by shard count) must not leak padding.
    # Sizes are chosen to land in TWO buckets total (args.batch and 16):
    # every distinct sharded bucket is a fresh XLA compile (~60 s cold on
    # the CI host), so the smoke proves raggedness, not compile stamina.
    t0 = time.perf_counter()
    for nn in (args.batch // 2 + 3, 13, 11):
        assert tab_mesh.verify_indexed(idxs[:nn], msgs[:nn], sigs[:nn]) == expect[:nn], nn
    print(f"ragged OK ({time.perf_counter() - t0:.1f}s)", flush=True)

    # chunked double-buffer path, forced, must match too (chunk bucket 16
    # rides the ragged compile; only the donated per-chunk jit is new)
    t0 = time.perf_counter()
    tab_chunk = PubkeyTable(pks, BatchVerifier(mesh=mesh, chunk_size=16))
    tab_chunk.chunked_single_shot = True
    assert tab_chunk.verify_indexed(idxs, msgs, sigs) == expect, "chunked diverges"
    print(f"chunked OK ({time.perf_counter() - t0:.1f}s)", flush=True)

    def best_of(table, k=3):
        best = float("inf")
        for _ in range(k):
            t0 = time.perf_counter()
            table.verify_indexed(idxs, msgs, sigs)
            best = min(best, time.perf_counter() - t0)
        return best

    t_mesh = best_of(tab_mesh)
    t_one = best_of(tab_one)
    speedup = t_one / t_mesh if t_mesh > 0 else 0.0
    return {
        "verify_shards": shards,
        "sharded_sigs_per_sec": round(args.batch / t_mesh, 1),
        "single_sigs_per_sec": round(args.batch / t_one, 1),
        "mesh_speedup_x": round(speedup, 3),
        "mesh_scaling_ratio": round(speedup / shards, 3),
        "verdicts_identical": True,
    }


async def live_node_phase(args, tmp: str) -> dict:
    from tendermint_tpu.config import test_config as make_test_cfg
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV
    from tendermint_tpu.types.events import EVENT_NEW_BLOCK, query_for_event
    from tendermint_tpu.types.params import BlockParams, ConsensusParams

    pv = MockPV()
    cfg = make_test_cfg(tmp)
    cfg.rpc.laddr = ""
    cfg.base.db_backend = "memdb"
    cfg.base.proxy_app = "kvstore"
    # the live engine, exactly as node.py wires it — mesh forced on so the
    # virtual CPU devices count as a mesh, every batch takes the device path
    cfg.tpu.enabled = True
    cfg.tpu.mesh = "on"
    cfg.tpu.mesh_devices = args.devices
    cfg.tpu.min_device_batch = 1
    gen = GenesisDoc(
        chain_id="mesh-smoke",
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10)],
        consensus_params=ConsensusParams(block=BlockParams(time_iota_ms=1)),
    )
    node = Node(cfg, gen, priv_validator=pv, db_backend="memdb")
    await node.start()
    try:
        sub = await node.event_bus.subscribe(
            "mesh-smoke", query_for_event(EVENT_NEW_BLOCK), buffer=100
        )
        got = 0

        async def consume():
            nonlocal got
            async for _ in sub:
                got += 1
                if got >= args.blocks:
                    return

        await asyncio.wait_for(consume(), args.node_timeout)
    finally:
        await node.stop()
    dispatches = node.flight_recorder.events(kinds=["verify.dispatch"])
    sharded = [
        e for e in dispatches
        if e.get("shards") == args.devices
        and e.get("path") in ("device", "indexed", "chunked")
    ]
    assert dispatches, "live node recorded no verify.dispatch events"
    assert sharded, (
        f"live node never dispatched sharded: {[{k: e.get(k) for k in ('path', 'shards')} for e in dispatches[:8]]}"
    )
    print(
        f"live node: {got} blocks, {len(sharded)}/{len(dispatches)} dispatches sharded over "
        f"{args.devices} devices", flush=True,
    )
    return {
        "live_node_blocks": got,
        "live_node_sharded_dispatches": len(sharded),
        "live_node_sharded_path": True,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--blocks", type=int, default=2)
    ap.add_argument("--node-timeout", type=float, default=120.0)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    _provision(args.devices)

    import tempfile

    report = {"mesh_devices": args.devices}
    report.update(engine_phase(args))
    with tempfile.TemporaryDirectory(prefix="mesh-smoke-") as tmp:
        report.update(asyncio.run(live_node_phase(args, tmp)))

    print("MESH SMOKE OK", flush=True)
    if args.json:
        print(json.dumps(report), flush=True)
    else:
        for k, v in report.items():
            print(f"  {k}: {v}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
