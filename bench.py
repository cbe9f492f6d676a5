#!/usr/bin/env python
"""Benchmark: the TPU batch-verification engine vs the reference's serial
host architecture, plus secondary BASELINE configs.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

Primary metric (BASELINE config #5 — 10k-validator commit replay):
batched ed25519 signatures/sec through the fused indexed kernel at steady
state (K pipelined batches, result fetched once — how fast-sync replay and
consecutive commit rounds actually drive the engine; host prep for batch
k+1 overlaps device compute of batch k, so per-batch cost is
max(host_prep, device)).  `vs_baseline` is the speedup over one-at-a-time
host verification with the same C ed25519 backend (the reference
architecture: crypto/ed25519/ed25519.go:151 inside the
types/validator_set.go:641-668 loop).

Extras report the single-shot latency, broken out into host prep, device
and dispatch — plus the other BASELINE configs: e2e commits/sec through a
live node, 100-validator commit verify, lite2 bisection, sr25519, multisig.

The localnet and in-proc-net rigs this shells out to are CPU correctness
smokes: every child runs with JAX_PLATFORMS=cpu, because this process
holds the chip and a chip belongs to one process.
"""

import argparse
import asyncio
import json
import os
import time

import numpy as np

# Every rig this benchmark shells out to is a CPU correctness smoke.  The
# parent initialises the TPU first (bench_primary), and a chip belongs to
# one process: a child that reached for it would fail or hang.
_CPU_CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def bench_primary(n_vals: int = 10_000):
    """10k-validator commit batch: latency + steady-state + breakdown.

    Measures the engine's steady-state path, the fused gather + verify
    kernel (the Pallas ladder on a TPU backend, XLA Straus elsewhere).  Table
    build time is reported separately (one-time per validator-set change).

    Also reports the host<->device dispatch RTT probe and BOTH single-shot
    flavors — monolithic (one dispatch) and double-buffered chunked (prep
    of chunk k+1 overlaps device compute of chunk k) — plus which one the
    probe auto-selects, so the chunked path is a measured number instead of
    a dormant code path."""
    import jax

    from tendermint_tpu.crypto import batch_verifier as bv
    from tendermint_tpu.crypto import hostprep
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier, PubkeyTable
    from tendermint_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
    keys = [Ed25519PrivKey.from_secret(b"bench-%d" % i) for i in range(n_vals)]
    pubkeys = [k.pub_key().bytes() for k in keys]
    msgs = [
        b"\x08\x02\x11" + i.to_bytes(8, "little") + b"commit-sign-bytes" * 5
        for i in range(n_vals)
    ]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]

    t0 = time.perf_counter()
    table = PubkeyTable(pubkeys, BatchVerifier())
    table_build_ms = (time.perf_counter() - t0) * 1000
    idxs = list(range(n_vals))
    ok = table.verify_indexed(idxs, msgs, sigs)  # warmup/compile
    assert all(ok), "bench batch failed to verify"

    # dispatch RTT probe: decides (and reports) whether chunked overlap pays
    probe = table.verifier.probe_dispatch_rtt()

    # single-shot latency, BOTH flavors: full host prep + dispatch + fetch,
    # nothing amortized (min over runs: host-side scheduling noise)
    def _timed_single_shot(chunked):
        table.chunked_single_shot = chunked
        lat = []
        table.verify_indexed(idxs, msgs, sigs)  # compile/warm this flavor
        for _ in range(5):
            t0 = time.perf_counter()
            table.verify_indexed(idxs, msgs, sigs)
            lat.append(time.perf_counter() - t0)
        return min(lat) * 1000

    mono_ms = _timed_single_shot(False)
    chunked_ms = _timed_single_shot(True) if n_vals >= 2 * bv._CHUNK else mono_ms
    table.chunked_single_shot = None  # back to probe-driven auto
    auto_chunked = table.verifier.chunked_auto()
    latency_ms = chunked_ms if (auto_chunked and n_vals >= 2 * bv._CHUNK) else mono_ms

    # host prep share
    items = [(pubkeys[i], msgs[i], sigs[i]) for i in range(n_vals)]
    prep = []
    for _ in range(3):
        t0 = time.perf_counter()
        h, s, ry, rs, valid = bv._scalar_rows(items)
        prep.append(time.perf_counter() - t0)
    host_prep_ms = min(prep) * 1000

    # steady state: K pipelined device batches, one fetch at the end
    K = 10
    b = table.verifier._bucket(n_vals)
    h2, s2, ry2, rs2 = bv._pad_scalar_rows(b, h, s, ry, rs)
    idx_arr = np.clip(
        np.concatenate([np.asarray(idxs, np.int32), np.zeros(b - n_vals, np.int32)]),
        0, n_vals - 1,
    )
    # the fused dispatch ships packed 32 B/scalar h and s (expanded
    # in-kernel) — device arrays here must match that wire format
    dev = [
        jax.device_put(a)
        for a in (idx_arr, bv._pack_digits(h2), bv._pack_digits(s2), ry2, rs2)
    ]
    fn = table._fused()
    np.asarray(fn(table.neg_a_rows, *dev))
    t0 = time.perf_counter()
    outs = [fn(table.neg_a_rows, *dev) for _ in range(K)]
    np.asarray(outs[-1])
    steady_device_ms = (time.perf_counter() - t0) / K * 1000

    steady_ms = max(steady_device_ms, host_prep_ms)
    sigs_per_sec = n_vals / (steady_ms / 1000)

    # serial host baseline (reference architecture), sampled
    sample = 512
    pks = [Ed25519PubKey(pk) for pk in pubkeys[:sample]]
    t0 = time.perf_counter()
    for pk, m, s_ in zip(pks, msgs[:sample], sigs[:sample]):
        assert pk.verify(m, s_)
    host_serial_per_sig = (time.perf_counter() - t0) / sample
    host_sigs_per_sec = 1.0 / host_serial_per_sig

    return {
        "sigs_per_sec": sigs_per_sec,
        "vs_baseline": sigs_per_sec / host_sigs_per_sec,
        "batch_ms_per_10k_commit": steady_ms,
        "single_shot_latency_ms": latency_ms,
        "single_shot_monolithic_ms": mono_ms,
        "single_shot_chunked_ms": chunked_ms,
        "chunked_auto_selected": bool(auto_chunked),
        "dispatch_rtt_ms": probe["dispatch_rtt_ms"],
        "prep_ms_per_chunk": probe["prep_ms_per_chunk"],
        "steady_device_ms": steady_device_ms,
        "host_prep_ms": host_prep_ms,
        "host_prep_fused_c": bool(hostprep.have_fast_prep()),
        "host_serial_sigs_per_sec": host_sigs_per_sec,
        "table_build_ms": table_build_ms,
    }


def bench_100val_commit():
    """BASELINE #2 flavor: one 100-validator commit through
    ValidatorSet.verify_commit with the engine installed."""
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier
    from tendermint_tpu.types import (
        BlockID,
        MockPV,
        PartSetHeader,
        Validator,
        ValidatorSet,
        Vote,
        VoteSet,
    )
    from tendermint_tpu.types.canonical import PRECOMMIT_TYPE

    pvs = [MockPV() for _ in range(100)]
    vset = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in pvs])
    pvs.sort(key=lambda pv: pv.address())
    bid = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))
    vs = VoteSet("bench-chain", 5, 0, PRECOMMIT_TYPE, vset)
    for pv in pvs:
        i, _ = vset.get_by_address(pv.address())
        v = Vote(type=PRECOMMIT_TYPE, height=5, round=0, block_id=bid,
                 timestamp_ns=1, validator_address=pv.address(), validator_index=i)
        pv.sign_vote("bench-chain", v)
        vs.add_vote(v)
    commit = vs.make_commit()
    BatchVerifier().install()
    try:
        vset.verify_commit("bench-chain", bid, 5, commit)  # warmup
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            vset.verify_commit("bench-chain", bid, 5, commit)
            times.append(time.perf_counter() - t0)
        return min(times) * 1000
    finally:
        from tendermint_tpu.crypto import batch as batch_hook

        batch_hook.set_verifier(None)


async def bench_e2e_commits():
    """Live-node throughput: solo validator, kvstore app, memdb — blocks
    committed per second through the full consensus+ABCI+store pipeline."""
    import tempfile

    from tendermint_tpu.config import test_config as make_test_cfg
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

    from tendermint_tpu.types.params import BlockParams, ConsensusParams

    pv = MockPV()
    gen = GenesisDoc(
        chain_id="bench-e2e",
        genesis_time_ns=time.time_ns(),
        validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10)],
        # iota=1ms: at 100+ commits/sec the default 1000 ms BFT-time step
        # would race block time ahead of wall clock and trip the
        # propose-side clock-drift guard (and lite2's) within seconds
        consensus_params=ConsensusParams(block=BlockParams(time_iota_ms=1)),
    )
    with tempfile.TemporaryDirectory() as home:
        cfg = make_test_cfg(home)
        cfg.rpc.laddr = ""
        cfg.base.db_backend = "memdb"
        cfg.consensus.timeout_commit = 0.0
        cfg.consensus.skip_timeout_commit = True
        node = Node(cfg, gen, priv_validator=pv, db_backend="memdb")
        await node.start()
        try:
            while node.block_store.height() < 2:
                await asyncio.sleep(0.01)
            start_h = node.block_store.height()
            t0 = time.perf_counter()
            await asyncio.sleep(5.0)
            dh = node.block_store.height() - start_h
            return dh / (time.perf_counter() - t0)
        finally:
            await node.stop()


async def bench_e2e_4val():
    """BASELINE config #1: 4-validator localnet (full nodes, real TCP
    gossip on localhost, batch-verification engine enabled) — committed
    blocks per second while all nodes stay in lock-step."""
    import tempfile

    from tendermint_tpu.config import test_config as make_test_cfg
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

    from tendermint_tpu.types.params import BlockParams, ConsensusParams

    pvs = sorted([MockPV() for _ in range(4)], key=lambda pv: pv.address())
    gen = GenesisDoc(
        chain_id="bench-4val",
        genesis_time_ns=time.time_ns(),
        validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs],
        consensus_params=ConsensusParams(block=BlockParams(time_iota_ms=1)),
    )
    with tempfile.TemporaryDirectory() as home:
        nodes = []
        for i, pv in enumerate(pvs):
            cfg = make_test_cfg(f"{home}/n{i}")
            cfg.rpc.laddr = ""
            cfg.base.db_backend = "memdb"
            cfg.p2p.laddr = "127.0.0.1:0"
            cfg.consensus.skip_timeout_commit = True
            cfg.consensus.timeout_commit = 0.0
            cfg.tpu.enabled = True
            nodes.append(Node(cfg, gen, priv_validator=pv, db_backend="memdb"))
        try:
            for node in nodes:
                await node.start()
            for i in range(4):
                for j in range(i + 1, 4):
                    addr = f"{nodes[j].node_key.id}@{nodes[j].switch.transport.listen_addr}"
                    await nodes[i].switch.dial_peer(addr)

            async def all_at(h):
                while not all(n.block_store.height() >= h for n in nodes):
                    await asyncio.sleep(0.02)

            await asyncio.wait_for(all_at(2), 60.0)
            start_h = min(n.block_store.height() for n in nodes)
            t0 = time.perf_counter()
            await asyncio.sleep(10.0)
            dh = min(n.block_store.height() for n in nodes) - start_h
            return dh / (time.perf_counter() - t0)
        finally:
            for node in nodes:
                if node.is_running:
                    await node.stop()


def bench_e2e_4val_procs(duration: float = 12.0):
    """BASELINE config #1 measured HONESTLY: 4 validator nodes as separate
    OS processes (own interpreter, own event loop, own JAX runtime), real
    TCP gossip on localhost, throughput-rig configs (`testnet --fast`:
    test-grade timeouts, skip_timeout_commit, time_iota_ms=1 genesis).
    Readiness-gated by networks/local/run_localnet.py: the clock starts
    only after every node's RPC reports height >= 1, so per-process JAX
    cold start is excluded.  Runs with --trace-net: the four recorder
    dumps must merge into one complete causal timeline with per-process
    loop attribution (the trace-net-smoke gate, wired into the bench so
    the cross-node tracing layer is exercised on every full run).
    Returns the run_localnet JSON result."""
    import socket
    import subprocess
    import sys
    import tempfile

    def _free_base_port():
        # testnet uses base+10i (p2p) and base+10i+1 (rpc) for i<4
        for _ in range(20):
            base = int.from_bytes(os.urandom(2), "big") % 30000 + 20000
            socks = []
            try:
                for off in range(0, 40, 10):
                    for d in (0, 1):
                        s = socket.socket()
                        socks.append(s)  # before bind: close it even on failure
                        s.bind(("127.0.0.1", base + off + d))
                return base
            except OSError:
                continue
            finally:
                for s in socks:
                    s.close()
        raise RuntimeError("no free port range found")

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        build = os.path.join(tmp, "build")
        subprocess.run(
            [sys.executable, "-m", "tendermint_tpu.cli", "testnet",
             "--validators", "4", "--output", build,
             "--base-port", str(_free_base_port()), "--fast"],
            check=True, capture_output=True, timeout=120, cwd=repo, env=_CPU_CHILD_ENV,
        )
        run = subprocess.run(
            [sys.executable, os.path.join(repo, "networks", "local", "run_localnet.py"),
             build, "--duration", str(duration), "--trace-net", "--json"],
            capture_output=True, text=True, timeout=duration + 150, cwd=repo, env=_CPU_CHILD_ENV,
        )
        if run.returncode != 0:
            raise RuntimeError(f"localnet run failed:\n{run.stdout}\n{run.stderr}")
        return json.loads(run.stdout.strip().splitlines()[-1])


def bench_chaos_recovery():
    """Chaos engine acceptance as a number: run the scripted
    partition/kill/twin scenario (networks/local/chaos_smoke.py) and
    report `chaos_partition_recovery_ms` — wall milliseconds from the
    partition healing to the first new commit, measured by the invariant
    checker while it also proves agreement, no-regression, restart
    recovery, and twin-evidence accountability.  Raises if any invariant
    failed."""
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        run = subprocess.run(
            [sys.executable, os.path.join(repo, "networks", "local", "chaos_smoke.py"),
             "--build-dir", os.path.join(tmp, "build"), "--base-port", "30756", "--json"],
            capture_output=True, text=True, timeout=420, cwd=repo, env=_CPU_CHILD_ENV,
        )
        if run.returncode != 0:
            raise RuntimeError(f"chaos smoke failed:\n{run.stdout}\n{run.stderr}")
        return json.loads(run.stdout.strip().splitlines()[-1])


def bench_disk():
    """Storage-fault chaos acceptance as numbers: run the rot/ENOSPC
    scenario (networks/local/disk_smoke.py) and report
    `disk_fault_recovery_ms` (seeded block-store bit-rot -> integrity-scan
    detection -> quarantine -> verified peer refill -> served again),
    `store_integrity_scan_ms` (the sweep itself) and `enospc_recovery_ms`
    (clean halt under ENOSPC -> heal + restart -> commits past the
    pre-fault tip), while the invariant checker also proves agreement and
    that no node ever served corrupted bytes as a valid block.  Raises if
    any invariant failed."""
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        run = subprocess.run(
            [sys.executable, os.path.join(repo, "networks", "local", "disk_smoke.py"),
             "--build-dir", os.path.join(tmp, "build"), "--base-port", "31756", "--json"],
            capture_output=True, text=True, timeout=420, cwd=repo, env=_CPU_CHILD_ENV,
        )
        if run.returncode != 0:
            raise RuntimeError(f"disk smoke failed:\n{run.stdout}\n{run.stderr}")
        return json.loads(run.stdout.strip().splitlines()[-1])


def bench_scale_100val():
    """BASELINE config #2 measured LIVE for the first time: a 100-validator
    in-process net (verify engine ON, chordal peer topology, relay gossip +
    maj23 vote aggregation) committing >= 10 consecutive blocks
    (networks/local/scale_smoke.py), plus a 50|50 partition/heal judged by
    the chaos invariant checker.  Reports `e2e_commits_per_sec_100val`,
    the gossip wakeup/batch telemetry, and the scheduler-profiler numbers
    that replace the old "Python-loop-bound" narrative with measurement:
    `loop_lag_ms_p90_100val`, `commit_skew_ms_100val` and
    `block_attribution_100val` (loop-task / GC / loop-lag / idle shares
    of each block's wall time, merged from all 100 recorders), plus the
    network-plane numbers from wire-level trace context:
    `vote_fanin_ms`, `part_stream_ms`, `gossip_hop_p90_ms` and
    `measured_skew_nodes` (nodes whose merge alignment came from
    measured origin-vs-receive latency, not landmark estimation).  Raises
    if the net failed to commit, any invariant was violated, or the heal
    never recovered."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    run = subprocess.run(
        [sys.executable, os.path.join(repo, "networks", "local", "scale_smoke.py"),
         "--json"],
        capture_output=True, text=True, timeout=3600, cwd=repo, env=_CPU_CHILD_ENV,
    )
    if run.returncode != 0:
        raise RuntimeError(f"scale smoke failed:\n{run.stdout[-2000:]}\n{run.stderr[-2000:]}")
    return json.loads(run.stdout.strip().splitlines()[-1])


def bench_rotation():
    """Dynamic validator sets measured live: run the rotation rig
    (networks/local/rotation_smoke.py — a 7-node staking-app net that
    grows 4→7 validators through real bond txs with a partition and a
    twin double-signer ACROSS the set change, observes the epoch
    barrel-shift, votes the halted twin out, live-migrates every
    validator ed25519→BLS12-381 and back one, fastsyncs a fresh node and
    bisects a lite2 client over the rotated history) and report
    `valset_update_latency_ms` (stake-tx submit → set effective),
    `bls_migration_height_gap` (set uniformity → first stored
    AggregateCommit) and `lite2_skip_across_rotation_ok`.  Any invariant
    violation or missing engine table rebuild fails the smoke, not just
    the bench."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    run = subprocess.run(
        [sys.executable, os.path.join(repo, "networks", "local", "rotation_smoke.py"),
         "--json"],
        capture_output=True, text=True, timeout=1800, cwd=repo, env=_CPU_CHILD_ENV,
    )
    if run.returncode != 0:
        raise RuntimeError(f"rotation smoke failed:\n{run.stdout[-2000:]}\n{run.stderr[-2000:]}")
    return json.loads(run.stdout.strip().splitlines()[-1])


def bench_mesh_scaling():
    """Sharded verify engine over 8 virtual CPU devices
    (networks/local/mesh_smoke.py): bit-identical verdicts vs the
    single-device path asserted (mixed batches, ragged sizes, chunked),
    a live solo node asserted to route commit verifies through the
    sharded path, and throughput of both paths measured.  Reports
    `sharded_sigs_per_sec` and `mesh_scaling_ratio` (speedup ÷ shards —
    the >= 0.7 acceptance gate applies on real multi-chip hardware; 8
    virtual CPU devices share this host's cores, so here the ratio is
    reported, not gated)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.abspath(__file__))
    run = subprocess.run(
        [sys.executable, os.path.join(repo, "networks", "local", "mesh_smoke.py"),
         "--json"],
        capture_output=True, text=True, timeout=1800, cwd=repo, env=_CPU_CHILD_ENV,
    )
    if run.returncode != 0:
        raise RuntimeError(f"mesh smoke failed:\n{run.stdout[-2000:]}\n{run.stderr[-2000:]}")
    return json.loads(run.stdout.strip().splitlines()[-1])


def bench_load():
    """Overload acceptance as numbers: run the tx-ingress firehose rig
    (networks/local/load_smoke.py — QoS-configured 4-val localnet, chaos
    invariant checker scraping underneath a saturating signed-tx
    firehose) and report `tx_ingress_sustained_tps` (accepted tx/sec at
    admission under >= 2x offered load) and `commit_latency_under_load_ms`
    (p90 commit interval from the target node's flight recorder while the
    firehose runs).  Raises if any invariant failed — silent drops, a
    commit stall, or an unrecovered post-firehose commit rate fail the
    smoke, not just the bench."""
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        run = subprocess.run(
            [sys.executable, os.path.join(repo, "networks", "local", "load_smoke.py"),
             "--build-dir", os.path.join(tmp, "build"), "--base-port", "31856", "--json"],
            capture_output=True, text=True, timeout=420, cwd=repo, env=_CPU_CHILD_ENV,
        )
        if run.returncode != 0:
            raise RuntimeError(f"load smoke failed:\n{run.stdout}\n{run.stderr}")
        return json.loads(run.stdout.strip().splitlines()[-1])


def bench_lite():
    """Light-client gateway acceptance as numbers: run the liteserve rig
    (networks/local/lite_smoke.py — 64 concurrent bisecting sessions
    against a gateway fronting a live 4-val localnet, then an adversarial
    twin-signing primary) and report `lite_bisections_per_sec` (tenant
    commits verified per second off the shared engine),
    `lite_cache_hit_ratio` / `lite_verify_coalesce_ratio` (work avoided by
    the shared store and single-flight coalescing),
    `lite_sessions_sustained`, and `lite_diverged_detect_ms` (wall time
    from the forged header being served to the tenant getting the real
    one back, primary demoted).  Raises if any invariant failed — a
    forged header reaching a tenant or the shared store fails the smoke,
    not just the bench."""
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        run = subprocess.run(
            [sys.executable, os.path.join(repo, "networks", "local", "lite_smoke.py"),
             "--build-dir", os.path.join(tmp, "build"), "--base-port", "33656", "--json"],
            capture_output=True, text=True, timeout=420, cwd=repo, env=_CPU_CHILD_ENV,
        )
        if run.returncode != 0:
            raise RuntimeError(f"lite smoke failed:\n{run.stdout}\n{run.stderr}")
        return json.loads(run.stdout.strip().splitlines()[-1])


def bench_finality():
    """Consensus-pipeline finality as numbers: run the A/B finality rig
    (networks/local/finality_smoke.py — the same 4-val localnet measured
    serial then pipelined, stage budgets from node0's flight recorder)
    and report `commit_to_commit_p50_ms`/`commit_to_commit_p90_ms`
    (pipelined idle), `commit_to_commit_p50_ms_serial` (the A/B
    baseline), `finality_under_load_p50_ms` (under a tools/loadgen.py
    firehose), both arms' per-stage budgets, and the pipelined arm's
    cross-node net budget: `vote_fanin_ms` (first vote seen → +2/3),
    `part_stream_ms` (first part → part set complete) and
    `gossip_hop_p90_ms` (wire-level trace-context propagation latency).
    Raises on any checker
    violation, a p50 >= 100 ms, or a p50 regression past the serial
    arm — the smoke gates, not just the bench."""
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        run = subprocess.run(
            [sys.executable, os.path.join(repo, "networks", "local", "finality_smoke.py"),
             "--build-dir", os.path.join(tmp, "build"), "--base-port", "31956", "--json"],
            capture_output=True, text=True, timeout=420, cwd=repo, env=_CPU_CHILD_ENV,
        )
        if run.returncode != 0:
            raise RuntimeError(f"finality smoke failed:\n{run.stdout}\n{run.stderr}")
        return json.loads(run.stdout.strip().splitlines()[-1])


def bench_forensics():
    """Crash forensics + self-diagnosis as numbers: run the forensics rig
    (networks/local/forensics_smoke.py — flight spool + watchdog armed on
    a 4-val chaos localnet) and report `crash_bundle_completeness` (share
    of a SIGKILLed node's interior pre-crash heights whose full
    propose→commit span chain reconstructs OFFLINE from its on-disk
    spool via `debug dump`; must be 1.0) and `health_detect_latency_ms`
    (wall ms from an injected partition to the node's own consensus_stall
    alarm on /health).  Raises if the bundle was incomplete, the alarm
    never fired/cleared, or any false alarm hit the quiet phase."""
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        run = subprocess.run(
            [sys.executable, os.path.join(repo, "networks", "local", "forensics_smoke.py"),
             "--build-dir", os.path.join(tmp, "build"), "--base-port", "32856", "--json"],
            capture_output=True, text=True, timeout=420, cwd=repo, env=_CPU_CHILD_ENV,
        )
        if run.returncode != 0:
            raise RuntimeError(f"forensics smoke failed:\n{run.stdout}\n{run.stderr}")
        return json.loads(run.stdout.strip().splitlines()[-1])


def bench_statesync_bootstrap():
    """Statesync bootstrap time, measured from REAL recorder spans: an
    empty 4th node joins a live 3-validator localnet via snapshot restore
    (networks/local/statesync_smoke.py) and reports the
    offer→chunk→restore→handover wall milliseconds from its own flight
    recorder — the `statesync_bootstrap_ms` BASELINE entry.  The rig
    FAILS (raises) if the joiner fell back to replay-from-genesis."""
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory() as tmp:
        run = subprocess.run(
            [sys.executable, os.path.join(repo, "networks", "local", "statesync_smoke.py"),
             "--build-dir", os.path.join(tmp, "build"), "--base-port", "29756", "--json"],
            capture_output=True, text=True, timeout=300, cwd=repo, env=_CPU_CHILD_ENV,
        )
        if run.returncode != 0:
            raise RuntimeError(f"statesync smoke failed:\n{run.stdout}\n{run.stderr}")
        return json.loads(run.stdout.strip().splitlines()[-1])


async def bench_vote_hop_flush():
    """Latency a SINGLE sparse vote pays in the AsyncBatchVerifier before
    its flush fires (the per-hop quantum the adaptive window shrinks) — at
    4 validators every vote rides this path, twice per block."""
    from tendermint_tpu.crypto.batch_verifier import AsyncBatchVerifier, BatchVerifier
    from tendermint_tpu.crypto.keys import Ed25519PrivKey

    k = Ed25519PrivKey.from_secret(b"hop")
    msg = b"\x08\x02\x11" + bytes(80)
    sig = k.sign(msg)
    svc = AsyncBatchVerifier(BatchVerifier())
    await svc.start()
    try:
        assert await svc.verify_one(k.pub_key().bytes(), msg, sig)  # warm
        times = []
        for _ in range(20):
            await asyncio.sleep(0.01)  # let the queue go idle (sparse regime)
            t0 = time.perf_counter()
            assert await svc.verify_one(k.pub_key().bytes(), msg, sig)
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2] * 1000  # median
    finally:
        await svc.stop()


async def bench_vote_ingest_100val():
    """BASELINE config #2 core: consensus-side aggregation of one round's
    100 precommits through the AsyncBatchVerifier vote-ingress path (what
    randConsensusNet exercises per round) — ms for all 100 votes from
    enqueue to verified."""
    from tendermint_tpu.crypto.batch_verifier import AsyncBatchVerifier, BatchVerifier
    from tendermint_tpu.types import (
        BlockID, MockPV, PartSetHeader, Validator, ValidatorSet, Vote, VoteSet,
    )
    from tendermint_tpu.types.canonical import PRECOMMIT_TYPE

    pvs = [MockPV() for _ in range(100)]
    vset = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in pvs])
    pvs.sort(key=lambda pv: pv.address())
    bid = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))
    votes = []
    for pv in pvs:
        i, _ = vset.get_by_address(pv.address())
        v = Vote(type=PRECOMMIT_TYPE, height=5, round=0, block_id=bid,
                 timestamp_ns=1, validator_address=pv.address(), validator_index=i)
        pv.sign_vote("bench-chain", v)
        votes.append((v, pv))
    from tendermint_tpu.libs.tracing import FlightRecorder

    rec = FlightRecorder()
    svc = AsyncBatchVerifier(BatchVerifier(recorder=rec), flush_interval=0.002)
    await svc.start()
    try:
        async def ingest():
            futs = []
            for v, pv in votes:
                futs.append(
                    svc.verify_one(
                        pv.get_pub_key().bytes(), v.sign_bytes("bench-chain"), v.signature
                    )
                )
            res = await asyncio.gather(*futs)
            assert all(res)

        seen = 0

        def all_on_device() -> bool:
            """True when every dispatch since the last look ran on the
            device; a failed bucket compile fails the bench."""
            nonlocal seen
            evs = rec.events(seen, kinds=["verify.bucket_compile", "verify.dispatch"])
            if evs:
                seen = evs[-1]["seq"] + 1
            for ev in evs:
                if ev.get("ok") is False:
                    raise RuntimeError(f"bucket compile failed: {ev}")
            paths = [ev["path"] for ev in evs if ev["kind"] == "verify.dispatch"]
            return bool(paths) and all(p == "device" for p in paths)

        # start() puts the engine in warm-up mode: flushes ride the host
        # tier ("host-cold") until their bucket's compile lands, and 100
        # host verifies finish long before a compile does.  Ingest until a
        # whole round is dispatched on the device, so the timed rounds
        # measure the device path.
        deadline = time.monotonic() + 600
        while True:
            await ingest()
            if all_on_device():
                break
            if time.monotonic() > deadline:
                raise RuntimeError("vote-ingest buckets never compiled")
            await asyncio.sleep(0.5)
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            await ingest()
            times.append(time.perf_counter() - t0)
        if not all_on_device():
            raise RuntimeError("a timed vote-ingest round left the device path")
        return min(times) * 1000
    finally:
        await svc.stop()


def bench_sr25519():
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    k = Sr25519PrivKey.from_secret(b"bench")
    sig = k.sign(b"bench message")
    pub = k.pub_key()
    assert pub.verify(b"bench message", sig)
    t0 = time.perf_counter()
    n = 30
    for _ in range(n):
        pub.verify(b"bench message", sig)
    return (time.perf_counter() - t0) / n * 1000


def bench_multisig():
    from tendermint_tpu.crypto.keys import Ed25519PrivKey
    from tendermint_tpu.crypto.multisig import (
        MultisigThresholdPubKey,
        build_multisig_signature,
    )
    from tendermint_tpu.libs.bitarray import BitArray

    keys = [Ed25519PrivKey.from_secret(b"ms%d" % i) for i in range(10)]
    pub = MultisigThresholdPubKey(7, [k.pub_key() for k in keys])
    msg = b"multisig bench payload"
    bits = BitArray(10)
    sigs = []
    for i in range(7):
        bits.set_index(i, True)
        sigs.append(keys[i].sign(msg))
    agg = build_multisig_signature(bits, sigs)
    assert pub.verify(msg, agg)
    t0 = time.perf_counter()
    n = 50
    for _ in range(n):
        pub.verify(msg, agg)
    return (time.perf_counter() - t0) / n * 1000


def bench_bls():
    """ROADMAP item 2 numbers: a 100-validator BLS aggregate commit is ONE
    96-byte signature + bitmap verified by ONE pairing check.  Reports
    `bls_agg_verify_ms` (the single FastAggregateVerify pairing for the
    whole commit — what lite2/statesync/fastsync pay per block instead of
    100 verifies), `bls_commit_bytes` vs the classic ed25519 commit at the
    same N (`bls_commit_shrink_x`, acceptance floor 10×), and the fold
    cost consensus pays once at commit time."""
    from tendermint_tpu.crypto.bls import scheme
    from tendermint_tpu.crypto.bls.keys import BlsPrivKey
    from tendermint_tpu.types import (
        BlockID,
        MockPV,
        PartSetHeader,
        Validator,
        ValidatorSet,
        Vote,
        VoteSet,
    )
    from tendermint_tpu.types.agg_commit import fold_commit
    from tendermint_tpu.types.canonical import PRECOMMIT_TYPE

    n_vals = 100

    def full_commit(pvs):
        vset = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in pvs])
        bid = BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32))
        vs = VoteSet("bench-chain", 5, 0, PRECOMMIT_TYPE, vset)
        for pv in pvs:
            i, _ = vset.get_by_address(pv.address())
            v = Vote(type=PRECOMMIT_TYPE, height=5, round=0, block_id=bid,
                     timestamp_ns=i + 1, validator_address=pv.address(),
                     validator_index=i)
            pv.sign_vote("bench-chain", v)
            vs.add_vote(v)
        return vset, vs.make_commit()

    bls_pvs = sorted(
        [MockPV(priv_key=BlsPrivKey.from_secret(b"bls-bench-%d" % i))
         for i in range(n_vals)],
        key=lambda pv: pv.address(),
    )
    vset, commit = full_commit(bls_pvs)
    t0 = time.perf_counter()
    agg = fold_commit(commit, vset, "bench-chain")
    fold_ms = (time.perf_counter() - t0) * 1000
    assert agg is not None and agg.signers.count() == n_vals

    pks = [v.pub_key.bytes() for v in vset.validators]
    msg = agg.sign_message("bench-chain")

    def measure_verify() -> float:
        assert scheme.fast_aggregate_verify(pks, msg, agg.agg_sig)  # warmup
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            assert scheme.fast_aggregate_verify(pks, msg, agg.agg_sig)
            times.append(time.perf_counter() - t0)
        return min(times) * 1000

    # per-tier attribution: the active tier's number keeps the historical
    # key, and both tiers are always reported so the C-tier speedup (and
    # any regression back to pure) is visible in one JSON line
    from tendermint_tpu.crypto.bls import ctier

    tier = scheme.active_tier()
    verify_ms = measure_verify()
    if tier == "c":
        verify_ms_c = verify_ms
        ctier.set_forced("pure")
        try:
            verify_ms_pure = measure_verify()
        finally:
            ctier.set_forced(None)
        # generous load-noise headroom over the 25 ms acceptance target:
        # the tier silently not engaging is a ~460 ms number this catches
        assert verify_ms_c <= 100.0, (
            f"C pairing tier engaged but bls_agg_verify_ms={verify_ms_c:.1f}"
        )
    else:
        verify_ms_c = None
        verify_ms_pure = verify_ms

    # cold hash-to-curve (no memo hit): the C map (expand_message_xmd +
    # SVDW + clear cofactor, all in csrc/bls12_381.c) vs the pure
    # reference map.  Acceptance: <= 1 ms with the C tier engaged.
    from tendermint_tpu.crypto.bls import hash_to_curve

    def measure_h2c(fn) -> float:
        times = []
        for i in range(7):
            m = b"bench-h2c-cold-%d" % i
            t0 = time.perf_counter()
            fn(m)
            times.append(time.perf_counter() - t0)
        return min(times) * 1000

    h2c_pure_ms = measure_h2c(
        lambda m: hash_to_curve.hash_to_g2(m, scheme.DST_SIG)
    )
    if tier == "c":
        h2c_ms = measure_h2c(lambda m: ctier.hash_to_g2_blob(m, scheme.DST_SIG))
        # the C map silently not engaging is the ~15 ms pure number
        assert h2c_ms <= 5.0, (
            f"C hash-to-curve engaged but bls_h2c_ms={h2c_ms:.2f}"
        )
    else:
        h2c_ms = h2c_pure_ms

    ed_pvs = sorted([MockPV() for _ in range(n_vals)], key=lambda pv: pv.address())
    _, ed_commit = full_commit(ed_pvs)
    bls_bytes = len(agg.encode())
    # classic commit canonical bytes: same proto layout AggregateCommit.encode
    # uses, with one CommitSig record per validator slot
    from tendermint_tpu.encoding.proto import field_bytes, field_varint

    ed_bytes = len(
        field_varint(1, ed_commit.height)
        + field_varint(2, ed_commit.round)
        + field_bytes(3, ed_commit.block_id.encode())
        + b"".join(field_bytes(4, cs.encode()) for cs in ed_commit.signatures)
    )
    shrink = ed_bytes / bls_bytes
    assert shrink >= 10.0, (
        f"aggregate commit only {shrink:.1f}x smaller than ed25519 at N={n_vals}"
    )
    out = {
        "bls_agg_verify_ms": round(verify_ms, 2),
        "bls_agg_verify_ms_pure": round(verify_ms_pure, 2),
        "bls_tier": tier,
        "bls_commit_bytes": bls_bytes,
        "ed25519_commit_bytes_100val": ed_bytes,
        "bls_commit_shrink_x": round(shrink, 1),
        "bls_fold_ms": round(fold_ms, 2),
        "bls_h2c_ms": round(h2c_ms, 3),
        "bls_h2c_ms_pure": round(h2c_pure_ms, 3),
    }
    if verify_ms_c is not None:
        out["bls_agg_verify_ms_c"] = round(verify_ms_c, 2)
    return out


async def bench_lite2():
    """BASELINE #4: bisection sync to height 20 of a 100-validator chain
    (every hop = batched commit verifications on the engine)."""
    import sys

    sys.path.insert(0, ".")
    import tests.test_lite2 as fixtures

    from tendermint_tpu.crypto.batch_verifier import BatchVerifier
    from tendermint_tpu.lite2 import Client, MemStore, MockProvider, TrustOptions

    vset, pvs = fixtures.rand_vset(100)
    headers, vals = fixtures.make_chain(20, {1: (vset, pvs)})
    BatchVerifier().install()
    try:
        provider = MockProvider(fixtures.CHAIN, headers, vals)
        opts = TrustOptions(fixtures.PERIOD, 1, headers[1].header.hash())

        async def sync():
            c = Client(fixtures.CHAIN, opts, provider, store=MemStore(),
                       now_fn=lambda: fixtures.T0 + 30 * fixtures.SEC)
            sh = await c.verify_header_at_height(20)
            assert sh.height == 20

        await sync()  # warmup/compile
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            await sync()
            times.append(time.perf_counter() - t0)
        return min(times) * 1000
    finally:
        from tendermint_tpu.crypto import batch as batch_hook

        batch_hook.set_verifier(None)


def _e2e_breakdown(procs: dict, hop_ms: float) -> str:
    """One-paragraph accounting of where each committed block's
    milliseconds go in the 4-validator multi-process run.

    Primary source: the flight recorder (libs/tracing.py) — run_localnet.py
    dumps each node's ring via the dump_flight_recorder RPC and medians the
    per-step spans, so this number and production telemetry come from the
    same instrumentation.  The narrative estimate below survives only as
    the fallback when the recorder dump failed."""
    rec = procs.get("recorder")
    if rec and rec.get("blocks"):
        cps = procs.get("commits_per_sec", 0) or 0.001
        return (
            f"4-val procs, flight-recorder sourced ({rec['blocks']} complete "
            f"propose→commit span chains from node0, same stream as the "
            f"dump_flight_recorder RPC): {cps:.1f} commits/sec; median block "
            f"{rec['block_ms']:.1f} ms = propose {rec['propose_ms']:.1f} ms "
            f"(proposal + rarest-first part bursts on event wakeups) + "
            f"prevote {rec['prevote_ms']:.1f} ms + precommit "
            f"{rec['precommit_ms']:.1f} ms (vote rounds: event-driven "
            f"vote_batch gossip — wakeups bound latency, not the "
            f"peer-gossip tick; serial C host verify, batches of 4 < "
            f"min_device_batch) + commit→next-height "
            f"{rec['commit_ms']:.1f} ms (block exec/store + new-height "
            f"turnaround). Sparse-regime adaptive vote-flush hop measures "
            f"{hop_ms:.2f} ms, over {procs.get('blocks', '?')} blocks in "
            f"{procs.get('measure_s', '?')} s with {os.cpu_count()} cores."
        )
    cps = procs.get("commits_per_sec", 0) or 0.001
    block_ms = 1000.0 / cps
    return (
        "[estimate: flight-recorder dump unavailable] "
        f"4-val procs: {cps:.1f} commits/sec = {block_ms:.1f} ms/block on "
        f"{os.cpu_count()} cores. "
        f"Consensus timeouts contribute ~0 (skip_timeout_commit, timeout_commit=0). "
        f"Per block: proposal + parts + 2 vote rounds ride the 5 ms "
        f"peer-gossip quantum (~3 hops of latency floor), votes verify on "
        f"the serial C host path (~0.15 ms/sig; batches of 4 are below "
        f"min_device_batch, so the rig runs engine-off — an idle engine's "
        f"warmup compiles stole cores from co-located nodes), and the "
        f"sparse-regime adaptive flush hop measures {hop_ms:.2f} ms "
        f"(vs 2 ms fixed-quantum before). The remainder is block "
        f"exec/store (live-path validator set reused; the O(height) "
        f"proposer-priority replay per block is gone) and msgpack "
        f"encode/decode per peer hop, measured over "
        f"{procs.get('blocks', '?')} blocks in {procs.get('measure_s', '?')} s "
        f"with 4 interpreters sharing this host's cores."
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="small-batch regression tripwire: primary engine numbers only "
        "(2k batch, no e2e nets), asserts host-prep and correctness budgets",
    )
    args = ap.parse_args()

    if args.smoke:
        primary = bench_primary(n_vals=2048)
        out = {
            "metric": "bench_smoke",
            "value": round(primary["sigs_per_sec"], 1),
            "unit": "sigs/sec",
            "host_prep_ms_2k": round(primary["host_prep_ms"], 2),
            "host_prep_fused_c": primary["host_prep_fused_c"],
            "dispatch_rtt_ms": round(primary["dispatch_rtt_ms"], 3),
            "chunked_auto_selected": primary["chunked_auto_selected"],
            "single_shot_latency_ms": round(primary["single_shot_latency_ms"], 2),
            "vote_hop_flush_ms": round(asyncio.run(bench_vote_hop_flush()), 3),
        }
        print(json.dumps(out))
        # tripwire: fused prep must stay under the 10k budget pro-rated
        # (15 ms / 10k = 3.1 ms at 2048) with headroom for CI-host noise
        if primary["host_prep_fused_c"]:
            assert primary["host_prep_ms"] < 8.0, (
                f"host prep regressed: {primary['host_prep_ms']:.2f} ms at 2048 sigs"
            )
        return

    primary = bench_primary()
    hop_ms = asyncio.run(bench_vote_hop_flush())
    try:
        procs = bench_e2e_4val_procs()
    except Exception as e:  # the rig must not sink the whole bench report
        procs = {"commits_per_sec": -1.0, "error": str(e)[:300]}
    try:
        statesync = bench_statesync_bootstrap()
    except Exception as e:
        statesync = {"statesync_bootstrap_ms": -1.0, "error": str(e)[:300]}
    try:
        chaos = bench_chaos_recovery()
    except Exception as e:
        chaos = {"chaos_partition_recovery_ms": -1.0, "error": str(e)[:300]}
    try:
        disk = bench_disk()
    except Exception as e:
        disk = {"disk_fault_recovery_ms": -1.0, "error": str(e)[:300]}
    try:
        scale = bench_scale_100val()
    except Exception as e:
        scale = {"e2e_commits_per_sec_100val": -1.0, "error": str(e)[:300]}
    try:
        load = bench_load()
    except Exception as e:
        load = {"tx_ingress_sustained_tps": -1.0, "error": str(e)[:300]}
    try:
        mesh = bench_mesh_scaling()
    except Exception as e:
        mesh = {"sharded_sigs_per_sec": -1.0, "error": str(e)[:300]}
    try:
        rotation = bench_rotation()
    except Exception as e:
        rotation = {"valset_update_latency_ms": -1.0, "error": str(e)[:300]}
    try:
        forensics = bench_forensics()
    except Exception as e:
        forensics = {"crash_bundle_completeness": -1.0, "error": str(e)[:300]}
    try:
        finality = bench_finality()
    except Exception as e:
        finality = {"commit_to_commit_p50_ms": -1.0, "error": str(e)[:300]}
    try:
        lite = bench_lite()
    except Exception as e:
        lite = {"lite_bisections_per_sec": -1.0, "error": str(e)[:300]}
    extras = {
        "commit_verify_100val_ms": bench_100val_commit(),
        "e2e_commits_per_sec_solo": asyncio.run(bench_e2e_commits()),
        "e2e_commits_per_sec_4val": asyncio.run(bench_e2e_4val()),
        "vote_ingest_100val_ms": asyncio.run(bench_vote_ingest_100val()),
        "lite2_bisection_100val_20h_ms": asyncio.run(bench_lite2()),
        "sr25519_verify_ms": bench_sr25519(),
        "multisig_7of10_verify_ms": bench_multisig(),
    }
    try:
        bls = bench_bls()
    except Exception as e:
        bls = {"bls_agg_verify_ms": -1.0, "error": str(e)[:300]}
    out = {
        "metric": "batched_ed25519_sigs_per_sec_per_chip",
        "value": round(primary["sigs_per_sec"], 1),
        "unit": "sigs/sec",
        "vs_baseline": round(primary["vs_baseline"], 2),
        "method": "steady-state pipelined (K=10, fetch-last); single-shot latency separate",
        "batch_ms_per_10k_commit": round(primary["batch_ms_per_10k_commit"], 2),
        "single_shot_latency_ms": round(primary["single_shot_latency_ms"], 2),
        "single_shot_monolithic_ms": round(primary["single_shot_monolithic_ms"], 2),
        "single_shot_chunked_ms": round(primary["single_shot_chunked_ms"], 2),
        "chunked_auto_selected": primary["chunked_auto_selected"],
        "dispatch_rtt_ms": round(primary["dispatch_rtt_ms"], 3),
        "prep_ms_per_chunk": round(primary["prep_ms_per_chunk"], 2),
        "steady_device_ms": round(primary["steady_device_ms"], 2),
        "host_prep_ms": round(primary["host_prep_ms"], 2),
        "host_prep_fused_c": primary["host_prep_fused_c"],
        "host_serial_sigs_per_sec": round(primary["host_serial_sigs_per_sec"], 1),
        "table_build_ms": round(primary["table_build_ms"], 1),
        "verify_shards": mesh.get("verify_shards"),
        "sharded_sigs_per_sec": mesh.get("sharded_sigs_per_sec", -1.0),
        "mesh_scaling_ratio": mesh.get("mesh_scaling_ratio", -1.0),
        "mesh_speedup_x": mesh.get("mesh_speedup_x"),
        "live_node_sharded_path": mesh.get("live_node_sharded_path"),
        "e2e_commits_per_sec_4val_procs": round(procs.get("commits_per_sec", -1.0), 2),
        "e2e_4val_procs_startup_s": procs.get("startup_s"),
        "statesync_bootstrap_ms": statesync.get("statesync_bootstrap_ms", -1.0),
        "statesync_bootstrap_wall_s": statesync.get("bootstrap_wall_s"),
        "tx_ingress_sustained_tps": load.get("tx_ingress_sustained_tps", -1.0),
        "commit_latency_under_load_ms": load.get("commit_latency_under_load_ms", -1.0),
        "load_offered_tps": load.get("offered_tps"),
        "load_throttled": load.get("throttled"),
        "load_idle_commits_per_sec": load.get("idle_commits_per_sec"),
        "load_recovery_commits_per_sec": load.get("recovery_commits_per_sec"),
        "lite_bisections_per_sec": lite.get("lite_bisections_per_sec", -1.0),
        "lite_cache_hit_ratio": lite.get("lite_cache_hit_ratio", -1.0),
        "lite_verify_coalesce_ratio": lite.get("lite_verify_coalesce_ratio"),
        "lite_sessions_sustained": lite.get("lite_sessions_sustained", -1),
        "lite_diverged_detect_ms": lite.get("lite_diverged_detect_ms", -1.0),
        "commit_to_commit_p50_ms": finality.get("commit_to_commit_p50_ms", -1.0),
        "commit_to_commit_p90_ms": finality.get("commit_to_commit_p90_ms", -1.0),
        "commit_to_commit_p50_ms_serial": finality.get("commit_to_commit_p50_ms_serial"),
        "finality_under_load_p50_ms": finality.get("finality_under_load_p50_ms", -1.0),
        "finality_budget_pipelined": finality.get("budget_pipelined"),
        "finality_budget_serial": finality.get("budget_serial"),
        "valset_update_latency_ms": rotation.get("valset_update_latency_ms", -1.0),
        "bls_migration_height_gap": rotation.get("bls_migration_height_gap", -1),
        "lite2_skip_across_rotation_ok": rotation.get(
            "lite2_skip_across_rotation_ok", False
        ),
        "rotation_epoch_observed": rotation.get("epoch_rotation_observed"),
        "rotation_table_rebuild_events": rotation.get("table_rebuild_events"),
        "chaos_partition_recovery_ms": chaos.get("chaos_partition_recovery_ms", -1.0),
        "chaos_restart_recovery_ms": chaos.get("restart_recovery_ms"),
        "chaos_evidence_height": chaos.get("evidence_height"),
        "disk_fault_recovery_ms": disk.get("disk_fault_recovery_ms", -1.0),
        "store_integrity_scan_ms": disk.get("store_integrity_scan_ms", -1.0),
        "enospc_recovery_ms": disk.get("enospc_recovery_ms"),
        "disk_scan_checked": disk.get("scan_checked"),
        "crash_bundle_completeness": forensics.get("crash_bundle_completeness", -1.0),
        "health_detect_latency_ms": forensics.get("health_detect_latency_ms", -1.0),
        "health_clear_ms": forensics.get("health_clear_ms"),
        "forensics_spool_events": forensics.get("spool_events"),
        "e2e_commits_per_sec_100val": scale.get("e2e_commits_per_sec_100val", -1.0),
        "scale_100val_block_ms": scale.get("block_ms"),
        "scale_100val_startup_s": scale.get("startup_s"),
        "scale_100val_engine_device_path": scale.get("engine_device_path"),
        "scale_100val_gossip": scale.get("gossip"),
        "loop_lag_ms_p90_100val": scale.get("loop_lag_ms_p90_100val", -1.0),
        "block_attribution_100val": scale.get("block_attribution_100val"),
        "commit_skew_ms_100val": scale.get("commit_skew_ms_100val", -1.0),
        "part_coverage_ms_p90_100val": scale.get("part_coverage_ms_p90_100val"),
        "trace_net_4val": (procs.get("trace_net") or {}) and {
            k: procs["trace_net"].get(k)
            for k in ("heights", "commit_skew_ms_p90", "failures")
        },
        "chaos_partition_recovery_ms_100val": scale.get(
            "chaos_partition_recovery_ms_100val"
        ),
        "vote_hop_flush_ms": round(hop_ms, 3),
        **bls,
        "e2e_4val_recorder": procs.get("recorder"),
        "e2e_4val_breakdown": _e2e_breakdown(procs, hop_ms),
        **{k: round(v, 2) for k, v in extras.items()},
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
