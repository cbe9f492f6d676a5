#!/usr/bin/env python
"""Scale smoke: a measured 100-validator live net — the `make scale-smoke`
acceptance rig for the relay gossip topology and maj23 vote aggregation.

This is the first time BASELINE config #2 (100-validator live net) commits
blocks at all: 100 full nodes in ONE process (own switches, real TCP
loopback connections, the verify engine ON), wired in a chordal-ring peer
topology (offsets 1, 2, 4, ... — degree O(log N), diameter O(log N))
instead of a 4950-connection full mesh.  Vote gossip rides the relay
topology (`consensus.gossip_relay_degree`) and the maj23 summary/pull
aggregation — full-mesh per-vote chatter is exactly what wedged this
configuration before (O(N²) frames per round, arXiv:2302.00418's fan-out
wall).

Phases:

  1. throughput — the net must commit >= --blocks CONSECUTIVE heights with
     every node agreeing; `e2e_commits_per_sec_100val` is measured between
     the first and last of those commits (min height across all nodes, so
     a straggler counts).  Gossip wakeup / batch-size / summary / pull
     stats are aggregated from the nodes' flight recorders.
  2. chaos — a 50|50 partition (via each node's LinkPolicyTable) must
     STALL the net (no side has +2/3), heal must recover within
     --recovery-bound, and the PR 5 invariant checker (agreement, no
     height regression) must pass over every node's block store with zero
     violations.

Engine routing is probed, not assumed: with an accelerator attached the
vote batches ride the device kernel; on a CPU-only host the engine's own
min_device_batch routing sends batches to the threaded C host tier
(device dispatch on 2-core CPU XLA is seconds per call — measured, not
guessed).  The JSON reports which path ran (`engine_device_path`).

With --json the last stdout line carries `e2e_commits_per_sec_100val` —
the number bench.py reports.
"""

import argparse
import asyncio
import json
import os
import resource
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)


def _raise_fd_limit() -> None:
    """~7 chordal connections per node × N nodes × 2 ends plus stores —
    the default 1024 soft limit is the first thing a 100-node process
    trips."""
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))


def chordal_offsets(n: int):
    offsets, k = [], 1
    while k < n:
        offsets.append(k)
        k *= 2
    return offsets


async def build_net(tmp: str, args, cpu_only: bool):
    from tendermint_tpu.config import test_config as make_test_cfg
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV
    from tendermint_tpu.types.params import BlockParams, ConsensusParams

    n = args.validators
    pvs = sorted([MockPV() for _ in range(n)], key=lambda pv: pv.address())
    gen = GenesisDoc(
        chain_id=f"scale-{n}val",
        genesis_time_ns=time.time_ns(),
        validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs],
        consensus_params=ConsensusParams(block=BlockParams(time_iota_ms=1)),
    )
    nodes = []
    for i, pv in enumerate(pvs):
        cfg = make_test_cfg(os.path.join(tmp, f"n{i}"))
        cfg.rpc.laddr = ""
        cfg.base.db_backend = "memdb"
        cfg.p2p.laddr = "127.0.0.1:0"
        cfg.p2p.max_num_inbound_peers = n + 8
        cfg.p2p.max_num_outbound_peers = max(10, len(chordal_offsets(n)))
        # the chordal wiring IS the topology under test — PEX would top
        # every node back up toward a full mesh and un-measure the relay
        cfg.p2p.pex = False
        # 64-way parallel dialing on a 2-core box: the 3 s default dial
        # timeout fails healthy handshakes under the storm
        cfg.p2p.dial_timeout = 30.0
        # batched frames should ride few packets: the 1 KiB reference
        # default fragments every vote_batch into a packb+seal+drain round
        # per KiB (the cap is the reference's own 64 KiB)
        cfg.p2p.max_packet_msg_payload_size = 32768
        # verify engine ON — the acceptance condition.  On a CPU-only host
        # route batches to the engine's threaded C host tier (its own
        # min_device_batch mechanism); with a chip attached, ride it.
        cfg.tpu.enabled = True
        if cpu_only:
            cfg.tpu.min_device_batch = 1 << 30
        # consensus starts DORMANT behind fastsync and is released onto the
        # formed mesh (see build_net) — a coordinated launch.  Without the
        # gate, 100 consensus instances churn rounds against a half-built
        # mesh and the dial storm never completes (measured: conns dying of
        # pong timeouts under the loop backlog).
        cfg.base.fast_sync = True
        # Python-scale timing: a block's vote aggregation takes tens of
        # seconds at N=100 on a shared 2-core interpreter, and nodes ENTER
        # each height spread over the commit-propagation tail.  Unlike the
        # small-net throughput rigs, timeout_commit must NOT be zeroed:
        # it is the reference's round-start aligner, and without it early
        # committers burn timeout_propose before the slow majority arrives
        # and every height >= 2 decays into nil-prevote round churn
        # (measured: pv=100/pc=92-mostly-nil -> round 1, repeatedly).
        # Vote timeouts cover the aggregation tail so a mixed nil/block
        # wave doesn't nil-cascade; the happy path never waits on them.
        cfg.consensus.timeout_propose = 15.0
        cfg.consensus.timeout_propose_delta = 3.0
        cfg.consensus.timeout_prevote = 10.0
        cfg.consensus.timeout_prevote_delta = 2.0
        cfg.consensus.timeout_precommit = 10.0
        cfg.consensus.timeout_precommit_delta = 2.0
        cfg.consensus.timeout_commit = 15.0
        cfg.consensus.skip_timeout_commit = False
        cfg.consensus.peer_gossip_sleep_duration = 1.0
        cfg.consensus.peer_query_maj23_sleep_duration = 5.0
        cfg.consensus.gossip_relay_degree = args.relay_degree
        # engage the relay whenever there are more peers than the degree —
        # the chordal wiring already bounds the peer set, so the default
        # full-mesh floor (12) would leave the topology untested
        cfg.consensus.gossip_relay_min_peers = args.relay_degree
        cfg.consensus.gossip_relay_debounce = args.debounce
        cfg.consensus.gossip_vote_summary = not args.no_summary
        # scheduler profiler: the first-started node owns the process-wide
        # task/GC accounting hooks (one loop, one GC — libs/loopprof.py);
        # every node still runs its own lag probe.  1 s probes keep 100
        # probe tasks negligible on an already-saturated loop, and the
        # high-rate gossip kinds are sampled 1-in-N so the ring survives a
        # full multi-minute block interval instead of evicting it.
        cfg.instrumentation.loop_probe_interval = args.probe_interval
        cfg.instrumentation.trace_sample_high_rate = args.trace_sample
        # 100 per-node watchdog tickers would add 50+ wakeups/sec to an
        # already loop-bound rig (the exact class PR 6 trimmed); the
        # checker judges this net from outside
        cfg.instrumentation.watchdog = False
        cfg.chaos.enabled = True
        cfg.chaos.seed = args.seed
        nodes.append(Node(cfg, gen, priv_validator=pv, db_backend="memdb"))

    # Coordinated launch: hold every node's consensus dormant behind the
    # fastsync gate while the mesh forms (the caught-up handover interval
    # is raised for the window, then restored — the same
    # statesync→fastsync→consensus machinery a bootstrapping node rides).
    from tendermint_tpu.fastsync import reactor as fs_reactor

    orig_interval = fs_reactor.SWITCH_TO_CONSENSUS_INTERVAL
    fs_reactor.SWITCH_TO_CONSENSUS_INTERVAL = 3600.0
    t0 = time.perf_counter()
    try:
        for node in nodes:
            await node.start()
        # chordal ring: i dials i+1, i+2, i+4, ... (mod n), batched —
        # the loop is quiet (consensus gated), so dials converge fast
        offsets = chordal_offsets(n)

        def edges():
            for i in range(n):
                for off in offsets:
                    j = (i + off) % n
                    yield i, j

        for attempt in range(4):  # re-dial edges that lost the storm
            dials = [
                (i, f"{nodes[j].node_key.id}@{nodes[j].switch.transport.listen_addr}")
                for i, j in edges()
                if nodes[j].node_key.id not in nodes[i].switch.peers
            ]
            if not dials:
                break
            for k in range(0, len(dials), 32):
                await asyncio.gather(
                    *(nodes[i].switch.dial_peer(addr) for i, addr in dials[k : k + 32]),
                    return_exceptions=True,
                )
            await asyncio.sleep(1.0)
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if all(node.switch.num_peers() >= len(offsets) for node in nodes):
                break
            await asyncio.sleep(0.2)
        else:
            raise RuntimeError(
                "peer mesh never converged: "
                f"{sorted(node.switch.num_peers() for node in nodes)[:5]}..."
            )
    finally:
        # release: every fastsync reactor sees itself caught up on its next
        # pass and hands over to consensus on the formed mesh
        fs_reactor.SWITCH_TO_CONSENSUS_INTERVAL = orig_interval
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        if all(
            node.consensus is not None and node.consensus.is_running for node in nodes
        ):
            break
        await asyncio.sleep(0.2)
    else:
        held = sum(
            1 for node in nodes if node.consensus is None or not node.consensus.is_running
        )
        raise RuntimeError(f"{held} nodes never switched fastsync→consensus")
    return nodes, time.perf_counter() - t0


def gossip_stats(nodes) -> dict:
    """Aggregate relay/aggregation telemetry from every node's flight
    recorder — the same stream `trace` and the RPC dump serve."""
    wakeups = summaries = pulls = pulled_votes = 0
    batch_sizes = []
    single = 0
    for node in nodes:
        for e in node.flight_recorder.events():
            k = e["kind"]
            if k == "gossip.wakeup":
                # high-rate kind: stored 1-in-N with the factor recorded
                wakeups += e.get("sampled", 1)
            elif k == "gossip.summary":
                summaries += 1
            elif k == "gossip.pull_serve":
                pulls += 1
                pulled_votes += e.get("n", 0)
            elif k == "gossip.votes":
                if e.get("mode") == "batch":
                    batch_sizes.append(e.get("n", 0))
                else:
                    single += 1
    batch_sizes.sort()
    return {
        "wakeups": wakeups,
        "vote_batches": len(batch_sizes),
        "vote_batch_mean": (
            round(sum(batch_sizes) / len(batch_sizes), 2) if batch_sizes else 0
        ),
        "vote_batch_p90": batch_sizes[int(len(batch_sizes) * 0.9)] if batch_sizes else 0,
        "single_vote_frames": single,
        "summaries": summaries,
        "pulls_served": pulls,
        "votes_pulled": pulled_votes,
    }


def profile_net(nodes, dump_dir: str = "") -> dict:
    """The measured answer to "where do the 60 s/block actually go":
    snapshot every node's flight recorder, align them onto one wall
    timeline (libs/tracemerge.py), and decompose each block interval into
    loop-task / GC / loop-lag / idle shares.  On this ONE-process rig the
    first-started node's profiler owns the process-wide spawn/GC hooks,
    so its attribution is the process attribution — the replacement for
    the old "Python-loop-bound" narrative.  Dumps optionally land in
    `dump_dir` (one JSON per node) for offline `trace-net` runs."""
    from tendermint_tpu.libs import tracemerge, tracing

    dumps = []
    for i, node in enumerate(nodes):
        snap = node.flight_recorder.snapshot()
        snap["node"] = f"n{i}"
        dumps.append(snap)
    if dump_dir:
        os.makedirs(dump_dir, exist_ok=True)
        for d in dumps:
            with open(os.path.join(dump_dir, f"{d['node']}.json"), "w") as fh:
                json.dump(d, fh)
    out = {}
    lags = sorted(
        node.loop_profiler.lag_p90_ms()
        for node in nodes
        if node.loop_profiler is not None
    )
    if lags:
        out["loop_lag_ms_p90_100val"] = round(lags[len(lags) // 2], 1)
        out["loop_lag_ms_max"] = round(
            max(node.loop_profiler.lag_max_ms for node in nodes
                if node.loop_profiler is not None), 1)
    merged = tracemerge.merge(dumps)
    out["commit_skew_ms_100val"] = merged["commit_skew_ms_p90"]
    out["part_coverage_ms_p90_100val"] = merged["coverage_ms_p90"]
    # how many nodes got MEASURED (wire trace context) rather than
    # landmark-estimated clock alignment in the merge
    out["measured_skew_nodes"] = sum(
        1 for s in merged.get("offset_sources", []) if s == "measured"
    )
    # cross-node net budget from one receiver's events (the stages are
    # per-receiver by construction; any non-proposer-biased node works)
    netb = tracing.net_budget(dumps[0]["events"]) if dumps else None
    if netb:
        out["net_budget"] = netb
        st = netb["stages"]
        out["vote_fanin_ms"] = st.get("vote_fanin", {}).get("p50_ms", -1.0)
        out["part_stream_ms"] = st.get("part_stream", {}).get("p50_ms", -1.0)
        out["gossip_hop_p90_ms"] = netb.get("hop_lat_all_ms", {}).get("p90", -1.0)
        print("net " + tracing.format_net_budget(netb).replace("\n", "\n  "),
              flush=True)
    att = None
    for d in dumps:  # only the hook-owning node carries loop.busy events
        att = tracemerge.median_attribution(tracemerge.attribution_by_height(d))
        if att:
            break
    out["block_attribution_100val"] = att
    slow = tracemerge.slowest_height(merged)
    if slow is not None:
        print(
            f"slowest block on the merged network timeline (height {slow}):",
            flush=True,
        )
        print(tracemerge.format_timeline(merged, [slow]), flush=True)
    if att:
        shares = " ".join(
            f"{k[:-4]}={v}%" for k, v in sorted(att.items()) if k.endswith("_pct")
        )
        print(f"block attribution (median % of block wall time): {shares}", flush=True)
    return out


async def run(args) -> dict:
    import jax

    from tendermint_tpu.chaos import InProcRig, InvariantChecker, RecoveryTimer, Scenario, ScenarioRunner

    cpu_only = all(d.platform == "cpu" for d in jax.devices())
    n = args.validators
    result = {
        "metric": "scale_smoke",
        "validators": n,
        "relay_degree": args.relay_degree,
        "engine_device_path": not cpu_only,
        "failures": [],
    }
    with tempfile.TemporaryDirectory() as tmp:
        nodes, startup_s = await build_net(tmp, args, cpu_only)
        result["startup_s"] = round(startup_s, 1)
        result["peers_per_node"] = round(
            sum(node.switch.num_peers() for node in nodes) / n, 1
        )
        print(
            f"net up: {n} validators, ~{result['peers_per_node']} peers/node, "
            f"startup {startup_s:.1f}s, engine "
            f"{'device' if not cpu_only else 'host-tier (CPU-only box)'}",
            flush=True,
        )
        try:
            # -- phase 1: consecutive commits + measured rate --------------
            def min_height():
                return min(node.block_store.height() for node in nodes)

            deadline = time.monotonic() + args.budget
            t_first = time.monotonic()
            while min_height() < 1 and time.monotonic() < deadline:
                await asyncio.sleep(0.5)
                if time.monotonic() - t_first > 30:
                    hs = sorted(node.block_store.height() for node in nodes)
                    print(
                        f"waiting for first commit everywhere: heights "
                        f"min/med/max={hs[0]}/{hs[len(hs) // 2]}/{hs[-1]}",
                        flush=True,
                    )
                    t_first = time.monotonic()
            h0 = min_height()
            if h0 < 1:
                heights = sorted(node.block_store.height() for node in nodes)
                result["failures"].append(f"no first commit within budget: {heights}")
                return result
            t0 = time.monotonic()
            target = h0 + args.blocks
            last_log = 0.0
            while min_height() < target and time.monotonic() < deadline:
                h = min_height()
                if time.monotonic() - last_log > 10:
                    print(f"+{time.monotonic() - t0:6.1f}s height {h}/{target}", flush=True)
                    last_log = time.monotonic()
                await asyncio.sleep(0.25)
            h1 = min_height()
            elapsed = time.monotonic() - t0
            cps = (h1 - h0) / elapsed if elapsed > 0 else 0.0
            result["blocks_committed"] = h1 - h0
            result["e2e_commits_per_sec_100val"] = round(cps, 3)
            result["block_ms"] = round(1000.0 / cps, 1) if cps > 0 else -1
            result["gossip"] = gossip_stats(nodes)
            if h1 < target:
                result["failures"].append(
                    f"only {h1 - h0}/{args.blocks} consecutive blocks within budget"
                )
            print(
                f"committed {h1 - h0} blocks in {elapsed:.1f}s = {cps:.2f} "
                f"commits/sec; gossip {result['gossip']}",
                flush=True,
            )
            # profiler + cross-node trace surface, BEFORE chaos so the
            # partition doesn't pollute the block attribution
            result.update(profile_net(nodes, args.dump_recorders))

            # every height h0..h1 must exist on every node and agree
            checker = InvariantChecker(n)
            for i, node in enumerate(nodes):
                checker.observe_node(i, node)
            agreed = checker.agreed_heights()
            if len([h for h in agreed if h0 <= h <= h1]) < min(args.blocks, h1 - h0):
                result["failures"].append(
                    f"agreement coverage too thin: {len(agreed)} heights cross-checked"
                )

            # -- phase 2: partition/heal chaos at scale --------------------
            if not args.skip_chaos:
                rig = InProcRig(nodes)
                half = n // 2
                text = (
                    "partition "
                    + ",".join(str(i) for i in range(half))
                    + "|"
                    + ",".join(str(i) for i in range(half, n))
                    + " @0"
                )
                scenario = Scenario.parse(text, seed=args.seed)
                result["scenario_fingerprint"] = scenario.fingerprint()[:16]
                await ScenarioRunner(scenario, rig).run()
                print("partitioned 50|50; waiting for stall...", flush=True)
                await asyncio.sleep(2.0)  # drain in-flight gossip
                stall_h = max(node.block_store.height() for node in nodes)
                # one block-time of silence (capped) is proof enough of a
                # stall at multi-minute block cadences
                await asyncio.sleep(
                    min(150.0, max(4.0, 1.2 * result.get("block_ms", 4000) / 1000.0))
                )
                tip = max(node.block_store.height() for node in nodes)
                if tip > stall_h + 1:
                    result["failures"].append(
                        f"commits continued across a 50|50 partition: {stall_h} -> {tip}"
                    )
                else:
                    print(f"partition stalled the net at ~{stall_h}", flush=True)
                for i, node in enumerate(nodes):
                    checker.observe_node(i, node)

                timer = RecoveryTimer()
                timer.mark("heal", min_height())
                await rig.heal()
                heal_deadline = time.monotonic() + args.recovery_bound
                while time.monotonic() < heal_deadline:
                    timer.observe(min_height())
                    if "heal" in timer.recovery_ms:
                        break
                    await asyncio.sleep(0.5)
                ms = timer.recovery_ms.get("heal")
                result["chaos_partition_recovery_ms_100val"] = (
                    round(ms, 1) if ms is not None else -1.0
                )
                if ms is None:
                    result["failures"].append(
                        f"net never recovered within {args.recovery_bound}s of heal"
                    )
                else:
                    print(f"healed; first new commit after {ms:.0f} ms", flush=True)
                for i, node in enumerate(nodes):
                    checker.observe_node(i, node)

            result["agreed_heights"] = len(checker.agreed_heights())
            result["max_height"] = max(node.block_store.height() for node in nodes)
            if checker.violations:
                result["failures"].append(f"invariant violations: {checker.violations}")
            result["violations"] = list(checker.violations)
        finally:
            stop_t0 = time.perf_counter()
            for i in range(0, len(nodes), 10):
                await asyncio.gather(
                    *(node.stop() for node in nodes[i : i + 10] if node.is_running),
                    return_exceptions=True,
                )
            print(f"net stopped in {time.perf_counter() - stop_t0:.1f}s", flush=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--validators", type=int, default=100)
    ap.add_argument("--blocks", type=int, default=10,
                    help="consecutive commits required (and the measure window)")
    ap.add_argument("--relay-degree", type=int, default=6)
    ap.add_argument("--debounce", type=float, default=0.25,
                    help="vote-coalescing linger per relay wakeup (seconds); "
                         "larger windows = fewer, bigger frames (a 2-core "
                         "box stalls in a tiny-frame flood below ~0.25)")
    ap.add_argument("--no-summary", action="store_true",
                    help="disable maj23 aggregation (A/B comparisons)")
    ap.add_argument("--probe-interval", type=float, default=1.0,
                    help="scheduler-profiler probe tick (seconds)")
    ap.add_argument("--trace-sample", type=int, default=8,
                    help="1-in-N sampling for high-rate recorder kinds "
                         "(gossip.wakeup) so the ring survives a block interval")
    ap.add_argument("--dump-recorders", default="",
                    help="directory to write every node's recorder dump "
                         "(one JSON per node, trace-net input)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--budget", type=float, default=2200.0,
                    help="seconds for startup-to-last-commit of phase 1 "
                         "(a 2-core CPU box runs ~2-3 min/block at N=100; "
                         "multi-core/TPU hosts are far faster)")
    ap.add_argument("--recovery-bound", type=float, default=420.0)
    ap.add_argument("--skip-chaos", action="store_true")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    _raise_fd_limit()
    result = asyncio.run(run(args))
    failures = result.pop("failures", [])
    if failures:
        print("SCALE SMOKE FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
    else:
        print(
            f"scale smoke ok: {result['validators']} validators, "
            f"{result.get('blocks_committed', 0)} consecutive commits at "
            f"{result.get('e2e_commits_per_sec_100val', 0)} commits/sec, "
            f"agreement over {result.get('agreed_heights', 0)} heights, "
            f"loop lag p90 {result.get('loop_lag_ms_p90_100val', '?')} ms, "
            f"commit skew p90 {result.get('commit_skew_ms_100val', '?')} ms, "
            f"heal recovery {result.get('chaos_partition_recovery_ms_100val', 'skipped')} ms"
        )
    if args.json:
        result["ok"] = not failures
        print(json.dumps(result))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
