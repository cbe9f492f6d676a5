#!/usr/bin/env python
"""Rotation smoke: dynamic validator sets driven end-to-end through the
staking app — the `make rotation-smoke` acceptance rig for PR "dynamic
validator sets".

A 7-node in-process net starts with 4 genesis validators (distinct powers)
running the staking ABCI app with epoch rotation enabled, then lives
through every set transition the subsystem promises, all via REAL signed
stake txs (no backdoor set surgery):

  1. growth     — three non-validators bond in (one directly via the rig,
                  two through the scenario DSL's new `valset` clauses);
                  `valset_update_latency_ms` is measured tx-submit →
                  set-effective.
  2. chaos      — one joiner is a configured TwinSigner: it starts
                  equivocating the moment it becomes a validator (a twin
                  ACROSS a set change), halts reference-correctly, and its
                  DuplicateVoteEvidence must land in a committed block.  A
                  partition across the set change + heal rides the same
                  scenario.
  3. epochs     — the staking app's epoch barrel-shift must change the
                  power assignment with ZERO client traffic.
  4. migration  — after the halted twin is voted out (stake tx signed with
                  its owner key, submitted through a live node), every
                  remaining validator live-rotates ed25519 → BLS12-381.
                  Aggregation must ENGAGE (stored commits become ONE
                  aggregate signature + bitmap; `bls_migration_height_gap`
                  = uniformity → first AggregateCommit) and then DISENGAGE
                  when one validator rotates back to ed25519.
  5. bootstrap  — a fresh node fastsyncs from genesis ACROSS the rotated/
                  mixed/aggregated history (catchup commits authenticated
                  against historical sets), and a lite2 client bisects from
                  a height-2 trust root to the tip over every set change
                  (`lite2_skip_across_rotation_ok`).
  6. judgement  — the chaos invariant checker (agreement, no height
                  regression; twin liveness-exempt) must report ZERO
                  violations, and the engine's set-rebuild pipeline must
                  have provably fired (`valset.update` +
                  `verify.table_rebuild` recorder events).

Engine note: the verify engine is ON (`tpu.enabled`); on a CPU-only host
`min_device_batch` routes batches to the threaded C host tier exactly like
scale_smoke, which keeps TableCache alive so set changes exercise the
rebuild path cheaply.

With --json the last stdout line carries `valset_update_latency_ms`,
`bls_migration_height_gap` and `lite2_skip_across_rotation_ok` — the
numbers bench.py's bench_rotation reports.
"""

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

# node roles (indices into the rig's node list)
GENESIS_VALS = [0, 1, 2, 3]
JOINER_A = 5          # bonds in directly via the rig (latency measurement)
JOINER_B = 6          # bonds in via the scenario DSL
TWIN = 4              # configured double-signer; bonds in via the DSL
FRESH = 7             # fastsync bootstrapper over the rotated history
GENESIS_POWERS = [10, 20, 30, 40]


def _node_cfg(tmp: str, i: int, args, cpu_only: bool):
    from tendermint_tpu.config import test_config as make_test_cfg

    cfg = make_test_cfg(os.path.join(tmp, f"n{i}"))
    cfg.rpc.laddr = ""
    cfg.base.db_backend = "memdb"
    cfg.base.proxy_app = "staking"
    cfg.p2p.laddr = "127.0.0.1:0"
    cfg.p2p.pex = False
    cfg.p2p.dial_timeout = 20.0
    cfg.p2p.max_num_inbound_peers = 16
    cfg.p2p.max_num_outbound_peers = 16
    # verify engine ON — set changes must hit the TableCache rebuild path.
    # CPU-only hosts route batches to the threaded C host tier via the
    # engine's own min_device_batch mechanism (the scale_smoke idiom).
    cfg.tpu.enabled = True
    if cpu_only:
        cfg.tpu.min_device_batch = 1 << 30
    cfg.chaos.enabled = True
    cfg.chaos.seed = args.seed
    if i == TWIN:
        cfg.chaos.twin = True
    # pace blocks at a steady few per second: heights must advance (epoch
    # boundaries, evidence inclusion) but the run spans minutes of wall
    # time and an unpaced empty-block net would pile up thousands of
    # heights for the fastsync/lite2 phases to chew through
    cfg.consensus.timeout_commit = args.block_pace
    cfg.consensus.skip_timeout_commit = False
    cfg.base.fast_sync = True  # coordinated launch gate (see build_net)
    cfg.instrumentation.watchdog = False
    # table rebuilds only fire while the set is all-ed25519, i.e. in the
    # first half of the run; the BLS/fastsync/lite2 phases emit enough
    # gossip+verify events afterwards to cycle the default 8192-slot ring
    # and evict them before the final judgement count — keep the whole run
    cfg.instrumentation.flight_recorder_size = 1 << 17
    return cfg


async def build_net(tmp: str, args, cpu_only: bool):
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV, RotatingPV
    from tendermint_tpu.types.params import BlockParams, ConsensusParams
    from tendermint_tpu.crypto.bls.keys import BlsPrivKey

    # Every migratable node holds a RotatingPV: candidate 0 is its ed25519
    # identity (the pre-migration signer AND the stake-tx owner key),
    # candidate 1 its BLS12-381 one.  The twin keeps a plain MockPV —
    # TwinSigner wraps a single raw key — and therefore never migrates.
    pvs = []
    for i in range(7):
        if i == TWIN:
            pvs.append(MockPV())
        else:
            pvs.append(RotatingPV(MockPV(), MockPV(BlsPrivKey.generate())))
    # sort the genesis validators by address so node index order matches
    # validator set order for the first 4 (log readability only)
    genesis_pvs = sorted(pvs[:4], key=lambda pv: pv.address())
    pvs[:4] = genesis_pvs

    gen = GenesisDoc(
        chain_id="rotation-smoke",
        genesis_time_ns=time.time_ns(),
        validators=[
            GenesisValidator(pv.address(), pv.get_pub_key(), power)
            for pv, power in zip(genesis_pvs, GENESIS_POWERS)
        ],
        consensus_params=ConsensusParams(block=BlockParams(time_iota_ms=1)),
        app_state={"staking": {"epoch_length": args.epoch}},
    )

    nodes = [
        Node(_node_cfg(tmp, i, args, cpu_only), gen, priv_validator=pvs[i], db_backend="memdb")
        for i in range(7)
    ]

    # coordinated launch behind the fastsync gate while the mesh forms
    from tendermint_tpu.fastsync import reactor as fs_reactor

    orig_interval = fs_reactor.SWITCH_TO_CONSENSUS_INTERVAL
    fs_reactor.SWITCH_TO_CONSENSUS_INTERVAL = 3600.0
    t0 = time.perf_counter()
    try:
        for node in nodes:
            await node.start()
        for attempt in range(4):
            # dial one direction only (i < j): simultaneous mutual dials
            # collide as duplicate connections and both get dropped
            dials = [
                (i, f"{nodes[j].node_key.id}@{nodes[j].switch.transport.listen_addr}")
                for i in range(7)
                for j in range(i + 1, 7)
                if nodes[j].node_key.id not in nodes[i].switch.peers
            ]
            if not dials:
                break
            await asyncio.gather(
                *(nodes[i].switch.dial_peer(addr) for i, addr in dials),
                return_exceptions=True,
            )
            await asyncio.sleep(0.5)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if all(node.switch.num_peers() >= 6 for node in nodes):
                break
            await asyncio.sleep(0.2)
        else:
            raise RuntimeError(
                f"mesh never converged: {[n.switch.num_peers() for n in nodes]}"
            )
    finally:
        fs_reactor.SWITCH_TO_CONSENSUS_INTERVAL = orig_interval
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if all(n.consensus is not None and n.consensus.is_running for n in nodes):
            break
        await asyncio.sleep(0.2)
    else:
        raise RuntimeError("nodes never switched fastsync→consensus")
    return nodes, gen, time.perf_counter() - t0


def _ed_addr(pv) -> bytes:
    """The node's ed25519 identity address (RotatingPV candidate 0 /
    MockPV), independent of which key is currently active."""
    cand = getattr(pv, "candidates", None)
    return (cand[0] if cand else pv).get_pub_key().address()


def _bls_addr(pv) -> bytes:
    for cand in getattr(pv, "candidates", []):
        if getattr(cand.get_pub_key(), "TYPE", "") == "tendermint/PubKeyBLS12381":
            return cand.get_pub_key().address()
    raise RuntimeError("node has no BLS candidate key")


def _val_set(node):
    """The CURRENT consensus validator set from the canonical store."""
    return node.state_store.load().validators


def _powers_by_addr(vset) -> dict:
    return {v.address.hex(): v.voting_power for v in vset.validators}


async def _wait_for(predicate, budget: float, what: str, tick: float = 0.1):
    deadline = time.monotonic() + budget
    while time.monotonic() < deadline:
        if predicate():
            return True
        await asyncio.sleep(tick)
    raise TimeoutError(f"timed out after {budget:.0f}s waiting for {what}")


def _tip(nodes) -> int:
    return max(n.block_store.height() for n in nodes)


async def _mesh_keeper(nodes, interval: float = 2.0):
    """Redial any dropped links (one direction, i < j).  pex is off, so a
    connection killed by a transient error (overload drop, decode error
    during the fastsync→consensus switch race) never heals on its own and
    strands a follower at height 0.  Partitions are message-drop policies
    on LIVE links keyed by peer id, so redialing never bypasses them."""
    while True:
        await asyncio.sleep(interval)
        dials = []
        for i, a in enumerate(nodes):
            if not a.is_running:
                continue
            for j in range(i + 1, len(nodes)):
                b = nodes[j]
                if not b.is_running or b.node_key.id in a.switch.peers:
                    continue
                dials.append(
                    a.switch.dial_peer(
                        f"{b.node_key.id}@{b.switch.transport.listen_addr}"
                    )
                )
        if dials:
            await asyncio.gather(*dials, return_exceptions=True)


def recorder_counts(nodes) -> dict:
    valset_updates = rebuilds = rebuild_ok = 0
    for node in nodes:
        for e in node.flight_recorder.events():
            if e["kind"] == "valset.update":
                valset_updates += 1
            elif e["kind"] == "verify.table_rebuild":
                rebuilds += 1
                rebuild_ok += 1 if e.get("ok") else 0
    return {
        "valset_update_events": valset_updates,
        "table_rebuild_events": rebuilds,
        "table_rebuild_ok_events": rebuild_ok,
    }


async def run(args) -> dict:
    import jax

    from tendermint_tpu.chaos import InProcRig, InvariantChecker, Scenario, ScenarioRunner
    from tendermint_tpu.chaos.checker import scan_committed_evidence
    from tendermint_tpu.types import Commit
    from tendermint_tpu.types.agg_commit import AggregateCommit
    from tendermint_tpu.types.evidence import DuplicateVoteEvidence

    cpu_only = all(d.platform == "cpu" for d in jax.devices())
    result = {
        "metric": "rotation_smoke",
        "engine_device_path": not cpu_only,
        "epoch_length": args.epoch,
        "failures": [],
    }
    with tempfile.TemporaryDirectory() as tmp:
        nodes, gen, startup_s = await build_net(tmp, args, cpu_only)
        result["startup_s"] = round(startup_s, 1)
        pvs = [n.priv_validator for n in nodes]
        # the twin's privval is wrapped in TwinSigner by Node; its identity
        # is still the inner ed25519 key
        ed_addrs = [_ed_addr(pv) for pv in pvs]
        print(
            f"net up: 4 genesis validators + 3 followers, startup {startup_s:.1f}s, "
            f"engine {'device' if not cpu_only else 'host-tier (CPU-only box)'}",
            flush=True,
        )
        fresh_node = None
        keeper_nodes = list(nodes)
        keeper = asyncio.ensure_future(_mesh_keeper(keeper_nodes))
        try:
            # -- phase 1: base chain, then measured growth ----------------
            await _wait_for(
                lambda: min(n.block_store.height() for n in nodes) >= 3,
                args.budget, "3 base commits everywhere",
            )
            rig = InProcRig(nodes)

            t_join = time.monotonic()
            await rig.valset("join", JOINER_A, power=15)
            addr_a = ed_addrs[JOINER_A]
            await _wait_for(
                lambda: _val_set(nodes[0]).has_address(addr_a),
                args.budget, f"node {JOINER_A} joining the set",
            )
            result["valset_update_latency_ms"] = round(
                (time.monotonic() - t_join) * 1000.0, 1
            )
            print(
                f"node {JOINER_A} bonded in: set effective after "
                f"{result['valset_update_latency_ms']} ms",
                flush=True,
            )

            # -- phase 2: scenario DSL — joins + partition + twin ---------
            # The twin bonds in MID-SCENARIO (a set change), equivocates on
            # its first own prevote, and halts.  The partition spans the
            # set change; the power edit lands after heal.  25 is absent
            # from the initial power multiset {10,20,30,40,15,10,5}, so its
            # appearance proves the edit applied (the epoch barrel-shift
            # permutes powers but preserves the multiset).
            text = "\n".join(
                [
                    f"valset join {JOINER_B} power=10 @0",
                    f"valset join {TWIN} power=5 @3",
                    f"partition {TWIN},{JOINER_A}|0,1,2,3,{JOINER_B} @6",
                    "heal @12",
                    "valset power 1=25 @15",
                ]
            )
            scenario = Scenario.parse(text, seed=args.seed)
            result["scenario_fingerprint"] = scenario.fingerprint()[:16]
            await ScenarioRunner(scenario, rig).run()
            addr_b, addr_twin = ed_addrs[JOINER_B], ed_addrs[TWIN]
            await _wait_for(
                lambda: (
                    _val_set(nodes[0]).has_address(addr_b)
                    and _val_set(nodes[0]).has_address(addr_twin)
                    and 25 in _powers_by_addr(_val_set(nodes[0])).values()
                ),
                args.budget, "DSL joins + power edit effective",
            )
            result["set_size_after_growth"] = _val_set(nodes[0]).size()
            if result["set_size_after_growth"] != 7:
                result["failures"].append(
                    f"expected 7 validators after growth, got {result['set_size_after_growth']}"
                )
            print(
                f"scenario done: set grew to {result['set_size_after_growth']} "
                f"across a partition; twin armed",
                flush=True,
            )

            # -- phase 3: twin accountability -----------------------------
            def _twin_evidence():
                for h, ev in scan_committed_evidence(nodes[0].block_store, max_back=500):
                    if isinstance(ev, DuplicateVoteEvidence) and (
                        ev.vote_a.validator_address == addr_twin
                    ):
                        result["twin_evidence_height"] = h
                        return True
                return False

            try:
                await _wait_for(
                    _twin_evidence, args.budget, "twin DuplicateVoteEvidence committed"
                )
                result["twin_evidence_committed"] = True
                print(
                    f"twin evidence committed at height {result['twin_evidence_height']}",
                    flush=True,
                )
            except TimeoutError as e:
                result["twin_evidence_committed"] = False
                result["failures"].append(str(e))

            # -- phase 4: epoch barrel-shift (zero client traffic) --------
            before = _powers_by_addr(_val_set(nodes[0]))
            h_before = nodes[0].state_store.load().last_block_height
            next_epoch = ((h_before // args.epoch) + 1) * args.epoch
            await _wait_for(
                lambda: nodes[0].state_store.load().last_block_height >= next_epoch + 3,
                args.budget, f"epoch boundary {next_epoch} + 2 to pass",
            )
            after = _powers_by_addr(_val_set(nodes[0]))
            rotated = set(before) == set(after) and before != after
            result["epoch_rotation_observed"] = rotated
            if not rotated:
                result["failures"].append(
                    f"epoch boundary {next_epoch} did not rotate powers: "
                    f"{before} -> {after}"
                )
            else:
                print(f"epoch barrel-shift observed at boundary {next_epoch}", flush=True)

            # -- phase 5: vote the halted twin out ------------------------
            # stake tx signed with the twin's OWNER key (extracted through
            # TwinSigner), submitted through a live node's mempool
            await rig.valset("leave", TWIN)
            await _wait_for(
                lambda: not _val_set(nodes[0]).has_address(addr_twin),
                args.budget, "twin leaving the set",
            )
            result["set_size_after_leave"] = _val_set(nodes[0]).size()
            print("halted twin voted out of the set", flush=True)

            # snapshot recorder counts while the all-ed25519 rebuild events
            # are still in the rings; the final count takes the max so the
            # verdict survives even if later traffic cycles them out
            counts_mid = recorder_counts(nodes)

            # -- phase 6: live ed25519 -> BLS migration -------------------
            migrators = [i for i in (GENESIS_VALS + [JOINER_A, JOINER_B])]
            for i in migrators:
                await rig.valset("migrate", i, scheme="bls12381")
                bi, ei = _bls_addr(pvs[i]), ed_addrs[i]
                await _wait_for(
                    lambda: (
                        _val_set(nodes[0]).has_address(bi)
                        and not _val_set(nodes[0]).has_address(ei)
                    ),
                    args.budget, f"node {i} migrating to bls12381",
                )
                print(f"node {i} migrated to BLS (set stayed live)", flush=True)
            h_uniform = nodes[0].state_store.load().last_block_height
            result["bls_uniform_height"] = h_uniform

            # aggregation must ENGAGE: a stored commit above uniformity
            # becomes ONE aggregate signature + signer bitmap
            agg_h = {"h": 0}

            def _agg_engaged():
                bs = nodes[0].block_store
                for h in range(h_uniform, bs.height() + 1):
                    c = bs.load_block_commit(h)
                    if isinstance(c, AggregateCommit):
                        agg_h["h"] = h
                        return True
                return False

            await _wait_for(_agg_engaged, args.budget, "BLS aggregation to engage")
            result["agg_engaged_height"] = agg_h["h"]
            result["bls_migration_height_gap"] = agg_h["h"] - h_uniform
            c = nodes[0].block_store.load_block_commit(agg_h["h"])
            if len(c.agg_sig) != 96:
                result["failures"].append(
                    f"aggregate commit at {agg_h['h']} has a {len(c.agg_sig)}-byte sig"
                )
            print(
                f"aggregation ENGAGED at height {agg_h['h']} "
                f"(gap {result['bls_migration_height_gap']} from uniformity)",
                flush=True,
            )

            # ...and DISENGAGE when one validator rotates back to ed25519
            await rig.valset("migrate", 0, scheme="ed25519")
            await _wait_for(
                lambda: _val_set(nodes[0]).has_address(ed_addrs[0]),
                args.budget, "node 0 rotating back to ed25519",
            )
            h_mixed = nodes[0].state_store.load().last_block_height

            def _agg_disengaged():
                bs = nodes[0].block_store
                tip = bs.height()
                if tip < h_mixed + 3:
                    return False
                c = bs.load_block_commit(tip - 1)
                return isinstance(c, Commit) and not isinstance(c, AggregateCommit)

            await _wait_for(_agg_disengaged, args.budget, "aggregation to disengage")
            result["agg_disengaged"] = True
            print("node 0 back on ed25519: aggregation DISENGAGED (mixed set)", flush=True)

            # -- phase 7: fresh node fastsyncs the rotated history --------
            from tendermint_tpu.node import Node
            from tendermint_tpu.types import MockPV

            tip_at_join = _tip(nodes)
            cfg7 = _node_cfg(tmp, FRESH, args, cpu_only)
            cfg7.chaos.twin = False
            fresh_node = Node(cfg7, gen, priv_validator=MockPV(), db_backend="memdb")
            await fresh_node.start()
            keeper_nodes.append(fresh_node)  # mesh keeper heals its links too
            for j in range(7):
                if j == TWIN:
                    continue  # the halted twin serves nothing
                try:
                    await fresh_node.switch.dial_peer(
                        f"{nodes[j].node_key.id}@{nodes[j].switch.transport.listen_addr}"
                    )
                except Exception:
                    pass
            await _wait_for(
                lambda: fresh_node.block_store.height() >= tip_at_join,
                args.budget,
                f"fresh node fastsyncing {tip_at_join} rotated heights",
                tick=0.25,
            )
            result["fastsync_joiner_height"] = fresh_node.block_store.height()
            print(
                f"fresh node fastsynced to {result['fastsync_joiner_height']} "
                f"across every set change",
                flush=True,
            )

            # -- phase 8: lite2 bisection across every rotation -----------
            from tendermint_tpu.lite2 import BISECTION, Client, LocalProvider, TrustOptions

            root = nodes[0].block_store.load_block(2)
            lite_tip = nodes[0].block_store.height() - 1
            client = Client(
                gen.chain_id,
                TrustOptions(
                    period_ns=3600 * 1_000_000_000,
                    height=2,
                    hash=root.header.hash(),
                ),
                LocalProvider(nodes[0]),
                witnesses=[LocalProvider(nodes[1])],
                mode=BISECTION,
            )
            try:
                await client.initialize()
                sh = await client.verify_header_at_height(lite_tip, time.time_ns())
                ok = sh is not None and sh.height == lite_tip
                result["lite2_skip_across_rotation_ok"] = bool(ok)
                if not ok:
                    result["failures"].append("lite2 returned a bogus header")
                else:
                    print(
                        f"lite2 bisected height 2 -> {lite_tip} across the rotations",
                        flush=True,
                    )
            except Exception as e:
                result["lite2_skip_across_rotation_ok"] = False
                result["failures"].append(f"lite2 bisection failed: {e!r}")

            # -- phase 9: invariants + engine-rebuild proof ---------------
            checker = InvariantChecker(8, liveness_exempt=[TWIN])
            for i, node in enumerate(nodes):
                checker.observe_node(i, node)
            checker.observe_node(7, fresh_node)
            result["agreed_heights"] = len(checker.agreed_heights())
            result["max_height"] = _tip(nodes)
            if checker.violations:
                result["failures"].append(f"invariant violations: {checker.violations}")
            result["violations"] = list(checker.violations)

            counts_end = recorder_counts(nodes + [fresh_node])
            result.update(
                {k: max(counts_mid.get(k, 0), v) for k, v in counts_end.items()}
            )
            if result["valset_update_events"] == 0:
                result["failures"].append("no valset.update recorder events fired")
            if result["table_rebuild_events"] == 0:
                result["failures"].append(
                    "no verify.table_rebuild recorder events: the engine table "
                    "never rebuilt on a set change"
                )
        except (TimeoutError, RuntimeError) as e:
            result["failures"].append(str(e))
            result["heights_at_failure"] = [n.block_store.height() for n in nodes]
            result["peers_at_failure"] = [n.switch.num_peers() for n in nodes]
        finally:
            keeper.cancel()
            stopping = [n for n in nodes if n.is_running]
            if fresh_node is not None and fresh_node.is_running:
                stopping.append(fresh_node)
            await asyncio.gather(*(n.stop() for n in stopping), return_exceptions=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--epoch", type=int, default=16,
                    help="staking epoch length (heights between barrel-shifts)")
    ap.add_argument("--block-pace", type=float, default=0.25,
                    help="timeout_commit pacing (seconds/block floor)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--budget", type=float, default=120.0,
                    help="per-phase wait budget (seconds)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    result = asyncio.run(run(args))
    failures = result.pop("failures", [])
    if failures:
        print("ROTATION SMOKE FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
    else:
        print(
            f"rotation smoke ok: set 4→7→6 validators, valset latency "
            f"{result.get('valset_update_latency_ms', '?')} ms, epoch rotation "
            f"{'observed' if result.get('epoch_rotation_observed') else 'MISSING'}, "
            f"twin evidence at h={result.get('twin_evidence_height', '?')}, BLS "
            f"aggregation engaged at h={result.get('agg_engaged_height', '?')} "
            f"(gap {result.get('bls_migration_height_gap', '?')}) and disengaged, "
            f"fastsync to {result.get('fastsync_joiner_height', '?')}, lite2 "
            f"bisection {'ok' if result.get('lite2_skip_across_rotation_ok') else 'FAILED'}, "
            f"{result.get('valset_update_events', 0)} valset.update / "
            f"{result.get('table_rebuild_events', 0)} table_rebuild events, "
            f"0 violations"
        )
    if args.json:
        result["ok"] = not failures
        print(json.dumps(result))
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
