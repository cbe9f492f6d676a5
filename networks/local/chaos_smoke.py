#!/usr/bin/env python
"""Chaos smoke: the scripted partition/kill/twin scenario against a real
4-validator multi-process localnet — the `make chaos-smoke` acceptance rig.

Scenario (seeded; the SAME seed replays the SAME fault timeline — the
script parses it twice and asserts identical fingerprints):

    twin 0                       node0 double-signs prevotes from genesis
    partition 0,1|2,3 @2~0.5     no side has +2/3 -> commits MUST stop
    heal @8~0.5                  commits must resume within the bound
    kill 2 @11                   SIGKILL mid-consensus (sqlite: durable)
    restart 2 @13                crash recovery + catchup via gossip

Faults are staged through each node's config-gated `unsafe_chaos_*` RPC
routes (partition = drop=1.0 set symmetrically on both sides' outbound
links) and OS signals; the invariant checker (chaos/checker.py — the same
code the in-process tier-1 tests use) scrapes `/status` and `/blockchain`
from every node each poll and accumulates violations:

  - agreement: no two nodes ever commit different hashes at one height
  - no height regression per node (sqlite backend: strict across restart)
  - commits stop during the partition (a "partition" that doesn't stall
    a 2|2 split means the fault layer isn't injecting)
  - commits resume within --recovery-bound after heal AND after restart
  - accountability: the twin's DuplicateVoteEvidence is committed into a
    block AND surfaces via BeginBlock byzantine_validators (the kvstore
    app records delivered addresses under the `__byzantine__` key)
  - self-diagnosis (libs/watchdog.py): every non-twin node's /health must
    be alarm-free through the pre-partition quiet phase (zero false
    alarms), the consensus_stall alarm must FIRE on a non-twin node while
    the partition holds (`health_detect_latency_ms`), and by the end of
    the recovery budget every live non-twin node must have CLEARED it —
    the node noticed the fault and noticed the recovery, by itself.
    (The twin is exempt: it reference-correctly halts on its own
    conflict, and its stall alarm firing is the watchdog being right.)

  - byzantine trace context: the twin forges a huge hop count and a
    far-future origin timestamp on its equivocation frames; at least one
    honest receiver must CLAMP them (gossip.hop `clamped`, counted via
    watermarked polls during the run) — forged wire trace fields are
    never trusted into skew estimation

With --json the last stdout line carries `chaos_partition_recovery_ms`
(heal -> first new commit, wall ms) — the number bench.py reports.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.parse
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import tendermint_tpu.store  # noqa: E402,F401 — registers BlockMeta with the codec
import tendermint_tpu.types  # noqa: E402,F401 — registers Block/evidence types
from tendermint_tpu.chaos.checker import InvariantChecker, RecoveryTimer  # noqa: E402
from tendermint_tpu.chaos.scenario import Scenario  # noqa: E402
from tendermint_tpu.rpc.jsonrpc import from_jsonable  # noqa: E402

SCENARIO = """
twin 0
partition 0,1|2,3 @2~0.5
heal @8~0.5
kill 2 @11
restart 2 @13
"""


def rpc(port: int, path: str, timeout: float = 3.0):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/{path}", timeout=timeout) as r:
        return json.load(r)


def rpc_call(port: int, method: str, **params):
    qs = urllib.parse.urlencode({k: str(v) for k, v in params.items()})
    return rpc(port, f"{method}?{qs}" if qs else method)


def height_of(port: int):
    try:
        return int(rpc(port, "status")["result"]["sync_info"]["latest_block_height"])
    except Exception:
        return None


def spawn(home: str, env) -> subprocess.Popen:
    log = open(os.path.join(home, "node.log"), "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cli", "--home", home, "node"],
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--build-dir", default="./build-chaos")
    ap.add_argument("--base-port", type=int, default=30656)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--recovery-bound", type=float, default=30.0,
                    help="max seconds from heal/restart to the next commit")
    ap.add_argument("--budget", type=float, default=90.0,
                    help="seconds after the last fault for evidence + recovery")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    # determinism gate: same text + seed => same resolved timeline
    scenario = Scenario.parse(SCENARIO, seed=args.seed)
    assert scenario.fingerprint() == Scenario.parse(SCENARIO, seed=args.seed).fingerprint(), \
        "scenario resolution is not deterministic"
    timeline = scenario.timeline()
    print(f"scenario fingerprint {scenario.fingerprint()[:16]} (seed {args.seed}):")
    for ev in timeline:
        print(f"  {ev.describe()}")

    build = os.path.abspath(args.build_dir)
    if os.path.isdir(build):
        shutil.rmtree(build)
    subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "testnet",
         "--validators", "4", "--output", build, "--base-port", str(args.base_port),
         "--fast", "--db-backend", "sqlite",
         "--chaos", "--chaos-seed", str(args.seed), "--twin", "0"],
        check=True, cwd=REPO,
    )
    homes = [os.path.join(build, f"node{i}") for i in range(4)]
    ports = [args.base_port + 10 * i + 1 for i in range(4)]

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [spawn(h, env) for h in homes]

    checker = InvariantChecker(4, liveness_exempt=[0])  # twin halts by design
    # heal recovery = first NEW commit anywhere (tip advance);
    # restart recovery = every live non-twin node past the pre-restart tip
    heal_timer = RecoveryTimer()
    restart_timer = RecoveryTimer()
    result = {}
    ok = False
    try:
        # readiness: every RPC answers; every NON-TWIN node commits ≥ 1
        # (the twin may reference-correctly halt within its first heights)
        deadline = time.time() + 120.0
        while time.time() < deadline:
            hs = [height_of(p) for p in ports]
            if all(h is not None for h in hs) and all(h >= 1 for h in hs[1:]):
                break
            if any(p.poll() is not None for p in procs):
                print("a node died during startup", file=sys.stderr)
                return 1
            time.sleep(0.5)
        else:
            print(f"startup timeout: heights {[height_of(p) for p in ports]}",
                  file=sys.stderr)
            return 1
        node_ids = [rpc(p, "status")["result"]["node_info"]["id"] for p in ports]
        twin_addr = from_jsonable(
            rpc(ports[0], "status")["result"]["validator_info"]["address"]
        )
        print(f"localnet ready, heights {[height_of(p) for p in ports]}; "
              f"twin addr {twin_addr.hex()[:12]}")

        live = [True] * 4

        # watchdog observation state: quiet -> partition -> post_heal
        hstate = {
            "phase": "quiet",
            "t_partition": None,
            "detect_t": None,
            "quiet_alarms": set(),
            "clear_t": None,
        }

        def health_of(port):
            try:
                return rpc(port, "health")["result"]
            except Exception:
                return None

        def poll_health():
            """Non-twin /health sampling: quiet-phase alarms are false
            positives; the first consensus_stall during the partition is
            the detection landmark; all-clear is tracked for the end."""
            stall_free = True
            for i, p in enumerate(ports):
                if i == 0 or not live[i]:
                    continue
                h = health_of(p)
                if h is None:
                    stall_free = False  # unreachable ≠ clear
                    continue
                alarms = set(h.get("alarms", {}))
                if hstate["phase"] == "quiet" and alarms:
                    hstate["quiet_alarms"].update(f"node{i}:{a}" for a in alarms)
                if (
                    hstate["phase"] == "partition"
                    and hstate["detect_t"] is None
                    and "consensus_stall" in alarms
                ):
                    hstate["detect_t"] = time.time()
                    print(
                        f"  watchdog: node{i} raised consensus_stall "
                        f"{hstate['detect_t'] - hstate['t_partition']:.1f}s "
                        f"after the partition"
                    )
                if "consensus_stall" in alarms:
                    stall_free = False
            return stall_free

        # wire-level trace forensics: the twin forges byzantine trace
        # context (huge hop count, far-future origin timestamp) on its
        # equivocation frames; honest receivers must CLAMP and count, never
        # trust it into skew estimation.  Polled watermarked DURING the run
        # (throttled) so ring eviction can't hide early forgeries.
        trace_state = {"wm": {}, "clamps": 0, "hops": 0, "last_t": 0.0}

        def poll_trace_clamps():
            if time.time() - trace_state["last_t"] < 2.0:
                return
            trace_state["last_t"] = time.time()
            for i, p in enumerate(ports):
                if i == 0 or not live[i]:
                    continue
                try:
                    snap = rpc_call(
                        p, "dump_flight_recorder",
                        since=trace_state["wm"].get(i, 0), kinds="gossip.hop",
                    )["result"]
                except Exception:
                    continue
                trace_state["wm"][i] = snap.get(
                    "next_seq", trace_state["wm"].get(i, 0)
                )
                evs = snap.get("events", [])
                trace_state["hops"] += len(evs)
                trace_state["clamps"] += sum(
                    1 for ev in evs if ev.get("clamped")
                )

        def scrape():
            hs = []
            for i, p in enumerate(ports):
                h = height_of(p)
                hs.append(h)
                checker.observe_height(i, h)
                if h is None or h < 1:
                    continue
                try:
                    metas = from_jsonable(
                        rpc(p, f"blockchain?min_height={max(1, h - 19)}&max_height={h}")
                        ["result"]
                    )["block_metas"]
                except Exception:
                    continue
                for meta in metas:
                    checker.observe_block_hash(i, meta.header.height, meta.block_id.hash)
            known = [h for h in hs if h is not None]
            if known:
                heal_timer.observe(max(known))
            live_non_twin = [h for j, h in enumerate(hs)
                             if j != 0 and live[j] and h is not None]
            if live_non_twin and all(
                live[j] and hs[j] is not None for j in range(1, 4)
            ):
                restart_timer.observe(min(live_non_twin))

        def tip_of(idxs):
            """Max known height over the given node indices; falls back to
            the checker's last observations so a poll where every RPC
            times out (loaded CI box) degrades instead of crashing."""
            known = [h for h in (height_of(ports[i]) for i in idxs) if h is not None]
            if known:
                return max(known)
            seen = [checker.last_height.get(i) for i in idxs]
            return max((h for h in seen if h is not None), default=1)

        # -- execute the timeline, scraping between events ------------------
        t0 = time.time()
        stall_window = None  # (t_start, max_height_at_start)
        for ev in timeline:
            while time.time() < t0 + ev.t:
                scrape()
                poll_health()
                poll_trace_clamps()
                time.sleep(0.4)
            print(f"+{time.time() - t0:6.2f}s executing {ev.describe()}")
            if ev.action == "twin":
                continue  # config-installed from genesis
            if ev.action == "partition":
                groups = ev.args["groups"]
                for gi, g1 in enumerate(groups):
                    for g2 in groups[gi + 1:]:
                        for a in g1:
                            for b in g2:
                                rpc_call(ports[a], "unsafe_chaos_link",
                                         peer_id=node_ids[b], drop=1.0)
                                rpc_call(ports[b], "unsafe_chaos_link",
                                         peer_id=node_ids[a], drop=1.0)
                time.sleep(1.0)  # drain in-flight gossip
                stall_window = (time.time(), tip_of(range(4)))
                hstate["phase"] = "partition"
                hstate["t_partition"] = time.time()
            elif ev.action == "heal":
                # the stall assertion: a 2|2 split has no +2/3 side, so at
                # most one in-flight height may have landed since the cut
                if stall_window is not None:
                    tip = tip_of(range(4))
                    if tip > stall_window[1] + 1:
                        checker.violations.append(
                            f"commits continued during partition: "
                            f"{stall_window[1]} -> {tip}"
                        )
                    print(f"  partition stalled the net at ~{stall_window[1]} "
                          f"for {time.time() - stall_window[0]:.1f}s (tip {tip})")
                # detection must have happened while the cut still held
                if hstate["detect_t"] is None:
                    poll_health()  # one last chance at the boundary
                hstate["phase"] = "post_heal"
                baseline = tip_of(range(4))
                for i, p in enumerate(ports):
                    if live[i]:
                        rpc_call(p, "unsafe_chaos_heal")
                heal_timer.mark("heal", baseline)
            elif ev.action == "kill":
                i = ev.args["node"]
                procs[i].send_signal(signal.SIGKILL)
                procs[i].wait(10)
                live[i] = False
            elif ev.action == "restart":
                i = ev.args["node"]
                baseline = tip_of([j for j in range(1, 4) if live[j]])
                procs[i] = spawn(homes[i], env)
                live[i] = True
                restart_timer.mark("restart", baseline)

        # -- recovery + accountability within the budget --------------------
        evidence_height = None
        byz_delivered = False
        deadline = time.time() + args.budget
        while time.time() < deadline:
            scrape()
            poll_trace_clamps()
            if poll_health() and hstate["clear_t"] is None:
                hstate["clear_t"] = time.time()
                print(f"  watchdog: consensus_stall clear on every live "
                      f"non-twin node at +{time.time() - t0:.1f}s")
            if evidence_height is None:
                tip = height_of(ports[1]) or 0
                for h in range(1, tip + 1):
                    try:
                        blk = from_jsonable(
                            rpc(ports[1], f"block?height={h}")["result"]
                        )["block"]
                    except Exception:
                        continue
                    if blk is not None and blk.evidence:
                        assert blk.evidence[0].address() == twin_addr, \
                            "committed evidence names the wrong validator"
                        evidence_height = h
                        break
            if not byz_delivered:
                try:
                    res = rpc_call(ports[1], "abci_query", data='"__byzantine__"')
                    val = from_jsonable(res["result"]["response"]).get("value") or b""
                    byz_delivered = twin_addr.hex().encode() in val
                except Exception:
                    pass
            if (not heal_timer.unrecovered() and not restart_timer.unrecovered()
                    and evidence_height is not None and byz_delivered
                    and hstate["clear_t"] is not None):
                break
            time.sleep(0.4)

        detect_ms = (
            round((hstate["detect_t"] - hstate["t_partition"]) * 1000, 1)
            if hstate["detect_t"] is not None and hstate["t_partition"] is not None
            else -1.0
        )
        result = {
            "metric": "chaos_smoke",
            "fingerprint": scenario.fingerprint(),
            "seed": args.seed,
            "chaos_partition_recovery_ms": round(heal_timer.recovery_ms.get("heal", -1.0), 1),
            "restart_recovery_ms": round(restart_timer.recovery_ms.get("restart", -1.0), 1),
            "health_detect_latency_ms": detect_ms,
            "health_quiet_alarms": sorted(hstate["quiet_alarms"]),
            "health_stall_cleared": hstate["clear_t"] is not None,
            "evidence_height": evidence_height,
            "byzantine_validators_delivered": byz_delivered,
            "heights": [height_of(p) for p in ports],
            "twin_equivocations": rpc(ports[0], "unsafe_chaos_status")
            ["result"]["equivocations"],
            "trace_clamps": trace_state["clamps"],
            "gossip_hop_events": trace_state["hops"],
            **checker.summary(),
        }
        failures = []
        if checker.violations:
            failures.append(f"invariant violations: {checker.violations}")
        for name, tmr in (("heal", heal_timer), ("restart", restart_timer)):
            ms = tmr.recovery_ms.get(name)
            if ms is None:
                failures.append(f"net never recovered after {name}")
            elif ms > args.recovery_bound * 1000:
                failures.append(f"{name} recovery {ms:.0f}ms exceeds bound")
        if evidence_height is None:
            failures.append("twin evidence never committed into a block")
        if not byz_delivered:
            failures.append("byzantine_validators never delivered via BeginBlock")
        if len(checker.agreed_heights()) < 3:
            failures.append("too few heights cross-checked for agreement")
        if hstate["detect_t"] is None:
            failures.append(
                "watchdog never raised consensus_stall during the partition"
            )
        if hstate["quiet_alarms"]:
            failures.append(
                f"watchdog false alarms during the quiet phase: "
                f"{sorted(hstate['quiet_alarms'])}"
            )
        if hstate["clear_t"] is None:
            failures.append(
                "watchdog consensus_stall never cleared on every live "
                "non-twin node after recovery"
            )
        if trace_state["clamps"] < 1:
            failures.append(
                "no clamped trace context observed: the twin's forged "
                "hop/origin fields were either not sent or TRUSTED by a "
                "receiver"
            )
        if failures:
            print("CHAOS SMOKE FAILED:", file=sys.stderr)
            for f in failures:
                print(f"  - {f}", file=sys.stderr)
        else:
            print(
                f"chaos smoke ok: agreement over "
                f"{len(checker.agreed_heights())} heights, heal recovery "
                f"{result['chaos_partition_recovery_ms']:.0f} ms, restart "
                f"recovery {result['restart_recovery_ms']:.0f} ms, stall "
                f"alarm in {result['health_detect_latency_ms']:.0f} ms "
                f"(0 false alarms, cleared after heal), twin evidence "
                f"committed at height {evidence_height} and delivered "
                f"via BeginBlock"
            )
            ok = True
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()
    if args.json and result:
        print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
