#!/usr/bin/env python
"""Disk smoke: storage-fault chaos against a real 4-validator multi-process
localnet — the `make disk-smoke` acceptance rig for ISSUE 15.

Scenario (seeded; parsed twice, identical fingerprints asserted):

    rot 3 blockstore h=3 @2     one byte of node3's stored block 3 rots
                                (persistent, via unsafe_chaos_rot)
    disk 2 enospc @8~0.5        every write on node2 returns ENOSPC
    disk 2 heal @16             the volume "recovers" (policy cleared)
    kill 2 @18                  the operator bounces the halted node
    restart 2 @20               crash recovery + catchup

What must hold (checker violations fail the rig):

  self-healing   node3's integrity scan (unsafe_store_integrity_scan)
                 DETECTS the rot, quarantines height 3, re-fetches the
                 block from peers through the fastsync channel, and ends
                 with `/block?height=3` serving a copy whose recomputed
                 hash matches the rest of the net — measured as
                 `disk_fault_recovery_ms` (rot -> verified refill);
                 `store_integrity_scan_ms` comes from the scan report
  clean halt     node2 under ENOSPC stops committing WITHOUT the
                 CONSENSUS FAILURE!!! banner (asserted against its log),
                 keeps answering `/status` and `/health` (the read path
                 stays up), and its watchdog raises the `disk_fault`
                 alarm as CRITICAL while the rest of the net keeps
                 committing (3 of 4 is +2/3)
  recovery       after heal + restart, node2 rejoins and commits past its
                 pre-fault tip inside --recovery-bound
                 (`enospc_recovery_ms`)
  integrity      every scraped `/block` body re-hashes to the meta hash
                 the node claims for it (observe_served_block) — a node
                 serving corrupted bytes as a valid block is a violation
  agreement      the standard checker invariants over every observation

With --json the last stdout line carries the measured numbers for
`bench.py bench_disk`.
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
rot 3 blockstore h=3 @2
disk 2 enospc @8~0.5
disk 2 heal @16
kill 2 @18
restart 2 @20
"""

ROT_HEIGHT = 3


def rpc(port: int, path: str, timeout: float = 5.0):
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


def health_of(port: int):
    try:
        return rpc(port, "health")["result"]
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
    ap.add_argument("--build-dir", default="./build-disk")
    ap.add_argument("--base-port", type=int, default=31656)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--recovery-bound", type=float, default=45.0,
                    help="max seconds for refill / restart recovery")
    ap.add_argument("--budget", type=float, default=90.0,
                    help="seconds after the last fault for recovery checks")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

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
         "--chaos", "--chaos-seed", str(args.seed)],
        check=True, cwd=REPO,
    )
    homes = [os.path.join(build, f"node{i}") for i in range(4)]
    ports = [args.base_port + 10 * i + 1 for i in range(4)]

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [spawn(h, env) for h in homes]

    checker = InvariantChecker(4)
    restart_timer = RecoveryTimer()
    result = {}
    ok = False
    live = [True] * 4
    try:
        # readiness: all four answer and pass the rot height
        deadline = time.time() + 120.0
        while time.time() < deadline:
            hs = [height_of(p) for p in ports]
            if all(h is not None and h >= ROT_HEIGHT + 1 for h in hs):
                break
            if any(p.poll() is not None for p in procs):
                print("a node died during startup", file=sys.stderr)
                return 1
            time.sleep(0.5)
        else:
            print(f"startup timeout: heights {[height_of(p) for p in ports]}",
                  file=sys.stderr)
            return 1
        print(f"localnet ready, heights {[height_of(p) for p in ports]}")

        state = {
            "scan_report": None,
            "rot_t": None,
            "refill_done_t": None,
            "rot_alarm_seen": False,
            "enospc_t": None,
            "halt_confirmed": False,
            "enospc_alarm_seen": False,
        }

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
                        rpc(p, f"blockchain?min_height={max(1, h - 9)}&max_height={h}")
                        ["result"]
                    )["block_metas"]
                except Exception:
                    continue
                for meta in metas:
                    checker.observe_block_hash(i, meta.header.height, meta.block_id.hash)
            known = [h for h in hs if h is not None]
            if known:
                restart_timer.observe(
                    min(h for j, h in enumerate(hs) if live[j] and h is not None)
                    if all(live[j] and hs[j] is not None for j in range(4))
                    else None
                )
            return hs

        def observe_served(i: int, height: int) -> bool:
            """Fetch the FULL block + the claimed meta hash; feed the
            served-corruption invariant.  Returns True when the node
            served a block for the height."""
            p = ports[i]
            try:
                blk = from_jsonable(rpc(p, f"block?height={height}")["result"])["block"]
                meta = from_jsonable(
                    rpc(p, f"blockchain?min_height={height}&max_height={height}")
                    ["result"]
                )["block_metas"]
            except Exception:
                return False
            if blk is None or not meta:
                return False
            checker.observe_served_block(
                i, height, meta[0].block_id.hash, blk.hash()
            )
            return True

        def poll_faults(now):
            # node3: refill completion = storage_info pending empty AND the
            # block is served again AND it re-hashes to the claimed meta
            if state["rot_t"] is not None and state["refill_done_t"] is None:
                try:
                    sinfo = rpc(ports[3], "storage_info")["result"]
                except Exception:
                    sinfo = None
                if sinfo is not None:
                    if not state["rot_alarm_seen"]:
                        h3 = health_of(ports[3])
                        if h3 and "disk_fault" in h3.get("alarms", {}):
                            state["rot_alarm_seen"] = True
                            print(f"  watchdog: node3 raised disk_fault on the rot")
                    pending = sinfo.get("refill", {}).get("pending", [])
                    quarantined = sinfo.get("blockstore", {}).get("quarantined", [])
                    if not pending and not quarantined and observe_served(3, ROT_HEIGHT):
                        state["refill_done_t"] = now
                        print(f"  node3 refilled height {ROT_HEIGHT} from peers "
                              f"({(now - state['rot_t']) * 1000:.0f} ms after rot)")
            # node2 under ENOSPC: read path must stay up, alarm critical,
            # no new commits
            if state["enospc_t"] is not None and not state["halt_confirmed"]:
                st = height_of(ports[2])
                h2 = health_of(ports[2])
                if st is not None and h2 is not None:
                    alarms = h2.get("alarms", {})
                    if ("disk_fault" in alarms
                            and alarms["disk_fault"]["severity"] == "critical"):
                        state["enospc_alarm_seen"] = True
                        state["halt_confirmed"] = True
                        print(f"  watchdog: node2 disk_fault CRITICAL with the "
                              f"read path still serving (/status answered {st})")

        # -- execute the timeline, scraping between events ------------------
        t0 = time.time()
        for ev in timeline:
            while time.time() < t0 + ev.t:
                scrape()
                poll_faults(time.time())
                time.sleep(0.4)
            print(f"+{time.time() - t0:6.2f}s executing {ev.describe()}")
            if ev.action == "rot":
                node = ev.args["node"]
                rpc_call(ports[node], "unsafe_chaos_rot", height=ev.args["height"])
                state["rot_t"] = time.time()
                # the debug-triggered integrity scan: detect + quarantine +
                # kick the peer refill
                report = rpc_call(ports[node], "unsafe_store_integrity_scan")["result"]
                state["scan_report"] = report
                print(f"  integrity scan: checked={report['checked']} "
                      f"corrupt={report['corrupt']} in {report['ms']} ms")
                if ev.args["height"] not in report["corrupt"]:
                    checker.violations.append(
                        f"integrity scan MISSED the injected rot at height "
                        f"{ev.args['height']}: {report}"
                    )
            elif ev.action == "disk":
                node = ev.args["node"]
                if ev.args["kind"] == "heal":
                    rpc_call(ports[node], "unsafe_chaos_disk", kind="heal",
                             store=ev.args["store"])
                else:
                    rpc_call(ports[node], "unsafe_chaos_disk",
                             kind=ev.args["kind"], store=ev.args["store"],
                             p=ev.args["p"])
                    state["enospc_t"] = time.time()
            elif ev.action == "kill":
                i = ev.args["node"]
                procs[i].send_signal(signal.SIGKILL)
                procs[i].wait(10)
                live[i] = False
            elif ev.action == "restart":
                i = ev.args["node"]
                baseline = max(
                    h for j, p in enumerate(ports) if live[j]
                    for h in [height_of(p)] if h is not None
                )
                procs[i] = spawn(homes[i], env)
                live[i] = True
                restart_timer.mark("restart", baseline)

        # -- recovery within the budget -------------------------------------
        deadline = time.time() + args.budget
        while time.time() < deadline:
            scrape()
            poll_faults(time.time())
            done = (
                state["refill_done_t"] is not None
                and "restart" in restart_timer.recovery_ms
            )
            if done:
                # node2 healthy again?
                h2 = health_of(ports[2])
                if h2 is not None and "disk_fault" not in h2.get("alarms", {}):
                    break
            time.sleep(0.4)

        # -- verdicts --------------------------------------------------------
        if state["scan_report"] is None:
            checker.violations.append("integrity scan never ran")
        if state["refill_done_t"] is None:
            checker.violations.append(
                f"quarantined block {ROT_HEIGHT} was never refilled from peers"
            )
        elif (state["refill_done_t"] - state["rot_t"]) > args.recovery_bound:
            checker.violations.append(
                f"refill took {state['refill_done_t'] - state['rot_t']:.1f}s "
                f"(bound {args.recovery_bound}s)"
            )
        if not state["enospc_alarm_seen"]:
            checker.violations.append(
                "node2 never raised a critical disk_fault alarm under ENOSPC"
            )
        if "restart" not in restart_timer.recovery_ms:
            checker.violations.append(
                "node2 never rejoined consensus after heal + restart"
            )
        # a clean halt never prints the consensus-failure banner
        log2 = open(os.path.join(homes[2], "node.log"), "rb").read()
        if b"CONSENSUS FAILURE" in log2:
            checker.violations.append(
                "node2 hit CONSENSUS FAILURE!!! under ENOSPC — the storage "
                "fault escaped the clean-halt path"
            )
        if b"consensus halted on storage fault" not in log2:
            checker.violations.append(
                "node2's log carries no attributed storage halt"
            )
        # final integrity pass over every live node's served blocks
        tip = min(h for h in (height_of(p) for p in ports) if h is not None)
        for i in range(4):
            for h in range(max(1, tip - 4), tip + 1):
                observe_served(i, h)

        checker.raise_if_violated()
        ok = True
        result = {
            "metric": "disk_smoke",
            "ok": True,
            "seed": args.seed,
            "fingerprint": scenario.fingerprint()[:16],
            "disk_fault_recovery_ms": round(
                (state["refill_done_t"] - state["rot_t"]) * 1000.0, 1
            ),
            "store_integrity_scan_ms": state["scan_report"]["ms"],
            "scan_checked": state["scan_report"]["checked"],
            "enospc_recovery_ms": round(restart_timer.recovery_ms["restart"], 1),
            "heights": [height_of(p) for p in ports],
            "heights_checked": len(checker.agreed_heights()),
        }
        print(f"disk smoke OK: refill {result['disk_fault_recovery_ms']} ms, "
              f"scan {result['store_integrity_scan_ms']} ms, "
              f"restart recovery {result['enospc_recovery_ms']} ms, "
              f"{result['heights_checked']} heights checked")
        return 0
    except AssertionError as e:
        print(f"INVARIANT VIOLATION:\n{e}", file=sys.stderr)
        return 1
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(15)
            except subprocess.TimeoutExpired:
                p.kill()
        if args.json:
            print(json.dumps(result if ok else {"metric": "disk_smoke", "ok": False}))


if __name__ == "__main__":
    sys.exit(main())
