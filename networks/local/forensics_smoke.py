#!/usr/bin/env python
"""Forensics smoke: crash-persistent black-box + live self-diagnosis
against a real 4-validator localnet — the `make forensics-smoke`
acceptance rig for the flight spool, the `debug dump` bundles and the
health watchdog.

Two acts:

  1. WATCHDOG, live.  After a quiet phase in which every node's /health
     must be alarm-free (zero false alarms), a 0,1|2,3 partition is
     staged through the chaos link layer: some node must raise the
     consensus_stall alarm while the cut holds
     (`health_detect_latency_ms` — injected fault to self-reported
     alarm), and after heal every node must CLEAR it within the recovery
     bound (`health_clear_ms`).

  2. FORENSICS, dead.  node3 is SIGKILLed mid-run — no signal handler,
     no atexit, nothing runs.  `tendermint_tpu debug dump --offline`
     then builds a bundle purely from its home directory, and the
     rig asserts the bundle's spool replay reconstructs a COMPLETE
     propose→prevote→precommit→commit span chain for every interior
     pre-crash height (`crash_bundle_completeness` = complete/interior,
     must be 1.0), that the watchdog's own health.alarm/health.clear
     events survived the crash inside the spool, and that the dead
     node's spool merges with a live node's RPC dump into one aligned
     causal timeline (tracemerge on a corpse).

With --json the last stdout line carries `crash_bundle_completeness` and
`health_detect_latency_ms` — the numbers bench.py reports.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tarfile
import time
import urllib.parse
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from tendermint_tpu.config import load_config, save_config  # noqa: E402
from tendermint_tpu.libs import tracemerge  # noqa: E402


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
    ap.add_argument("--build-dir", default="./build-forensics")
    ap.add_argument("--base-port", type=int, default=32656)
    ap.add_argument("--quiet", type=float, default=2.5,
                    help="seconds of alarm-free running required before faults")
    ap.add_argument("--detect-bound", type=float, default=20.0,
                    help="max seconds from partition to the stall alarm")
    ap.add_argument("--partition-hold", type=float, default=6.0,
                    help="minimum partition duration: every node's own "
                    "stall threshold must elapse so every spool carries "
                    "the health.alarm event")
    ap.add_argument("--recovery-bound", type=float, default=60.0,
                    help="max seconds from heal to commits resuming")
    ap.add_argument("--clear-bound", type=float, default=10.0,
                    help="max seconds from commits resuming to every node "
                    "clearing the stall alarm (watchdog tick latency, not "
                    "net re-mesh time)")
    ap.add_argument("--post-heal", type=float, default=4.0,
                    help="clean running time before the SIGKILL")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    build = os.path.abspath(args.build_dir)
    if os.path.isdir(build):
        shutil.rmtree(build)
    subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "testnet",
         "--validators", "4", "--output", build, "--base-port", str(args.base_port),
         "--fast", "--db-backend", "sqlite", "--chaos"],
        check=True, cwd=REPO,
    )
    homes = [os.path.join(build, f"node{i}") for i in range(4)]
    ports = [args.base_port + 10 * i + 1 for i in range(4)]

    # arm the forensics layer: the spool is opt-in, the rig is its proof
    for home in homes:
        path = os.path.join(home, "config", "config.toml")
        cfg = load_config(path, home=home)
        cfg.instrumentation.flight_spool = True
        cfg.instrumentation.flight_spool_flush_interval = 0.2
        cfg.instrumentation.flight_spool_size_limit = 16 * 1024 * 1024
        cfg.instrumentation.watchdog_interval = 0.25
        cfg.instrumentation.watchdog_stall_seconds = 2.5
        save_config(cfg, path)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [spawn(h, env) for h in homes]

    result = {}
    failures = []
    ok = False
    try:
        deadline = time.time() + 120.0
        while time.time() < deadline:
            hs = [height_of(p) for p in ports]
            if all(h is not None and h >= 1 for h in hs):
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
        print(f"localnet ready, heights {[height_of(p) for p in ports]}")

        # -- act 1: watchdog against an injected partition ------------------
        quiet_alarms = set()
        t_end = time.time() + args.quiet
        while time.time() < t_end:
            for i, p in enumerate(ports):
                h = health_of(p)
                if h is None:
                    continue
                quiet_alarms.update(f"node{i}:{a}" for a in h.get("alarms", {}))
            time.sleep(0.25)
        if quiet_alarms:
            failures.append(f"false alarms during the quiet phase: {sorted(quiet_alarms)}")

        print("staging 0,1|2,3 partition")
        for a, b in [(0, 2), (0, 3), (1, 2), (1, 3)]:
            rpc_call(ports[a], "unsafe_chaos_link", peer_id=node_ids[b], drop=1.0)
            rpc_call(ports[b], "unsafe_chaos_link", peer_id=node_ids[a], drop=1.0)
        t_part = time.time()
        detect_ms = None
        while time.time() < t_part + args.detect_bound:
            for i, p in enumerate(ports):
                h = health_of(p)
                if h is not None and "consensus_stall" in h.get("alarms", {}):
                    detect_ms = round((time.time() - t_part) * 1000, 1)
                    print(f"  node{i} raised consensus_stall after {detect_ms:.0f} ms")
                    break
            if detect_ms is not None:
                break
            time.sleep(0.2)
        if detect_ms is None:
            failures.append(
                f"no consensus_stall alarm within {args.detect_bound}s of the partition"
            )

        # hold the cut until EVERY node's own stall threshold has elapsed
        # (each node must raise — and later clear — its own alarm, so the
        # health.alarm/clear events land in every spool)
        time.sleep(max(0.0, t_part + args.partition_hold - time.time()))
        alarmed = [
            i for i, p in enumerate(ports)
            if (health_of(p) or {}).get("alarms", {}).get("consensus_stall")
        ]
        if len(alarmed) < 4:
            failures.append(
                f"only nodes {alarmed} raised consensus_stall while the cut held"
            )

        print("healing")
        for p in ports:
            rpc_call(p, "unsafe_chaos_heal")
        t_heal = time.time()
        # phase 1: commits resume (net recovery — re-dial + round
        # reconvergence; the chaos engine's number, bounded loosely)
        base_tip = max(
            (h for h in (height_of(p) for p in ports) if h is not None), default=0
        )
        recovery_ms = None
        while time.time() < t_heal + args.recovery_bound:
            tips = [h for h in (height_of(p) for p in ports) if h is not None]
            if tips and max(tips) > base_tip:
                recovery_ms = round((time.time() - t_heal) * 1000, 1)
                print(f"  commits resumed {recovery_ms:.0f} ms after heal")
                break
            time.sleep(0.2)
        if recovery_ms is None:
            failures.append(
                f"commits did not resume within {args.recovery_bound}s of heal"
            )
        # phase 2: the watchdogs NOTICE the recovery — all-clear within a
        # tick-latency bound of commits resuming (this PR's number)
        t_rec = time.time()
        clear_ms = None
        while time.time() < t_rec + args.clear_bound:
            states = [health_of(p) for p in ports]
            if all(
                h is not None and "consensus_stall" not in h.get("alarms", {})
                for h in states
            ):
                clear_ms = round((time.time() - t_heal) * 1000, 1)
                print(f"  stall alarm clear on every node "
                      f"{round((time.time() - t_rec) * 1000):d} ms after recovery")
                break
            time.sleep(0.2)
        if clear_ms is None:
            failures.append(
                f"stall alarm did not clear on every node within "
                f"{args.clear_bound}s of commits resuming"
            )

        time.sleep(args.post_heal)  # clean post-heal heights for the spool

        # -- act 2: SIGKILL + offline bundle --------------------------------
        victim_tip = height_of(ports[3])
        print(f"SIGKILLing node3 at height {victim_tip}")
        procs[3].send_signal(signal.SIGKILL)
        procs[3].wait(10)
        time.sleep(0.5)

        dump_dir = os.path.join(build, "bundles")
        run = subprocess.run(
            [sys.executable, "-m", "tendermint_tpu.cli", "--home", homes[3],
             "debug", "dump", "--offline", "--output", dump_dir],
            capture_output=True, text=True, cwd=REPO, timeout=60,
        )
        print(run.stdout.strip())
        if run.returncode != 0:
            failures.append(f"offline debug dump failed: {run.stderr[-500:]}")
            raise SystemExit
        bundles = sorted(
            os.path.join(dump_dir, f) for f in os.listdir(dump_dir)
            if f.endswith(".tar.gz")
        )
        if not bundles:
            failures.append("debug dump wrote no bundle")
            raise SystemExit

        sections = {}
        with tarfile.open(bundles[-1]) as tar:
            for member in tar.getmembers():
                name = os.path.basename(member.name)
                fh = tar.extractfile(member)
                if fh is not None:
                    sections[name] = fh.read()
        need = {"manifest.json", "config.toml", "spool.json", "span_report.json"}
        missing = need - set(sections)
        if missing:
            failures.append(f"bundle missing sections: {sorted(missing)}")
            raise SystemExit

        spool_dump = json.loads(sections["spool.json"])
        rep = json.loads(sections["span_report.json"])
        interior = rep["interior"]
        complete = len(rep["complete"])
        completeness = round(complete / interior, 3) if interior else 0.0
        print(
            f"offline bundle: {len(spool_dump['events'])} spool events, "
            f"{complete}/{interior} interior pre-crash heights with complete "
            f"span chains (bad={rep['bad']}, truncated={len(rep['truncated'])})"
        )
        if interior < 3:
            failures.append(f"too few interior pre-crash heights recorded ({interior})")
        if rep["bad"]:
            failures.append(f"broken span chains in the crash spool: {rep['bad']}")
        if complete != interior:
            failures.append(
                f"crash bundle incomplete: {complete}/{interior} heights "
                f"(truncated {rep['truncated']})"
            )
        kinds = {ev.get("kind") for ev in spool_dump["events"]}
        if "health.alarm" not in kinds or "health.clear" not in kinds:
            failures.append(
                "the watchdog's health.alarm/health.clear self-diagnosis did "
                f"not survive the crash in the spool (kinds seen: {len(kinds)})"
            )

        # the critical transition must have auto-captured a bundle too
        auto_dir = os.path.join(homes[3], "data", "forensics")
        autodumps = (
            [f for f in os.listdir(auto_dir) if f.endswith(".tar.gz")]
            if os.path.isdir(auto_dir) else []
        )
        if not autodumps:
            failures.append("no auto-bundle written on the critical transition")

        # dead-node causal merge: the corpse's spool + a live node's RPC
        # dump onto one timeline with agreeing hashes
        spool_path = os.path.join(homes[3], "data", "flight.spool")
        dead = tracemerge.load_dump(spool_path, name="node3-dead")
        live = rpc(ports[0], "dump_flight_recorder")["result"]
        live["node"] = "node0"
        merged = tracemerge.merge([dead, live])
        shared = [
            h for h, e in merged["heights"].items()
            if "node3-dead" in e["nodes"] and "node0" in e["nodes"]
        ]
        if len(shared) < 3:
            failures.append(
                f"dead-node merge aligned only {len(shared)} shared heights"
            )
        if merged["hash_mismatch_heights"]:
            failures.append(
                f"dead-node merge hash mismatch at {merged['hash_mismatch_heights']}"
            )
        print(
            f"dead-node causal merge: {len(shared)} shared heights aligned, "
            f"commit skew p90 {merged['commit_skew_ms_p90']} ms"
        )

        result = {
            "metric": "forensics_smoke",
            "crash_bundle_completeness": completeness,
            "health_detect_latency_ms": detect_ms if detect_ms is not None else -1.0,
            "health_clear_ms": clear_ms if clear_ms is not None else -1.0,
            "heal_recovery_ms": recovery_ms if recovery_ms is not None else -1.0,
            "interior_precrash_heights": interior,
            "spool_events": len(spool_dump["events"]),
            "spool_dropped": spool_dump.get("dropped", 0),
            "bundle_sections": len(sections),
            "autodumps": len(autodumps),
            "merged_shared_heights": len(shared),
            "victim_tip": victim_tip,
            "heights": [height_of(p) for p in ports[:3]],
        }
    except SystemExit:
        pass
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()

    if failures:
        print("FORENSICS SMOKE FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
    elif result:
        print(
            f"forensics smoke ok: crash bundle complete "
            f"({result['interior_precrash_heights']} pre-crash heights from "
            f"{result['spool_events']} spooled events), stall alarm in "
            f"{result['health_detect_latency_ms']:.0f} ms, clear in "
            f"{result['health_clear_ms']:.0f} ms, {result['autodumps']} "
            f"auto-bundle(s), dead-node merge aligned"
        )
        ok = True
    if args.json and result:
        print(json.dumps(result))
    return 0 if ok and not failures else 1


if __name__ == "__main__":
    sys.exit(main())
