#!/usr/bin/env python
"""Load smoke: the tx-ingress firehose against a real 4-validator
multi-process localnet — the `make load-smoke` acceptance rig for the
overload-robustness layer.

Three phases against QoS-configured nodes (per-source RPC rate limit,
bounded in-flight broadcasts, commit-waiter cap, mempool sig_precheck +
priority eviction, per-peer gossip pacing):

  idle      measure the net's unloaded commit rate
  firehose  tendermint_tpu/tools/loadgen.py drives signed-tx envelopes at
            every node's broadcast endpoint as fast as the connections go
            — by construction >= 2x what admission control accepts —
            while the PR 5 chaos invariant checker scrapes /status +
            /blockchain from every node underneath (agreement, no height
            regression); commit-latency-under-load percentiles come from
            node0's flight recorder
  recover   firehose off; the commit rate must return to within 2x idle

FAILS on: any checker violation; a commit stall under load; rejections
WITHOUT explicit overload errors (silent drops: transport-error share of
offered > 5%); offered < 2x accepted (the firehose never saturated
admission); unrecovered post-firehose commit rate.

With --json the last stdout line carries `tx_ingress_sustained_tps` and
`commit_latency_under_load_ms` — the numbers bench.py reports.
"""

import argparse
import asyncio
import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import tendermint_tpu.store  # noqa: E402,F401 — registers BlockMeta with the codec
import tendermint_tpu.types  # noqa: E402,F401 — registers Block types
from tendermint_tpu.chaos.checker import InvariantChecker  # noqa: E402
from tendermint_tpu.config import load_config, save_config  # noqa: E402
from tendermint_tpu.rpc.jsonrpc import from_jsonable  # noqa: E402
from tendermint_tpu.tools import loadgen  # noqa: E402


def rpc(port: int, path: str, timeout: float = 3.0):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/{path}", timeout=timeout) as r:
        return json.load(r)


def height_of(port: int):
    try:
        return int(rpc(port, "status")["result"]["sync_info"]["latest_block_height"])
    except Exception:
        return None


def scrape(checker: InvariantChecker, ports) -> None:
    for i, p in enumerate(ports):
        h = height_of(p)
        checker.observe_height(i, h)
        if h is None or h < 1:
            continue
        try:
            metas = from_jsonable(
                rpc(p, f"blockchain?min_height={max(1, h - 19)}&max_height={h}")["result"]
            )["block_metas"]
        except Exception:
            continue
        for meta in metas:
            checker.observe_block_hash(i, meta.header.height, meta.block_id.hash)


def commit_rate(ports, window: float, checker: InvariantChecker) -> float:
    """Blocks/sec over `window` seconds (max known tip), scraping the
    checker along the way."""
    start = None
    deadline = time.time() + window
    while time.time() < deadline:
        scrape(checker, ports)
        tips = [h for h in (height_of(p) for p in ports) if h is not None]
        if tips and start is None:
            start = (time.time(), max(tips))
        time.sleep(0.4)
    tips = [h for h in (height_of(p) for p in ports) if h is not None]
    if start is None or not tips:
        return 0.0
    dt = time.time() - start[0]
    return (max(tips) - start[1]) / dt if dt > 0 else 0.0


def spawn(home: str, env) -> subprocess.Popen:
    log = open(os.path.join(home, "node.log"), "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cli", "--home", home, "node"],
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )


def poll_status_health(ports, health_seen) -> None:
    """Sample every node's /status `health` block (the watchdog verdict):
    the rig asserts the node SELF-reports degradation under the firehose
    — shedding alone could be a node lying to itself about being fine."""
    for i, p in enumerate(ports):
        try:
            st = rpc(p, "status")["result"]
        except Exception:
            continue
        h = st.get("health")
        if h is None:
            continue
        health_seen["block_present"] = True
        if h.get("verdict") != "ok":
            health_seen["degraded"].update(
                f"node{i}:{a}" for a in h.get("alarms", ["<no-alarm-name>"])
            )


async def _load_phase(ports, checker, args, health_seen):
    """Run the firehose and the checker scraper concurrently on one loop
    (the scraper hops to a thread per poll so the loadgen workers keep
    the loop)."""
    targets = [f"127.0.0.1:{p}" for p in ports]
    stop = asyncio.Event()

    def _scrape_once():
        scrape(checker, ports)
        poll_status_health(ports, health_seen)

    async def scraper():
        while not stop.is_set():
            await asyncio.get_event_loop().run_in_executor(None, _scrape_once)
            try:
                await asyncio.wait_for(stop.wait(), 0.5)
            except asyncio.TimeoutError:
                pass

    scr = asyncio.create_task(scraper())
    try:
        result = await loadgen.run_load(
            targets,
            duration=args.load_duration,
            rate=0.0,  # as fast as the connections go: the firehose
            connections=args.connections,
            tx_bytes=args.tx_bytes,
            mode="sync",
            fee=1,  # nonzero priority exercises the fee lane end to end
            monitor_target=targets[0],
        )
    finally:
        stop.set()
        await scr
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--build-dir", default="./build-load")
    ap.add_argument("--base-port", type=int, default=31656)
    ap.add_argument("--idle", type=float, default=6.0)
    ap.add_argument("--load-duration", type=float, default=15.0)
    ap.add_argument("--recover", type=float, default=10.0)
    ap.add_argument("--connections", type=int, default=16)
    ap.add_argument("--tx-bytes", type=int, default=192)
    ap.add_argument("--rate-limit", type=float, default=25.0,
                    help="per-source broadcast rate limit configured on each node "
                    "(tx/sec) — sized so even a slow single-host client "
                    "(~300 req/s on 2 cores) overruns the 4-node admission "
                    "ceiling by >= 2x")
    ap.add_argument("--latency-bound", type=float, default=10_000.0,
                    help="max p90 commit interval under load (ms)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    build = os.path.abspath(args.build_dir)
    if os.path.isdir(build):
        shutil.rmtree(build)
    subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "testnet",
         "--validators", "4", "--output", build,
         "--base-port", str(args.base_port), "--fast"],
        check=True, cwd=REPO,
    )
    homes = [os.path.join(build, f"node{i}") for i in range(4)]
    ports = [args.base_port + 10 * i + 1 for i in range(4)]

    # arm the full QoS surface on every node: the rig is only honest if
    # the machinery under test is ON
    for home in homes:
        path = os.path.join(home, "config", "config.toml")
        cfg = load_config(path, home=home)
        cfg.mempool.sig_precheck = True
        cfg.mempool.size = 2000
        cfg.rpc.broadcast_rate = args.rate_limit
        cfg.rpc.broadcast_rate_burst = int(args.rate_limit)
        cfg.rpc.max_broadcast_inflight = 256
        cfg.rpc.max_commit_waiters = 16
        save_config(cfg, path)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [spawn(h, env) for h in homes]

    checker = InvariantChecker(4)
    result = {}
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
        print(f"localnet ready, heights {[height_of(p) for p in ports]}")

        idle_cps = commit_rate(ports, args.idle, checker)
        print(f"idle commit rate: {idle_cps:.2f} blocks/sec")

        health_seen = {"block_present": False, "degraded": set()}
        t0 = time.time()
        load = asyncio.run(_load_phase(ports, checker, args, health_seen))
        load_wall = time.time() - t0
        tip_after_load = max(
            (h for h in (height_of(p) for p in ports) if h is not None), default=0
        )
        print(
            f"firehose {load_wall:.1f}s: offered {load['offered_tps']}/s, "
            f"accepted {load['tx_ingress_sustained_tps']}/s, throttled "
            f"{load['throttled']}, rejected {load['rejected']}, transport "
            f"errors {load['transport_errors']}, {load['commits_under_load']} "
            f"commits under load, latency {load['commit_latency_under_load_ms']}"
        )

        recover_cps = commit_rate(ports, args.recover, checker)
        print(f"recovery commit rate: {recover_cps:.2f} blocks/sec "
              f"(idle was {idle_cps:.2f})")
        if health_seen["degraded"]:
            print(f"self-reported degradation under load: "
                  f"{sorted(health_seen['degraded'])}")
        # the degradation must CLEAR once the firehose is off — poll past
        # the recovery window for every node to report ok again (mempool
        # drains as blocks commit; lag subsides)
        health_recovered = False
        clear_deadline = time.time() + 20.0
        while time.time() < clear_deadline:
            verdicts = []
            for p in ports:
                try:
                    verdicts.append(
                        rpc(p, "status")["result"].get("health", {}).get("verdict")
                    )
                except Exception:
                    verdicts.append(None)
            if all(v == "ok" for v in verdicts):
                health_recovered = True
                break
            time.sleep(0.5)

        lat = load["commit_latency_under_load_ms"]
        result = {
            "metric": "load_smoke",
            "tx_ingress_sustained_tps": load["tx_ingress_sustained_tps"],
            "commit_latency_under_load_ms": lat.get("p90", -1.0),
            "commit_latency_percentiles_ms": lat,
            "offered_tps": load["offered_tps"],
            "throttled": load["throttled"],
            "rejected": load["rejected"],
            "transport_errors": load["transport_errors"],
            "retry_after_seen": load["retry_after_seen"],
            "commits_under_load": load["commits_under_load"],
            "idle_commits_per_sec": round(idle_cps, 2),
            "recovery_commits_per_sec": round(recover_cps, 2),
            "health_degraded_under_load": sorted(health_seen["degraded"]),
            "health_recovered": health_recovered,
            "heights": [height_of(p) for p in ports],
            **checker.summary(),
        }

        failures = []
        if checker.violations:
            failures.append(f"invariant violations: {checker.violations}")
        if load["tx_ingress_sustained_tps"] <= 0:
            failures.append("no txs accepted under load")
        if load["offered_tps"] < 2 * load["tx_ingress_sustained_tps"]:
            failures.append(
                f"firehose never saturated admission: offered "
                f"{load['offered_tps']}/s < 2x accepted "
                f"{load['tx_ingress_sustained_tps']}/s"
            )
        if load["throttled"] <= 0:
            failures.append("no explicit overload rejections observed")
        if load["retry_after_seen"] <= 0:
            failures.append("overload errors carried no retry_after hint")
        silent = load["transport_errors"] / max(1, load["offered"])
        if silent > 0.05:
            failures.append(
                f"{silent:.1%} of offered txs vanished into transport errors "
                "(silent drops)"
            )
        if load["commits_under_load"] < 2 or tip_after_load <= 1:
            failures.append("consensus stalled under the firehose")
        if lat.get("p90", -1.0) < 0 or lat["p90"] > args.latency_bound:
            failures.append(
                f"commit latency under load p90 {lat.get('p90')} ms exceeds "
                f"{args.latency_bound} ms"
            )
        if recover_cps < idle_cps / 2:
            failures.append(
                f"post-firehose commit rate {recover_cps:.2f}/s did not recover "
                f"to within 2x idle ({idle_cps:.2f}/s)"
            )
        if len(checker.agreed_heights()) < 3:
            failures.append("too few heights cross-checked for agreement")
        if not health_seen["block_present"]:
            failures.append("/status never carried a health block (watchdog off?)")
        if not health_seen["degraded"]:
            failures.append(
                "no node self-reported degradation during the firehose "
                "(the watchdog missed sustained saturation)"
            )
        if not health_recovered:
            failures.append(
                "health verdict did not return to ok after the firehose"
            )
        if failures:
            print("LOAD SMOKE FAILED:", file=sys.stderr)
            for f in failures:
                print(f"  - {f}", file=sys.stderr)
        else:
            print(
                f"load smoke ok: {load['tx_ingress_sustained_tps']} tx/s "
                f"sustained under a {load['offered_tps']} tx/s firehose, "
                f"p90 commit interval {lat['p90']} ms, agreement over "
                f"{len(checker.agreed_heights())} heights, recovery "
                f"{recover_cps:.2f}/s vs idle {idle_cps:.2f}/s"
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
