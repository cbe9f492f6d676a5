#!/usr/bin/env python
"""Finality smoke: the consensus-pipeline A/B acceptance rig against a
real 4-validator multi-process localnet — `make finality-smoke`.

Two arms, each a fresh `testnet --fast` build (the --fast rig runs on
memdb, so an in-place restart cannot carry the chain across arms):

  serial     pipeline_delivery = pipeline_speculative_assembly = False on
             every node: height H+1 cannot start until H's ABCI delivery
             completes on the receive routine (pre-pipeline behaviour)
  pipelined  both knobs ON (the shipping default): ABCI finalize runs on
             a spawned delivery task, H+1's propose overlaps H's
             finalize, the proposer's part-set is speculatively
             pre-built — then a tools/loadgen.py firehose window measures
             finality under ingress pressure

Each arm measures commit-to-commit latency and the per-stage budget
(propose / prevote / precommit / commit_persist / finalize /
next_propose) from node0's flight recorder via `dump_flight_recorder`
seq watermarks, while the chaos invariant checker scrapes /status +
/blockchain from every node underneath (agreement, no height
regression).

FAILS on: any checker violation; either arm too stalled to budget;
pipelined idle commit-to-commit p50 >= --latency-bound (default 100 ms);
pipelined p50 regressing past --regress-tolerance x the serial p50; a
stall under the firehose; too few cross-checked heights.

With --json the last stdout line carries `commit_to_commit_p50_ms`,
`commit_to_commit_p90_ms`, `finality_under_load_p50_ms`, both arms'
stage budgets, and the pipelined arm's cross-node net budget
(`vote_fanin_ms`, `part_stream_ms`, `gossip_hop_p90_ms` plus the full
`net_budget` breakdown) — the numbers bench.py reports as bench_finality.
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
from tendermint_tpu.libs import tracing  # noqa: E402
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


def recorder_seq(port: int) -> int:
    """Current flight-recorder watermark: pass it back as `since` to dump
    only events recorded after this instant."""
    snap = rpc(port, "dump_flight_recorder?kinds=none")["result"]
    return int(snap.get("next_seq", 0))


def recorder_events(port: int, since: int):
    snap = rpc(port, f"dump_flight_recorder?since={since}", timeout=10.0)["result"]
    return snap.get("events", [])


def spawn(home: str, env) -> subprocess.Popen:
    log = open(os.path.join(home, "node.log"), "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cli", "--home", home, "node"],
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )


def arm_pipeline(homes, on: bool) -> None:
    """Flip the pipeline knobs on every node's config.toml."""
    for home in homes:
        path = os.path.join(home, "config", "config.toml")
        cfg = load_config(path, home=home)
        cfg.consensus.pipeline_delivery = on
        cfg.consensus.pipeline_speculative_assembly = on
        save_config(cfg, path)


def build_testnet(build: str, base_port: int, pipeline_on: bool):
    """Fresh 4-val --fast testnet with the pipeline knobs armed the
    requested way on every node.  Returns (homes, ports)."""
    if os.path.isdir(build):
        shutil.rmtree(build)
    subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "testnet",
         "--validators", "4", "--output", build,
         "--base-port", str(base_port), "--fast"],
        check=True, cwd=REPO,
    )
    homes = [os.path.join(build, f"node{i}") for i in range(4)]
    ports = [base_port + 10 * i + 1 for i in range(4)]
    arm_pipeline(homes, on=pipeline_on)
    return homes, ports


def start_net(homes, env, ports):
    """Spawn all nodes and wait for every height to reach 1.  On failure
    the spawned processes are torn down before raising — the caller never
    sees them, so it cannot clean them up itself."""
    procs = [spawn(h, env) for h in homes]
    try:
        deadline = time.time() + 120.0
        while time.time() < deadline:
            hs = [height_of(p) for p in ports]
            if all(h is not None and h >= 1 for h in hs):
                return procs
            if any(p.poll() is not None for p in procs):
                raise RuntimeError("a node died during startup")
            time.sleep(0.5)
        raise RuntimeError(
            f"startup timeout: heights {[height_of(p) for p in ports]}"
        )
    except BaseException:
        stop_net(procs)
        raise


def stop_net(procs) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(10)
        except subprocess.TimeoutExpired:
            p.kill()


def measure_budget(ports, checker, window: float):
    """Scrape the checker for `window` seconds, then decompose node0's
    recorder events from the window into both budgets: the local stage
    budget and the cross-node net budget (proposal propagation, part
    stream, vote fan-in, hop latencies — wire-level trace context)."""
    mark = recorder_seq(ports[0])
    deadline = time.time() + window
    while time.time() < deadline:
        scrape(checker, ports)
        time.sleep(0.4)
    events = recorder_events(ports[0], mark)
    return tracing.stage_budget(events), tracing.net_budget(events)


async def _load_phase(ports, checker, args):
    """Firehose + concurrent checker scraping on one loop (the scraper
    hops to a thread per poll so the loadgen workers keep the loop)."""
    targets = [f"127.0.0.1:{p}" for p in ports]
    stop = asyncio.Event()

    async def scraper():
        while not stop.is_set():
            await asyncio.get_event_loop().run_in_executor(
                None, scrape, checker, ports
            )
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
            fee=1,
            monitor_target=targets[0],
        )
    finally:
        stop.set()
        await scr
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--build-dir", default="./build-finality")
    ap.add_argument("--base-port", type=int, default=31956)
    ap.add_argument("--measure", type=float, default=8.0,
                    help="idle measurement window per arm (seconds)")
    ap.add_argument("--load-duration", type=float, default=8.0)
    ap.add_argument("--connections", type=int, default=8)
    ap.add_argument("--tx-bytes", type=int, default=192)
    ap.add_argument("--latency-bound", type=float, default=100.0,
                    help="max pipelined idle commit-to-commit p50 (ms) — "
                    "the sub-second-finality hard number at 4 validators")
    ap.add_argument("--regress-tolerance", type=float, default=1.25,
                    help="pipelined p50 must stay <= tolerance x serial p50 "
                    "(idle --fast blocks are empty, so the arms differ by "
                    "scheduling noise; a real re-serialization would add the "
                    "whole finalize span and blow well past this)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    build = os.path.abspath(args.build_dir)

    env = dict(os.environ, JAX_PLATFORMS="cpu")

    # one checker per arm: each arm is a fresh chain from genesis, so a
    # shared checker would see the height reset as a regression
    checker_serial = InvariantChecker(4)
    checker = InvariantChecker(4)
    result = {}
    ok = False
    procs = []
    try:
        # -- arm A: serial baseline ------------------------------------
        homes, ports = build_testnet(build, args.base_port, pipeline_on=False)
        procs = start_net(homes, env, ports)
        print(f"serial arm ready, heights {[height_of(p) for p in ports]}")
        budget_serial, _ = measure_budget(ports, checker_serial, args.measure)
        stop_net(procs)
        procs = []
        if budget_serial:
            print("serial " + tracing.format_budget(budget_serial).replace("\n", "\n  "))

        # -- arm B: pipelined (the shipping default) -------------------
        homes, ports = build_testnet(build, args.base_port, pipeline_on=True)
        procs = start_net(homes, env, ports)
        print(f"pipelined arm ready, heights {[height_of(p) for p in ports]}")
        budget_on, net_on = measure_budget(ports, checker, args.measure)
        if budget_on:
            print("pipelined " + tracing.format_budget(budget_on).replace("\n", "\n  "))
        if net_on:
            print("pipelined " + tracing.format_net_budget(net_on).replace("\n", "\n  "))

        # firehose window: finality under ingress pressure
        mark = recorder_seq(ports[0])
        load = asyncio.run(_load_phase(ports, checker, args))
        budget_load = tracing.stage_budget(recorder_events(ports[0], mark))
        print(
            f"firehose: offered {load['offered_tps']}/s, accepted "
            f"{load['tx_ingress_sustained_tps']}/s, "
            f"{load['commits_under_load']} commits under load"
        )
        if budget_load:
            print("under-load " + tracing.format_budget(budget_load).replace("\n", "\n  "))

        p50_serial = budget_serial["commit_to_commit_p50_ms"] if budget_serial else -1.0
        p50_on = budget_on["commit_to_commit_p50_ms"] if budget_on else -1.0
        p90_on = budget_on["commit_to_commit_p90_ms"] if budget_on else -1.0
        p50_load = budget_load["commit_to_commit_p50_ms"] if budget_load else -1.0
        net_stages = (net_on or {}).get("stages", {})
        result = {
            "metric": "finality_smoke",
            "commit_to_commit_p50_ms": p50_on,
            "commit_to_commit_p90_ms": p90_on,
            "commit_to_commit_p50_ms_serial": p50_serial,
            "finality_under_load_p50_ms": p50_load,
            "vote_fanin_ms": net_stages.get("vote_fanin", {}).get("p50_ms", -1.0),
            "part_stream_ms": net_stages.get("part_stream", {}).get("p50_ms", -1.0),
            "gossip_hop_p90_ms": (net_on or {}).get(
                "hop_lat_all_ms", {}
            ).get("p90", -1.0),
            "budget_serial": budget_serial,
            "budget_pipelined": budget_on,
            "budget_under_load": budget_load,
            "net_budget": net_on,
            "offered_tps": load["offered_tps"],
            "tx_ingress_sustained_tps": load["tx_ingress_sustained_tps"],
            "commits_under_load": load["commits_under_load"],
            "heights": [height_of(p) for p in ports],
            **checker.summary(),
        }

        failures = []
        if checker_serial.violations:
            failures.append(
                f"invariant violations (serial arm): {checker_serial.violations}"
            )
        if checker.violations:
            failures.append(f"invariant violations: {checker.violations}")
        if budget_serial is None:
            failures.append("serial arm produced no complete span chains")
        if budget_on is None:
            failures.append("pipelined arm produced no complete span chains")
        if p50_on >= 0 and p50_on >= args.latency_bound:
            failures.append(
                f"pipelined commit-to-commit p50 {p50_on} ms >= "
                f"{args.latency_bound} ms bound"
            )
        if p50_on >= 0 and p50_serial >= 0 and p50_on > args.regress_tolerance * p50_serial:
            failures.append(
                f"pipelined p50 {p50_on} ms regressed past "
                f"{args.regress_tolerance}x serial baseline {p50_serial} ms"
            )
        if load["commits_under_load"] < 2:
            failures.append("consensus stalled under the firehose")
        if budget_load is None:
            failures.append("no complete span chains under load")
        if len(checker.agreed_heights()) < 3:
            failures.append("too few heights cross-checked for agreement")
        if failures:
            print("FINALITY SMOKE FAILED:", file=sys.stderr)
            for f in failures:
                print(f"  - {f}", file=sys.stderr)
        else:
            print(
                f"finality smoke ok: pipelined commit-to-commit p50 "
                f"{p50_on} ms (serial {p50_serial} ms, bound "
                f"{args.latency_bound} ms), under-load p50 {p50_load} ms, "
                f"agreement over {len(checker.agreed_heights())} heights"
            )
            ok = True
    finally:
        stop_net(procs)
    if args.json and result:
        print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
