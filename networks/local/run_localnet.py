#!/usr/bin/env python
"""Run a generated testnet as real OS processes (no docker needed).

Usage:
    python -m tendermint_tpu.cli testnet --validators 4 --output ./build [--fast]
    python networks/local/run_localnet.py ./build [--duration 30] [--json]

Spawns one `tendermint_tpu node` process per node directory (RPC/P2P ports
are read from each node's config.toml — no port arithmetic, so generators
can use any free ports), waits until EVERY node's RPC answers with height
>= 1 (readiness gate: per-process JAX import + XLA warmup takes seconds
and must not eat into the measurement window), then measures committed
blocks per second over --duration seconds of wall clock.

Exit code 0 iff every node committed at least 3 blocks and all heads agree
within 2 heights.  With --json, the last stdout line is a JSON object:
{"commits_per_sec", "blocks", "measure_s", "startup_s", "heights"} —
the e2e_commits_per_sec_4val_procs number bench.py reports (BASELINE
config #1 measured from real multi-process nodes, not one shared event
loop).
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

try:
    import tomllib
except ImportError:  # Python < 3.11
    import tomli as tomllib

# the script lives in networks/local/; the package at the repo root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from tendermint_tpu.libs import tracing  # noqa: E402


def rpc(port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/{path}", timeout=2) as r:
        return json.load(r)


def rpc_port_of(home: str) -> int:
    with open(os.path.join(home, "config", "config.toml"), "rb") as f:
        laddr = tomllib.load(f)["rpc"]["laddr"]
    # "tcp://127.0.0.1:26657" or "127.0.0.1:26657"
    return int(laddr.rsplit(":", 1)[1])


def dump_recorder(port: int) -> dict:
    """One node's full dump_flight_recorder snapshot (events + dropped
    count + the monotonic→wall anchor trace-net alignment needs)."""
    return rpc(port, "dump_flight_recorder")["result"]


def trace_check(rpc_ports) -> bool:
    """Every node must show complete propose→commit span chains for its
    interior recorded heights.  A busy ring that wrapped mid-chain reports
    prefix-truncated heights — honest, not fatal (hard-failing there made
    the check useless exactly on the loaded nets it is for); only a
    mid-chain hole fails.  This is what `make trace-smoke` asserts."""
    ok = True
    for port in rpc_ports:
        try:
            snap = dump_recorder(port)
        except Exception as e:
            print(f"trace check: node on :{port} unreachable: {e}", file=sys.stderr)
            ok = False
            continue
        rep = tracing.span_report(snap["events"], dropped=snap.get("dropped", 0))
        if rep["interior"] < 3 or rep["bad"] or not rep["complete"]:
            print(
                f"trace check FAILED on :{port}: {rep['interior']} interior heights, "
                f"complete={len(rep['complete'])} truncated={len(rep['truncated'])} "
                f"broken chains: {rep['bad']}",
                file=sys.stderr,
            )
            ok = False
        else:
            msg = f"trace check ok on :{port}: {len(rep['complete'])} complete span chains"
            if rep["truncated"]:
                msg += f" ({len(rep['truncated'])} truncated by ring wrap)"
            print(msg)
    return ok


def poll_heights(rpc_ports) -> list:
    heights = []
    for port in rpc_ports:
        try:
            heights.append(
                int(rpc(port, "status")["result"]["sync_info"]["latest_block_height"])
            )
        except Exception:
            heights.append(-1)
    return heights


def poll_ready(rpc_ports) -> list:
    """Per-node readiness: height >= 1 AND the node reports sync phase
    `caught_up` (`/status` sync_info.sync_phase — a node mid-statesync or
    mid-fastsync serves RPC long before it can keep up with the net, so
    height alone is a premature gate).  Missing key falls back to the old
    height-only check."""
    ready = []
    for port in rpc_ports:
        try:
            si = rpc(port, "status")["result"]["sync_info"]
            ok = int(si["latest_block_height"]) >= 1
            if "sync_phase" in si:
                ok = ok and si["sync_phase"] == "caught_up"
            ready.append(ok)
        except Exception:
            ready.append(False)
    return ready


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("build_dir")
    ap.add_argument("--duration", type=float, default=30.0,
                    help="measurement window AFTER all nodes are ready")
    ap.add_argument("--startup-timeout", type=float, default=90.0,
                    help="max wait for every node's RPC to report height >= 1")
    ap.add_argument("--json", action="store_true",
                    help="print a JSON result line (commits/sec) at the end")
    ap.add_argument("--trace-check", action="store_true",
                    help="fail unless every node's flight recorder shows a complete "
                    "propose→commit span chain for every interior block")
    ap.add_argument("--dump-recorders", default="",
                    help="directory to write every node's recorder dump "
                    "(one JSON per node — `tendermint_tpu trace-net` input)")
    ap.add_argument("--trace-net", action="store_true",
                    help="merge every node's dump into one causal timeline and "
                    "fail unless it is complete, aligned, and carries nonzero "
                    "loop attribution for every interior block (trace-net-smoke)")
    args = ap.parse_args()

    homes = sorted(
        os.path.join(args.build_dir, d)
        for d in os.listdir(args.build_dir)
        if d.startswith("node")
    )
    if not homes:
        print(f"no node*/ directories under {args.build_dir}", file=sys.stderr)
        return 2
    rpc_ports = [rpc_port_of(home) for home in homes]

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "tendermint_tpu.cli", "--home", home, "node"],
            env=env,
            stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT,
        )
        for home in homes
    ]
    print(f"spawned {len(procs)} nodes; waiting for all RPCs to reach height 1")
    ok = False
    result = {}
    try:
        # readiness gate: the duration clock starts only once every node is
        # serving RPC and has committed its first block
        t_start = time.time()
        ready_deadline = t_start + args.startup_timeout
        while time.time() < ready_deadline:
            if all(poll_ready(rpc_ports)):
                heights = poll_heights(rpc_ports)
                if min(heights) >= 1:
                    break
            if any(p.poll() is not None for p in procs):
                print("a node process exited during startup", file=sys.stderr)
                return 1
            time.sleep(0.5)
        else:
            print(f"startup timeout: heights {poll_heights(rpc_ports)}", file=sys.stderr)
            return 1
        startup_s = time.time() - t_start

        # the gate's heights are already validated (all >= 1); a fresh poll
        # could transiently fail to -1 under load and corrupt the baseline
        start_heights = heights
        t0 = time.time()
        deadline = t0 + args.duration
        while time.time() < deadline:
            time.sleep(min(2.0, max(0.1, deadline - time.time())))
            heights = poll_heights(rpc_ports)
            print("heights:", heights)
        # retry any RPC that failed on the final poll — a single timed-out
        # status call must not turn the headline commits/sec negative
        for _ in range(5):
            if min(heights) >= 0:
                break
            time.sleep(0.5)
            retried = poll_heights(rpc_ports)
            heights = [max(a, b) for a, b in zip(heights, retried)]
        measure_s = time.time() - t0
        blocks = min(heights) - min(start_heights)
        result = {
            "commits_per_sec": round(blocks / measure_s, 2),
            "blocks": blocks,
            "measure_s": round(measure_s, 2),
            "startup_s": round(startup_s, 2),
            "heights": heights,
        }
        # per-block ms timeline from node0's flight recorder — the same
        # event stream dump_flight_recorder serves; bench.py sources its
        # e2e_4val_breakdown from this instead of ad-hoc timers
        try:
            result["recorder"] = tracing.block_breakdown(dump_recorder(rpc_ports[0])["events"])
        except Exception as e:
            print(f"flight recorder dump failed: {e}", file=sys.stderr)
        if min(heights) >= 3 and max(heights) - min(heights) <= 2:
            print("localnet healthy: all nodes committing in lock-step")
            ok = True
        if args.trace_check and not trace_check(rpc_ports):
            ok = False
        if args.dump_recorders or args.trace_net:
            try:
                snaps = []
                for i, port in enumerate(rpc_ports):
                    snap = dump_recorder(port)
                    # per-node files / timeline rows keyed by the home dir
                    # name, not the moniker (which operators may not vary)
                    snap["node"] = os.path.basename(homes[i])
                    snaps.append(snap)
            except Exception as e:
                print(f"recorder dump failed: {e}", file=sys.stderr)
                if args.trace_net:
                    ok = False
                snaps = []
            if snaps and args.dump_recorders:
                os.makedirs(args.dump_recorders, exist_ok=True)
                for snap in snaps:
                    path = os.path.join(args.dump_recorders, f"{snap['node']}.json")
                    with open(path, "w") as fh:
                        json.dump(snap, fh)
                print(f"wrote {len(snaps)} recorder dumps to {args.dump_recorders}")
            if snaps and args.trace_net:
                # merged causal timeline across every process — each node
                # is a separate interpreter here, so the per-node loop
                # attribution is TRUE per-node (unlike the in-proc rigs)
                from tendermint_tpu.libs import tracemerge

                merged = tracemerge.merge(snaps)
                failures = tracemerge.check(snaps, merged)
                result["trace_net"] = {
                    "heights": len(merged["heights"]),
                    "offsets_ms": merged["offsets_ms"],
                    "commit_skew_ms_p50": merged["commit_skew_ms_p50"],
                    "commit_skew_ms_p90": merged["commit_skew_ms_p90"],
                    "coverage_ms_p90": merged["coverage_ms_p90"],
                    "attribution": {
                        s["node"]: tracemerge.median_attribution(
                            tracemerge.attribution_by_height(s)
                        )
                        for s in snaps
                    },
                    "failures": failures,
                }
                slow = tracemerge.slowest_height(merged)
                if slow is not None:
                    print(f"slowest block (height {slow}) on the merged timeline:")
                    print(tracemerge.format_timeline(merged, [slow]))
                print(tracemerge.format_attribution(snaps))
                if failures:
                    print("trace-net check FAILED:", file=sys.stderr)
                    for f in failures:
                        print(f"  - {f}", file=sys.stderr)
                    ok = False
                else:
                    print(
                        f"trace-net check ok: {len(merged['heights'])} heights "
                        f"aligned across {len(snaps)} nodes"
                    )
    except KeyboardInterrupt:
        pass
    finally:
        for p in procs:
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
