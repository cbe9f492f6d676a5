#!/usr/bin/env python
"""Statesync smoke: an empty 4th node joins a live 3-validator localnet
via snapshot restore — the `make statesync-smoke` acceptance rig.

Flow:
  1. generate a 3-validator `testnet --fast` tree, switch on app
     snapshots ([statesync] snapshot_interval) in every config;
  2. run the validators as OS processes until a snapshot provably exists
     (height > interval + 2);
  3. read the trust root (header hash at a committed height) from node0's
     RPC, generate a 4th EMPTY node home with `[statesync] enable`,
     trust servers = node0+node1 RPC, persistent peers = all validators;
  4. start the joiner and require, within --budget seconds: sync phase
     reaches `caught_up`, the joiner's `earliest_block_height` is ABOVE
     genesis (fell-back-to-replay ⇒ FAIL), its flight recorder shows the
     full statesync.offer→chunk→restore→handover span chain, and it then
     FOLLOWS consensus (head advances ≥ 2 more heights).

With --json the last stdout line carries `statesync_bootstrap_ms`
(measured from the recorder spans) — the number bench.py reports.
"""

import argparse
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

from tendermint_tpu.config import load_config, save_config  # noqa: E402
from tendermint_tpu.libs import tracing  # noqa: E402
from tendermint_tpu.rpc.jsonrpc import from_jsonable  # noqa: E402

# the --fast rig commits ~10 blocks/sec: a snapshot lives keep_recent ×
# interval blocks, so 10 × 10 gives the joiner a ~10 s window per
# snapshot (plus re-discovery of fresher ones between candidates)
SNAPSHOT_INTERVAL = 10
SNAPSHOT_KEEP_RECENT = 10


def rpc(port: int, path: str):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/{path}", timeout=3) as r:
        return json.load(r)


def heights(ports):
    out = []
    for p in ports:
        try:
            out.append(int(rpc(p, "status")["result"]["sync_info"]["latest_block_height"]))
        except Exception:
            out.append(-1)
    return out


def spawn(home: str, env) -> subprocess.Popen:
    log = open(os.path.join(home, "node.log"), "wb")
    return subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cli", "--home", home, "node"],
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--build-dir", default="./build-statesync")
    ap.add_argument("--base-port", type=int, default=29656)
    ap.add_argument("--budget", type=float, default=90.0,
                    help="seconds the joiner gets from spawn to caught_up + follow")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    build = os.path.abspath(args.build_dir)
    if os.path.isdir(build):
        shutil.rmtree(build)
    subprocess.run(
        [sys.executable, "-m", "tendermint_tpu.cli", "testnet",
         "--validators", "3", "--output", build,
         "--base-port", str(args.base_port), "--fast"],
        check=True, capture_output=True, timeout=120, cwd=REPO,
    )

    homes = sorted(os.path.join(build, d) for d in os.listdir(build) if d.startswith("node"))
    rpc_ports = []
    for home in homes:
        path = os.path.join(home, "config", "config.toml")
        cfg = load_config(path, home=home)
        cfg.statesync.snapshot_interval = SNAPSHOT_INTERVAL
        cfg.statesync.snapshot_keep_recent = SNAPSHOT_KEEP_RECENT
        cfg.statesync.snapshot_chunk_bytes = 4096
        save_config(cfg, path)
        rpc_ports.append(int(cfg.rpc.laddr.rsplit(":", 1)[1]))

    env = dict(os.environ, JAX_PLATFORMS="cpu")

    procs = [spawn(home, env) for home in homes]
    joiner_proc = None
    result, ok = {}, False
    try:
        # validators up + a snapshot provably taken
        deadline = time.time() + 90
        while time.time() < deadline:
            hs = heights(rpc_ports)
            if min(hs) > SNAPSHOT_INTERVAL + 2:
                break
            if any(p.poll() is not None for p in procs):
                print("a validator process died during startup", file=sys.stderr)
                return 1
            time.sleep(0.5)
        else:
            print(f"validators never reached snapshot height: {heights(rpc_ports)}",
                  file=sys.stderr)
            return 1
        print(f"validators at {heights(rpc_ports)}; snapshot at {SNAPSHOT_INTERVAL} exists")

        # trust root from node0 (height 2 is long-committed and canonical)
        commit = from_jsonable(rpc(rpc_ports[0], "commit?height=2")["result"])
        trust_hash = commit["signed_header"].header.hash().hex()

        # the 4th, EMPTY node: node0's config shape (fast-rig timeouts,
        # memdb, chain id) with its own ports, statesync on, peers +
        # trust servers wired
        joiner_home = os.path.join(build, "joiner")
        cfg = load_config(os.path.join(homes[0], "config", "config.toml"),
                          home=joiner_home)
        cfg.home = joiner_home
        cfg.base.moniker = "joiner"
        cfg.base.fast_sync = True
        jp = args.base_port + 50
        cfg.p2p.laddr = f"tcp://127.0.0.1:{jp}"
        cfg.rpc.laddr = f"tcp://127.0.0.1:{jp + 1}"
        peers = []
        for home in homes:
            c = load_config(os.path.join(home, "config", "config.toml"), home=home)
            nid = subprocess.run(
                [sys.executable, "-m", "tendermint_tpu.cli", "--home", home, "show_node_id"],
                capture_output=True, text=True, cwd=REPO, timeout=60,
            ).stdout.strip()
            peers.append(f"{nid}@{c.p2p.laddr.split('://')[-1]}")
        cfg.p2p.persistent_peers = ",".join(peers)
        cfg.statesync.enable = True
        cfg.statesync.rpc_servers = ",".join(
            f"127.0.0.1:{p}" for p in rpc_ports[:2]
        )
        cfg.statesync.trust_height = 2
        cfg.statesync.trust_hash = trust_hash
        cfg.statesync.discovery_time = 2.0
        cfg.ensure_dirs()
        save_config(cfg, os.path.join(joiner_home, "config", "config.toml"))
        shutil.copy(os.path.join(homes[0], "config", "genesis.json"),
                    os.path.join(joiner_home, "config", "genesis.json"))

        t_join = time.time()
        joiner_proc = spawn(joiner_home, env)
        jrpc = jp + 1

        # gate 1: caught_up within budget, never having replayed genesis
        caught_up = False
        while time.time() - t_join < args.budget:
            if joiner_proc.poll() is not None:
                print("joiner process died", file=sys.stderr)
                return 1
            try:
                si = rpc(jrpc, "status")["result"]["sync_info"]
            except Exception:
                time.sleep(0.5)
                continue
            if si["sync_phase"] == "caught_up" and int(si["latest_block_height"]) >= 1:
                caught_up = True
                base = int(si["earliest_block_height"])
                break
            time.sleep(0.5)
        if not caught_up:
            print(f"joiner never caught up within {args.budget}s", file=sys.stderr)
            return 1
        bootstrap_wall_s = time.time() - t_join
        if base <= 1:
            print(f"FAIL: joiner replayed from genesis (base={base}) — statesync "
                  "did not carry the bootstrap", file=sys.stderr)
            return 1
        print(f"joiner caught up in {bootstrap_wall_s:.1f}s wall; store base={base} "
              f"(snapshot height, not genesis)")

        # gate 2: recorder proves the offer→chunk→restore→handover chain
        events = rpc(jrpc, "dump_flight_recorder")["result"]["events"]
        boot_ms = tracing.statesync_bootstrap_ms(events)
        if boot_ms is None:
            kinds = sorted({e["kind"] for e in events if str(e["kind"]).startswith("statesync")})
            print(f"FAIL: incomplete statesync span chain (saw {kinds})", file=sys.stderr)
            return 1
        print(f"statesync_bootstrap_ms={boot_ms:.1f} (offer→handover, from recorder spans)")

        # gate 3: the joiner FOLLOWS consensus — commits keep landing
        h0 = int(rpc(jrpc, "status")["result"]["sync_info"]["latest_block_height"])
        follow_deadline = time.time() + max(10.0, args.budget - (time.time() - t_join))
        while time.time() < follow_deadline:
            h = int(rpc(jrpc, "status")["result"]["sync_info"]["latest_block_height"])
            if h >= h0 + 2:
                ok = True
                break
            time.sleep(0.5)
        if not ok:
            print("FAIL: joiner caught up but stopped committing", file=sys.stderr)
            return 1
        print(f"joiner following consensus (height {h0} -> {h}); smoke PASSED")
        result = {
            "statesync_bootstrap_ms": round(boot_ms, 1),
            "bootstrap_wall_s": round(bootstrap_wall_s, 2),
            "snapshot_height": base,
            "joiner_height": h,
            "validator_heights": heights(rpc_ports),
        }
    except KeyboardInterrupt:
        pass
    finally:
        for p in procs + ([joiner_proc] if joiner_proc else []):
            p.send_signal(signal.SIGTERM)
        for p in procs + ([joiner_proc] if joiner_proc else []):
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()
    if args.json and result:
        print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
