#!/usr/bin/env python
"""Lite smoke: the multi-tenant light-client gateway against a real
4-validator multi-process localnet — the `make lite-smoke` acceptance rig
for the liteserve subsystem.

Topology: 4 validator nodes; an ADVERSARIAL FORWARDING PROXY (in this
process) in front of node0; a liteserve gateway subprocess whose primary
is the proxy and whose witnesses are nodes 1-3.

Phases:

  fleet     >= 64 concurrent bisecting tenants (tools/loadgen.py --lite
            flavor) create sessions at a shared trust root and hammer
            verified-commit queries over random heights — the shared
            store + verification cache must absorb the fan-in
            (lite_cache_hit_ratio, lite_verify_coalesce_ratio, every
            session sustained), while the PR 5 chaos invariant checker
            scrapes the validator net underneath (agreement, no height
            regression: the gateway must cost the chain nothing)
  adversary the proxy starts serving a TWIN-SIGNED conflicting header
            (all four validator keys, TwinSigner — bypassing the
            double-sign guard) for a fresh height: the gateway's witness
            cross-check must detect the divergence, roll back nothing
            into the shared store, demote the primary and promote an
            honest witness — and keep serving every other tenant
            throughout
  settle    the validator net must still agree; a fresh tenant asking
            about the forged height must get the REAL header

With --json the last stdout line carries `lite_bisections_per_sec`,
`lite_cache_hit_ratio`, `lite_verify_coalesce_ratio`,
`lite_sessions_sustained` and `lite_diverged_detect_ms` — the numbers
bench.py reports.
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
from tendermint_tpu.chaos.twin import TwinSigner  # noqa: E402
from tendermint_tpu.privval.file import FilePV  # noqa: E402
from tendermint_tpu.rpc.jsonrpc import from_jsonable, make_response  # noqa: E402
from tendermint_tpu.tools import loadgen  # noqa: E402
from tendermint_tpu.types import (  # noqa: E402
    BlockID,
    Header,
    PartSetHeader,
    SignedHeader,
    Vote,
    VoteSet,
)
from tendermint_tpu.types.canonical import PRECOMMIT_TYPE  # noqa: E402


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


def spawn_node(home: str, env) -> subprocess.Popen:
    log = open(os.path.join(home, "node.log"), "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cli", "--home", home, "node"],
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )


class AdversarialPrimary:
    """A forwarding JSON-RPC proxy in front of node0.  Unarmed it is a
    byte-transparent relay; armed it answers `commit` for specific
    heights with a twin-signed conflicting header — the lying-primary
    attack the witness cross-check exists for."""

    def __init__(self, upstream_port: int):
        self.upstream = f"http://127.0.0.1:{upstream_port}/"
        self.forged = {}  # height -> SignedHeader (twin-signed)
        self.hijacked = 0
        self._runner = None
        self._session = None
        self.port = 0

    async def start(self, port: int) -> None:
        import aiohttp
        from aiohttp import web

        self._session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=10.0)
        )
        app = web.Application()
        app.router.add_post("/", self._handle)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001

    async def stop(self) -> None:
        if self._session is not None:
            await self._session.close()
        if self._runner is not None:
            await self._runner.cleanup()

    async def _handle(self, request):
        from aiohttp import web

        body = await request.read()
        if self.forged:
            try:
                req = json.loads(body)
            except ValueError:
                req = None
            if isinstance(req, dict) and req.get("method") == "commit":
                h = (req.get("params") or {}).get("height")
                sh = self.forged.get(h)
                if sh is not None:
                    self.hijacked += 1
                    return web.json_response(make_response(
                        req.get("id"), {"signed_header": sh, "canonical": True}
                    ))
        async with self._session.post(self.upstream, data=body) as r:
            return web.Response(body=await r.read(), content_type="application/json")


def forge_twin_header(homes, chain_id: str, real_sh, vset) -> SignedHeader:
    """The twin attack at header granularity: copy the real header at this
    height, flip its app_hash, and re-commit the new BlockID with ALL
    validator keys wrapped in TwinSigner (which signs anything, bypassing
    the last-sign-state guard a correct validator relies on)."""
    real = real_sh.header
    forged = Header(
        version_block=real.version_block,
        version_app=real.version_app,
        chain_id=real.chain_id,
        height=real.height,
        time_ns=real.time_ns,
        last_block_id=real.last_block_id,
        last_commit_hash=real.last_commit_hash,
        data_hash=real.data_hash,
        validators_hash=real.validators_hash,
        next_validators_hash=real.next_validators_hash,
        consensus_hash=real.consensus_hash,
        app_hash=b"\xde\xad\xbe\xef" * 8,
        last_results_hash=real.last_results_hash,
        evidence_hash=real.evidence_hash,
        proposer_address=real.proposer_address,
    )
    assert forged.hash() != real.hash()
    twins = []
    for home in homes:
        pv = FilePV.load(
            os.path.join(home, "config", "priv_validator_key.json"),
            os.path.join(home, "data", "priv_validator_state.json"),
        )
        twins.append(TwinSigner(pv))
    bid = BlockID(forged.hash(), PartSetHeader(1, forged.hash()))
    vs = VoteSet(chain_id, forged.height, 0, PRECOMMIT_TYPE, vset)
    for twin in twins:
        idx, _ = vset.get_by_address(twin.address())
        v = Vote(
            type=PRECOMMIT_TYPE,
            height=forged.height,
            round=0,
            block_id=bid,
            timestamp_ns=real.time_ns + 1,
            validator_address=twin.address(),
            validator_index=idx,
        )
        twin.sign_vote(chain_id, v)
        vs.add_vote(v)
    return SignedHeader(forged, vs.make_commit())


async def lite_rpc(http, base: str, method: str, **params):
    async with http.post(f"http://{base}/", data=json.dumps(
        {"jsonrpc": "2.0", "id": 1, "method": method, "params": params}
    )) as resp:
        return await resp.json()


async def run(args, homes, ports, procs, env) -> dict:
    import aiohttp

    from tendermint_tpu.lite2 import HTTPProvider

    checker = InvariantChecker(4)
    result = {}
    failures = []

    # -- startup ----------------------------------------------------------
    deadline = time.time() + 120.0
    while time.time() < deadline:
        hs = [height_of(p) for p in ports]
        if all(h is not None and h >= 4 for h in hs):
            break
        if any(p.poll() is not None for p in procs):
            raise RuntimeError("a node died during startup")
        await asyncio.sleep(0.5)
    else:
        raise RuntimeError(f"startup timeout: heights {[height_of(p) for p in ports]}")
    print(f"localnet ready, heights {[height_of(p) for p in ports]}")

    with open(os.path.join(homes[0], "config", "genesis.json")) as fh:
        chain_id = json.load(fh)["chain_id"]

    node0 = HTTPProvider(chain_id, f"127.0.0.1:{ports[0]}")
    root_sh = await node0.signed_header(2)
    trust_hash = root_sh.header.hash().hex()

    # -- adversarial proxy + gateway subprocess ----------------------------
    proxy = AdversarialPrimary(ports[0])
    await proxy.start(args.base_port + 90)
    ls_port = args.base_port + 91
    ls_log = open(os.path.join(os.path.dirname(homes[0]), "liteserve.log"), "ab")
    ls_proc = subprocess.Popen(
        [sys.executable, "-m", "tendermint_tpu.cli", "liteserve",
         "--chain-id", chain_id,
         "--primary", f"127.0.0.1:{proxy.port}",
         "--witnesses", ",".join(f"127.0.0.1:{p}" for p in ports[1:]),
         "--laddr", f"tcp://127.0.0.1:{ls_port}",
         "--height", "2", "--hash", trust_hash,
         "--witness-quorum", "2", "--witness-timeout", "5.0"],
        env=env, stdout=ls_log, stderr=subprocess.STDOUT,
    )
    ls_base = f"127.0.0.1:{ls_port}"

    http = aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=20.0))
    try:
        deadline = time.time() + 60.0
        while time.time() < deadline:
            if ls_proc.poll() is not None:
                raise RuntimeError("liteserve died during startup (see liteserve.log)")
            try:
                res = await lite_rpc(http, ls_base, "lite_status")
                if "result" in res:
                    break
            except aiohttp.ClientError:
                pass
            await asyncio.sleep(0.3)
        else:
            raise RuntimeError("liteserve startup timeout")
        print(f"liteserve ready on {ls_base}")

        # checker scraper underneath everything (executor: urllib is sync)
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

        # -- phase 1: the tenant fleet ------------------------------------
        fleet = await loadgen.run_lite_load(
            ls_base,
            sessions=args.sessions,
            duration=args.load_duration,
            trust_height=2,
            trust_hash=trust_hash,
        )
        print(
            f"fleet: {fleet['lite_sessions_sustained']}/{fleet['lite_sessions']} "
            f"sessions sustained, {fleet['lite_bisections_per_sec']} verified "
            f"queries/s, hit ratio {fleet['lite_cache_hit_ratio']}, coalesce "
            f"ratio {fleet['lite_verify_coalesce_ratio']}, latency "
            f"{fleet['lite_commit_latency_ms']}"
        )

        # -- phase 2: the adversarial primary -----------------------------
        # pick a FRESH height (not yet in the gateway's verified span) and
        # wait for the chain to commit it
        status = (await lite_rpc(http, ls_base, "lite_status"))["result"]
        target = int(status["latest_trusted_height"]) + 3
        deadline = time.time() + 60.0
        while time.time() < deadline:
            tips = [h for h in (height_of(p) for p in ports) if h is not None]
            if tips and max(tips) >= target + 1:
                break
            await asyncio.sleep(0.3)
        real_sh = await node0.signed_header(target)
        vset = await node0.validator_set(target)
        forged = forge_twin_header(homes, chain_id, real_sh, vset)
        proxy.forged[target] = forged
        print(f"adversary armed: twin-signed conflicting header at height {target}")

        victim = (await lite_rpc(
            http, ls_base, "lite_session_new", trust_height=2, trust_hash=trust_hash,
        ))["result"]["session"]
        bystander = (await lite_rpc(
            http, ls_base, "lite_session_new", trust_height=2, trust_hash=trust_hash,
        ))["result"]["session"]

        t0 = time.monotonic()
        res = await lite_rpc(http, ls_base, "lite_commit", session=victim,
                             height=target)
        detect_ms = round((time.monotonic() - t0) * 1e3, 1)
        served_real = False
        if "result" in res:
            got = from_jsonable(res["result"])["signed_header"]
            served_real = got.header.hash() == real_sh.header.hash()
        status = (await lite_rpc(http, ls_base, "lite_status"))["result"]
        verify = status["verify"]
        print(
            f"adversary phase: detect+recover {detect_ms} ms, diverged "
            f"{verify['diverged_detected']}, primary replacements "
            f"{verify['primary_replacements']} (demoted: "
            f"{verify['demoted_primaries']}), proxy hijacks {proxy.hijacked}, "
            f"served real header: {served_real}"
        )

        # bystander keeps being served during/after the incident, and a
        # FRESH tenant asking the forged height gets the real chain
        by = await lite_rpc(http, ls_base, "lite_commit", session=bystander,
                            height=target - 1)
        fresh = (await lite_rpc(
            http, ls_base, "lite_session_new", trust_height=2, trust_hash=trust_hash,
        ))["result"]["session"]
        re_res = await lite_rpc(http, ls_base, "lite_commit", session=fresh,
                                height=target)
        re_real = (
            "result" in re_res
            and from_jsonable(re_res["result"])["signed_header"].header.hash()
            == real_sh.header.hash()
        )

        # -- settle -------------------------------------------------------
        await asyncio.sleep(args.settle)
        stop.set()
        await scr

        # -- verdict ------------------------------------------------------
        if checker.violations:
            failures.append(f"invariant violations: {checker.violations}")
        if fleet["lite_sessions_sustained"] < args.sessions:
            failures.append(
                f"only {fleet['lite_sessions_sustained']}/{args.sessions} "
                "sessions sustained"
            )
        if fleet["lite_cache_hit_ratio"] <= 0.5:
            failures.append(
                f"cache hit ratio {fleet['lite_cache_hit_ratio']} <= 0.5: the "
                "shared store is not absorbing the fan-in"
            )
        if fleet["lite_verify_coalesce_ratio"] <= 0:
            failures.append("no verification coalescing observed")
        if fleet["lite_transport_errors"] > 0.05 * max(1, fleet["lite_requests_completed"]):
            failures.append(
                f"{fleet['lite_transport_errors']} transport errors (silent drops)"
            )
        if proxy.hijacked <= 0:
            failures.append("the adversarial proxy was never consulted")
        if verify["diverged_detected"] < 1:
            failures.append("divergence was not detected")
        if verify["primary_replacements"] < 1:
            failures.append("the lying primary was not demoted")
        if not served_real:
            failures.append(
                "the victim tenant was not served the real header after recovery"
            )
        if "result" not in by:
            failures.append(f"bystander tenant failed during the incident: {by}")
        if not re_real:
            failures.append("a fresh tenant saw poisoned state at the forged height")
        if len(checker.agreed_heights()) < 3:
            failures.append("too few heights cross-checked for agreement")

        result = {
            "metric": "lite_smoke",
            "lite_bisections_per_sec": fleet["lite_bisections_per_sec"],
            "lite_cache_hit_ratio": fleet["lite_cache_hit_ratio"],
            "lite_verify_coalesce_ratio": fleet["lite_verify_coalesce_ratio"],
            "lite_sessions_sustained": fleet["lite_sessions_sustained"],
            "lite_diverged_detect_ms": detect_ms,
            "lite_commit_latency_ms": fleet["lite_commit_latency_ms"],
            "lite_requests_completed": fleet["lite_requests_completed"],
            "lite_throttled": fleet["lite_throttled"],
            "diverged_detected": verify["diverged_detected"],
            "primary_replacements": verify["primary_replacements"],
            "proxy_hijacks": proxy.hijacked,
            "heights": [height_of(p) for p in ports],
            **checker.summary(),
        }
    finally:
        await http.close()
        await node0.close()
        if ls_proc.poll() is None:
            ls_proc.send_signal(signal.SIGTERM)
            try:
                ls_proc.wait(10)
            except subprocess.TimeoutExpired:
                ls_proc.kill()
        await proxy.stop()

    return result, failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--build-dir", default="./build-lite")
    ap.add_argument("--base-port", type=int, default=33656)
    ap.add_argument("--sessions", type=int, default=64)
    ap.add_argument("--load-duration", type=float, default=12.0)
    ap.add_argument("--settle", type=float, default=4.0)
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

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [spawn_node(h, env) for h in homes]

    ok = False
    result = {}
    try:
        result, failures = asyncio.run(run(args, homes, ports, procs, env))
        if failures:
            print("LITE SMOKE FAILED:", file=sys.stderr)
            for f in failures:
                print(f"  - {f}", file=sys.stderr)
        else:
            print(
                f"lite smoke ok: {result['lite_sessions_sustained']} sessions "
                f"sustained at {result['lite_bisections_per_sec']} verified "
                f"queries/s, hit ratio {result['lite_cache_hit_ratio']}, "
                f"coalesce ratio {result['lite_verify_coalesce_ratio']}, "
                f"divergence detected+recovered in "
                f"{result['lite_diverged_detect_ms']} ms, agreement over "
                f"{result.get('heights_checked', 0)} heights"
            )
            ok = True
    except Exception as e:  # noqa: BLE001 — the rig reports, then fails
        print(f"LITE SMOKE ERROR: {e!r}", file=sys.stderr)
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
