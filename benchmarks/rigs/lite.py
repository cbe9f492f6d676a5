"""The rig of kind `lite`: a light client syncing through the program's own
proxy (`tendermint_tpu light`, lite2/proxy.py), as a wallet or a relayer
meets it: one downstream client asks the proxy for a header and waits while
it verifies its way there.

One process holds the chip and runs the proxy, started by the program's own
code (see program_start); its primary and its witnesses are child processes
over one generated chain (benchmarks/primary.py), which never import JAX.

What the traffic file states (`kind: lite`): `mode` (`sequence`: every header
between the trusted one and the one asked for is verified, upstream's
BenchmarkSequence; `bisection`: on a static set one jump, two commit checks),
`gap` (how far past the last height each request asks: a number, or
`{"kind": "shuffled", "values": [...]}`: every seed has the same gaps, in
another order), `warm_in_blocks` (heights verified before the window may
open), and, for the chain's generator, `txs_per_block`, `tx_bytes` and
`heights` as every traffic file.  What the configuration states beyond the
chain's: `witnesses` (how many more primaries stand as witnesses; default 1)
and `light` (`trusting_period_h`, default upstream's 168; the trust root is
height 1 and the generator's block id).

The traffic: one downstream client (this process; the proxy runs on the same
event loop, as the node does in `replay`), closed loop, one request
outstanding, asks the proxy's own RPC for `/commit?height=h`, `h` a `gap`
past the last.  The stamps are taken as each answer returns, from the
client's side of the served path: the heights a request made the proxy's own
(all of them in `sequence` mode, the one asked for in `bisection`) share its
stamp.  A "block" of the window is such a height: a signed header and its
validator set, verified and stored.

The guarantee the run holds the deployment to: the proxy answers with a
header only after verifying a commit of over two thirds of the set that its
trusted state vouches for, and after cross-checking it with every witness.
`checks()` has the comparisons; every limit is 0.

The clock: the generator's chain starts at a fixed time in 2023, so under
the wall clock every header has expired.  The proxy is given a clock that
reads the chain's last second plus the time since the rig started: expiry
and drift are checked as they would be by a client that runs while the
chain is young, and are not switched off.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import json
import os
import random
import subprocess
import sys
import time
from typing import Awaitable, Callable, Dict, List, Optional, Sequence

import numpy as np

from benchmarks import chain as chainlib
from benchmarks import harness
from benchmarks.harness import HarnessFailure, log

MODES = ("sequence", "bisection")
TRUSTING_PERIOD_H = 168  # upstream's default `--trusting-period`
PRIMARY_START_DEADLINE_S = 60.0
REQUEST_DEADLINE_S = 60.0  # one request of the downstream client
# the last height asked for stays this many requests (of the widest gap)
# short of the last height the primaries hold
TIP_MARGIN_REQUESTS = 4
# the control `lying_primary` forges the header this many requests past the
# one that ends warm-in: a height inside any window that is not empty
LIE_AFTER_REQUESTS = 6
# the chain the primaries serve while the real one is made: the trust root
# and its commit
PREFIX_HEIGHTS = 2


# ---------------------------------------------------------------------------
# what the files state
# ---------------------------------------------------------------------------


def mode_of(cell: harness.Cell) -> str:
    mode = cell.traffic.get("mode")
    if mode not in MODES:
        raise HarnessFailure(f"traffic {cell.traffic['name']}: `mode` is one of {MODES}, not {mode!r}")
    return mode


def gap_values(traffic: dict) -> List[int]:
    """The gaps a traffic file's `gap` allows, in the file's order."""
    gap = traffic.get("gap")
    if isinstance(gap, int) and gap >= 1:
        return [gap]
    if isinstance(gap, dict) and gap.get("kind") == "shuffled":
        values = [int(v) for v in gap["values"]]
        if values and min(values) >= 1:
            return values
    raise HarnessFailure(
        f"traffic {traffic['name']}: `gap` is a number from 1, or "
        '{"kind": "shuffled", "values": [...]}, not ' + repr(gap)
    )


def schedule(traffic: dict, seed: int, last_height: int, first: int = 1) -> List[int]:
    """The heights the downstream client asks for, in order, from the trust
    root `first` on: each a gap past the last, up to `last_height`.  A
    `shuffled` gap goes through its values in an order drawn from the seed,
    then through them again in another: every seed has the same gaps."""
    values = gap_values(traffic)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 3])
    asked, at = [], first
    while True:
        for gap in (values if len(values) == 1 else rng.permutation(values).tolist()):
            at += gap
            if at > last_height:
                return asked
            asked.append(at)


# ---------------------------------------------------------------------------
# the primaries
# ---------------------------------------------------------------------------


class Primaries:
    """The providers of the light client under test: child processes over
    the generated chain, the primary first, then the witnesses."""

    def __init__(self):
        self.procs: List[subprocess.Popen] = []
        self.addrs: List[str] = []
        self.served: List[dict] = []  # what each said it served, once stopped

    async def start(self, chain_dir: str, config_file: str, seed: int, count: int) -> None:
        args = [sys.executable, os.path.join(harness.BENCH_DIR, "primary.py"), "--chain", chain_dir,
                "--config", config_file, "--seed", str(seed)]
        self.procs = [
            subprocess.Popen(args, env=harness._child_env(), stdin=subprocess.PIPE,
                             stdout=subprocess.PIPE)
            for _ in range(count)
        ]
        loop = asyncio.get_running_loop()
        for proc in self.procs:
            line = await asyncio.wait_for(
                loop.run_in_executor(None, proc.stdout.readline), PRIMARY_START_DEADLINE_S
            )
            if not line:
                raise HarnessFailure("a primary exited before it listened")
            self.addrs.append(json.loads(line)["addr"])

    async def switch(self, chain_dir: str, lie_at: int = 0) -> None:
        """From now on every one of them serves this chain, and with
        `lie_at` the forged header at that height."""
        loop = asyncio.get_running_loop()
        order = json.dumps({"chain": chain_dir, "lie_at": lie_at}).encode() + b"\n"
        for proc in self.procs:
            proc.stdin.write(order)
            proc.stdin.flush()
        for proc in self.procs:
            line = await asyncio.wait_for(
                loop.run_in_executor(None, proc.stdout.readline), PRIMARY_START_DEADLINE_S
            )
            if not line:
                raise HarnessFailure("a primary exited as it was handed the chain")

    def stop(self) -> None:
        """Close each primary's stdin, read what it served, wait for it."""
        for proc in self.procs:
            try:
                out, _ = proc.communicate(timeout=10)  # closes its stdin
            except subprocess.TimeoutExpired:
                proc.kill()
                out, _ = proc.communicate()
            lines = out.splitlines()
            self.served.append(json.loads(lines[-1]) if lines else {"calls": {}, "commits": []})
        self.procs = []


# ---------------------------------------------------------------------------
# the process under test
# ---------------------------------------------------------------------------


async def program_start(**settings):
    """Start what `tendermint_tpu light` starts, by the program's own code:
    `lite2.proxy.start_proxy(**settings)`, the part of `run_proxy` before its
    wait, which builds and starts the `LightProxy` and returns it.  The
    settings are run_proxy's own (`chain_id`, `primary_addr`, `witness_addrs`,
    `laddr`, `trust_height`, `trust_hash`, `trusting_period_s`) and two more,
    `mode` and `now_fn`, handed on to `Client`.

    The program as it stands has no such function (the split is an edit to
    the program, which the PR that brought this rig could not make): there
    the proxy is built here as run_proxy builds it, line for line, from the
    program's own classes.  Nothing else of this rig knows which of the two
    ran."""
    from tendermint_tpu.lite2 import proxy as proxylib

    starter = getattr(proxylib, "start_proxy", None)
    if starter is not None:
        return await starter(**settings)
    from tendermint_tpu.lite2.client import Client, TrustOptions
    from tendermint_tpu.lite2.provider import HTTPProvider

    chain_id = settings["chain_id"]
    client = Client(
        chain_id,
        TrustOptions(
            int(settings["trusting_period_s"] * 1e9), settings["trust_height"],
            settings["trust_hash"],
        ),
        HTTPProvider(chain_id, settings["primary_addr"]),
        witnesses=[HTTPProvider(chain_id, w) for w in settings["witness_addrs"]],
        mode=settings["mode"],
        now_fn=settings["now_fn"],
    )
    proxy = proxylib.LightProxy(client, settings["laddr"])
    await proxy.start()
    return proxy


def chain_clock(cell: harness.Cell) -> Callable[[], int]:
    """The proxy's clock: the chain's last second (no header is later: a
    height's time is under a second past genesis + height seconds) plus the
    time since now."""
    tip_ns = chainlib.GENESIS_TIME_NS + (cell.heights + 1) * chainlib.BLOCK_INTERVAL_NS
    t0 = time.monotonic_ns()
    return lambda: tip_ns + time.monotonic_ns() - t0


async def stop_proxy(proxy) -> None:
    """Stop the proxy and close its providers' connections."""
    try:
        await asyncio.wait_for(proxy.stop(), harness.NODE_STOP_DEADLINE_S)
        client = proxy.client
        for provider in [client.primary, *client.witnesses, *client.demoted_witnesses]:
            close = getattr(provider, "close", None)
            if close is not None:
                await close()
    except Exception as exc:  # the run has its result or its failure by now
        log(f"the proxy did not stop cleanly: {exc!r}")


# ---------------------------------------------------------------------------
# the downstream client
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    height: int  # the height asked for
    heights: Sequence[int]  # the heights an answer makes the proxy's own
    t_sent_ns: int = 0
    t_answered_ns: int = 0
    header: Optional[object] = None  # the answer's header
    error: Optional[str] = None


class Downstream:
    """One client of the proxy's RPC, one request outstanding: asks for the
    schedule's heights in order and stamps each answer as it returns."""

    def __init__(self, client, asked: Sequence[int], mode: str, stamps: harness.Stamps):
        self.client = client
        self.mode = mode
        self.stamps = stamps
        self.todo = collections.deque(asked)
        self.last = 1  # the trust root
        self.done: List[Request] = []

    async def next(self) -> Request:
        """Ask for the next height; the stamp is taken before anything else
        is done with the answer."""
        from tendermint_tpu.rpc import RPCError

        h = self.todo.popleft()
        heights = range(self.last + 1, h + 1) if self.mode == "sequence" else [h]
        req = Request(h, heights, t_sent_ns=time.monotonic_ns())
        try:
            got = await asyncio.wait_for(self.client.commit(h), REQUEST_DEADLINE_S)
        except (RPCError, asyncio.TimeoutError) as exc:
            req.t_answered_ns = time.monotonic_ns()
            req.error = repr(exc)
        else:
            self.stamps.note(heights)
            req.t_answered_ns = self.stamps.times_ns[-1]
            req.header = got["signed_header"].header
            self.last = h
        self.done.append(req)
        return req


# ---------------------------------------------------------------------------
# correct?
# ---------------------------------------------------------------------------


def witness_checks_missed(requests: Sequence[Request], window: harness.Window,
                          witnesses: Sequence[dict]) -> int:
    """Of the requests answered in the window, how many some witness was not
    asked about: by the witness processes' own record of the `/commit` calls
    they served inside the window (one clock for the machine), the worst
    witness's."""
    due = {r.height for r in requests}
    missed = 0
    for served in witnesses:
        asked = {
            h for t_ns, h in served["commits"] if window.t_open_ns < t_ns <= window.t_close_ns
        }
        missed = max(missed, len(due - asked))
    return missed


async def compare_with_reference(
    client, proxy, window: harness.Window, in_window: Sequence[Request], meta: dict, seed: int,
    chain_id: str, vset, history, secret_of: Dict[bytes, bytes],
) -> Dict[str, int]:
    """The comparisons with the plain reference, each a count that must be 0:
    every header the proxy returned in the window against the generator's
    block id for its height; a seeded sample of the commits it accepted,
    read back from it and re-verified signature by signature by the
    reference; the tampered copies of the window's last commit through
    ValidatorSet.verify_commit on the warm table; and that what the stamps
    say was stored is in the proxy's store."""
    if window.blocks == 0:
        return {"reference_not_reached": 1}
    rng = random.Random(seed ^ 0x5EED)
    wrong_blocks = sum(
        1 for r in in_window if r.header.hash().hex() != meta["hashes"][r.height - 1]
    )
    stored = set(proxy.client.store.heights())
    not_stored = sum(1 for h in window.block_heights if h not in stored)
    heights = window.block_heights
    n_commits = harness.sample_size(len(history.at(1)[0]))
    sample = sorted(set(rng.sample(heights, min(n_commits - 1, len(heights))) + [heights[-1]]))
    wrong_commits = 0
    for h in sample:
        sh = (await client.commit(h))["signed_header"]  # answered from the proxy's store
        if sh.header.hash().hex() != meta["hashes"][h - 1]:
            wrong_blocks += 1
        if sh.commit.height != h or harness.accepted_wrongly(
                chain_id, history, sh.commit, meta["hashes"][h - 1]):
            wrong_commits += 1
    commit = sh.commit  # the window's last
    pubs, powers = history.at(commit.height)
    mismatches = harness.tamper_mismatches(
        chain_id, vset, commit, pubs, powers, [secret_of[p] for p in pubs], rng
    )
    return {
        "wrong_block_ids": wrong_blocks, "heights_not_stored": not_stored,
        "commits_accepted_wrongly": wrong_commits, "verdict_mismatches": mismatches,
    }


def checks(
    cell: harness.Cell, window: harness.Window, in_window: Sequence[Request],
    failed: Sequence[Request], min_device_batch: int, compiles: int, last_asked: int,
    tip: int, witnesses: Sequence[dict], lie_at: int, stored: Sequence[int],
) -> Dict[str, int]:
    """The window's rules, each a count that must be 0: the engine's (see
    harness.engine_rules), with a table-path dispatch due for every height
    stored in `sequence` mode and two for every request in `bisection`; no
    request answered with an error (but the one that meets the lie, under
    the control); the schedule still short of the primaries' tip; the window
    not empty; every answer cross-checked with every witness; and under the
    control `lying_primary`, no header stored at or past the lie."""
    due = window.blocks if mode_of(cell) == "sequence" else 2 * len(in_window)
    out = harness.engine_rules(cell, window, min_device_batch, compiles, due)
    margin = TIP_MARGIN_REQUESTS * max(gap_values(cell.traffic))
    out["requests_failed"] = len(failed)
    out["chain_exhausted"] = int(last_asked > tip - margin)
    out["window_empty"] = int(window.blocks == 0)
    if witnesses:
        out["witness_checks_missed"] = witness_checks_missed(in_window, window, witnesses)
    if lie_at:
        out["forged_headers_stored"] = sum(1 for h in stored if h >= lie_at)
    if cell.chips > 1:
        out["unsharded_dispatches"] = harness.unsharded_dispatches(cell, window)
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

FAULTS = ("lying_primary",) + harness.ENGINE_FAULTS


async def run_cell(
    cell: harness.Cell, seed: int, seconds: float, trace: bool, t_start: float,
    faults: Sequence[str] = (), start: Optional[Callable[..., Awaitable]] = None,
) -> dict:
    """One run of one cell of kind `lite`; returns the result object (see
    run.py).  `faults` (harness.ENGINE_FAULTS and `lying_primary`) and
    `start`, a callable that starts the process under test in program_start's
    place and with its arguments, are for the tests and the control."""
    from tendermint_tpu import ops  # noqa: F401 — places the compile cache
    from tendermint_tpu.config import TPUConfig
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.rpc.client import HTTPClient
    from tendermint_tpu.types import Validator, ValidatorSet

    for fault in faults:
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r} for a cell of kind lite")
    mode = mode_of(cell)
    tip = cell.heights - 1  # the last height whose commit the chain holds
    asked = schedule(cell.traffic, seed, tip)
    warm_in = harness.warm_in_blocks(cell.config, cell.traffic)
    n_warm = next((i + 1 for i, h in enumerate(asked) if h - 1 >= warm_in), len(asked))
    lying = "lying_primary" in faults
    if lying and n_warm + LIE_AFTER_REQUESTS >= len(asked):
        raise HarnessFailure("the chain is too short to lie inside a window")
    lie_at = asked[n_warm + LIE_AFTER_REQUESTS] if lying else 0
    n_witnesses = int(cell.config.get("witnesses", 1))
    period_h = cell.config.get("light", {}).get("trusting_period_h", TRUSTING_PERIOD_H)

    clock = harness.CompileClock()
    chain_dir, chain_proc = harness.start_chain(cell, seed)
    prefix = dataclasses.replace(cell, heights=PREFIX_HEIGHTS)
    prefix_dir, prefix_proc = harness.start_chain(prefix, seed)
    primaries = Primaries()
    proxy = client = None
    tracing = False
    try:
        secrets, pubs, powers = chainlib.committee(seed, cell.config)
        chain_id = chainlib.chain_id(cell.config["name"], seed)
        os.makedirs(harness.OUT_DIR, exist_ok=True)
        config_file = os.path.join(harness.OUT_DIR, f"{cell.name}.s{seed}.spec.primary.json")
        with open(config_file, "w") as f:
            json.dump(cell.config, f)

        # -- the primaries over the chain's first heights, the proxy, the engine ----
        trust = await harness.wait_chain(prefix_dir, prefix_proc, harness.CHAIN_DEADLINE_S)
        prefix_proc = None
        await primaries.start(prefix_dir, config_file, seed, 1 + n_witnesses)
        proxy = await (start or program_start)(
            chain_id=chain_id, primary_addr=primaries.addrs[0],
            witness_addrs=primaries.addrs[1:], laddr="tcp://127.0.0.1:0", trust_height=1,
            trust_hash=bytes.fromhex(trust["hashes"][0]), trusting_period_s=period_h * 3600.0,
            mode=mode, now_fn=chain_clock(cell),
        )
        verifier = getattr(proxy, "batch_verifier", None)
        recorder = getattr(proxy, "flight_recorder", None)
        stand_in = next((f for f in faults if f in harness.STAND_INS), None)
        if recorder is None or (verifier is None and stand_in is None):
            raise HarnessFailure("the light client started without its verify engine")
        min_device_batch = (
            verifier.min_device_batch if verifier is not None else TPUConfig().min_device_batch
        )
        watch = harness.EngineWatch(recorder, min_device_batch)
        if stand_in is not None:
            harness.plant_engine_fault(stand_in, recorder, shards=cell.chips)
        vset = ValidatorSet([Validator.new(Ed25519PubKey(p), w) for p, w in zip(pubs, powers)])
        if [v.pub_key.bytes() for v in vset.validators] != pubs:
            raise HarnessFailure("the program orders the validator set otherwise than by address")
        warm_commit = harness.make_commit(chain_id, vset, secrets, seed, cell.config["absent_share"])
        warm = await harness.warm_engine(
            watch, verifier, getattr(proxy, "table_cache", None), chain_id, vset, pubs, powers,
            warm_commit, stand_in,
        )

        # -- the chain itself under the same addresses, warm-in ---------------------
        meta = await harness.wait_chain(chain_dir, chain_proc, harness.CHAIN_DEADLINE_S)
        chain_proc = None
        if meta["hashes"][:1] != trust["hashes"][:1]:
            raise HarnessFailure("the chain does not begin as its first heights alone did")
        history = chainlib.set_history(zip(pubs, powers), meta)
        await primaries.switch(chain_dir, lie_at)
        stamps = harness.Stamps()
        client = HTTPClient(proxy.listen_addr)
        downstream = Downstream(client, asked, mode, stamps)
        t0 = time.monotonic()
        for _ in range(n_warm):
            req = await downstream.next()
            if req.error is not None:
                raise HarnessFailure(f"warm-in: the proxy answered height {req.height} with {req.error}")
            if time.monotonic() - t0 > harness.WARM_IN_DEADLINE_S:
                raise HarnessFailure(
                    f"only {len(stamps.heights)} heights verified {harness.WARM_IN_DEADLINE_S:.0f} s "
                    "after the first was asked for"
                )
            watch.poll()

        # -- the window ---------------------------------------------------------------
        if trace:
            from benchmarks import trace as tracelib

            trace_dir, trace_anchor_ns = harness.open_trace(cell, seed)
            tracing = True
        for fault in faults:
            if fault in harness.ENGINE_FAULTS and fault not in harness.STAND_INS:
                harness.plant_engine_fault(fault, recorder)
        compiles_before = clock.count
        n_events_before = len(watch.events)
        # The window runs from answer to answer: it opens as the next answer
        # returns and closes with the last one that returns within `seconds`
        # of that.  The heights of the opening answer are not the window's.
        log(f"window opens with the next answer, after height {downstream.last}")
        if not downstream.todo:
            raise HarnessFailure("warm-in took the whole chain: it is too short for this cell")
        opening = await downstream.next()
        if opening.error is not None:
            raise HarnessFailure(f"the proxy answered height {opening.height} with {opening.error}")
        first = len(stamps.times_ns) - 1
        n_before = len(downstream.done)
        last_poll = time.monotonic()
        while downstream.todo:
            req = await downstream.next()
            if req.error is not None or req.t_answered_ns - opening.t_answered_ns > seconds * 1e9:
                break
            if time.monotonic() - last_poll >= harness.POLL_PERIOD_S:
                watch.poll()
                last_poll = time.monotonic()
        summary = None
        if trace:
            tracelib.stop()
            tracing = False
        peak = harness.memory_peak()
        watch.poll()
        window = harness.cut_window(
            cell, stamps, first, seconds, watch.events[n_events_before:], [],
            history.membership_heights, recorder=recorder,
        )
        setup_s = window.t_open_ns / 1e9 - t_start
        since_open = downstream.done[n_before:]
        in_window = [
            r for r in since_open if r.error is None and r.t_answered_ns <= window.t_close_ns
        ]
        # under the control the proxy owes an error where the request meets the lie
        failed = [r for r in since_open
                  if r.error is not None and not (lie_at and lie_at in r.heights)]
        log(f"set-up took {setup_s:.1f} s")
        log(f"window closed: {window.blocks} heights in {len(in_window)} requests in "
            f"{window.seconds:.2f} s, heights {window.block_heights[:1]}..{window.block_heights[-1:]} "
            f"of {tip}")
        for r in since_open:
            if r.error is not None:
                log(f"the proxy answered height {r.height} with {r.error}")
        if trace:
            summary = harness.close_trace(cell, trace_dir, trace_anchor_ns, window)

        # -- correct? -----------------------------------------------------------------
        stored = proxy.client.store.heights()
        spare_secrets, spare_pubs = chainlib.standby(seed, cell.config)
        primaries.stop()  # what is compared from here on, the proxy answers from its store
        found = checks(
            cell, window, in_window, failed, watch.min_device_batch, clock.count - compiles_before,
            downstream.done[-1].height, tip, primaries.served[1:], lie_at, stored,
        )
        found.update(await compare_with_reference(
            client, proxy, window, in_window, meta, seed, chain_id, vset, history,
            dict(zip(pubs + spare_pubs, secrets + spare_secrets)),
        ))
        problems = harness.engine_failures(watch.events, watch.min_device_batch, warm=False)
        found["engine_errors"] = max(found["engine_errors"], len(problems))
        latencies = [(r.t_answered_ns - r.t_sent_ns) / 1e6 for r in in_window]
        return harness.result_object(cell, window, found, setup_s, trace, summary, peak, {
            "seed": seed, "warm": warm, "compile_s": round(clock.seconds, 2),
            "persistent_cache_hits": clock.cache_hits, "chain_generate_s": meta["generate_s"],
            "rtt_probe": getattr(verifier, "rtt_probe", None),
            "mode": mode, "gap": cell.traffic["gap"], "witnesses": n_witnesses,
            "requests_in_window": len(in_window),
            "requests_per_s": len(in_window) / window.seconds if window.seconds else None,
            "heights_stored": [window.block_heights[0], window.block_heights[-1]]
            if window.blocks else [],
            "request_ms": {
                f"p{q}": harness.percentile(latencies, q) for q in (50, 90, 95, 99, 100)
            } if latencies else None,
            "lie_at": lie_at or None,
            "errors": [[r.height, r.error] for r in downstream.done if r.error is not None],
            "served": [p["calls"] for p in primaries.served],
        })
    finally:
        if tracing:
            from benchmarks import trace as tracelib

            tracelib.stop()
        if client is not None:
            await client.close()
        if proxy is not None:
            await stop_proxy(proxy)
        primaries.stop()
        harness.release_run(faults, chain_proc, prefix_proc)
        if start is not None:  # the tests' may have installed an engine of its own
            harness.unplant_faults()
