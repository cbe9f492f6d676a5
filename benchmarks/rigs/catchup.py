"""The rig of kind `catchup`: a node that fell behind catches up by
consensus, from peers at the chain's tip, as a validator restarting after a
halt or a sentry after a partition does when it runs with fast sync off.

One process holds the chip and runs the node under test (`Node.start`,
`fast_sync = false`, the default [tpu]); its peers are child processes over
one generated chain (benchmarks/catchup_peer.py), each the program's own
switch and ConsensusReactor standing at the tip, which never import JAX.
What reaches the node is the program's own catch-up: for each height, the
stored commit's precommits as `vote_batch` frames (`_send_commit_votes`),
then the block's parts (`_gossip_catchup_block_parts`).  The node verifies
each frame's fresh votes in one engine call (the vote lane:
`_receive_vote_batch`, `AsyncBatchVerifier.verify_direct`), adds them to its
vote set, enters commit on +2/3 precommits and finalizes once the block's
parts are in, checking the block's LastCommit through the table
(`validate_block`).  It waits on no timeout: a node behind commits as soon
as it can.  Closed loop: the node's own pace.

What the traffic file states (`kind: catchup`): `warm_in_blocks` (heights
committed before the window may open), and for the chain's generator
`txs_per_block`, `tx_bytes` and `heights`, as every traffic file.  What the
configuration states beyond the chain's: `catchup_peers` (how many peers at
the tip; default 2).

Set-up (all of it counted in `setup_s`): generate or load the chain (a child
process, while this one goes on), start the node, warm the engine on the
chain's own set: the table path through `ValidatorSet.verify_commit` and the
vote lane through `verify_direct` on a commit's worth of precommits, each
until the flight recorder shows it on the device; start the peers, dial
them, let `warm_in_blocks` heights commit.  The window opens with the first
height committed after that; a "block" of the window is a height committed,
stamped as the node's event bus publishes NewBlock.

The rules (each a count that must be 0): the engine's (harness.engine_rules:
no failed engine event, no engine-sized batch on the host tier, no compile
or table build inside the window, a table-path dispatch for every height);
`fast_sync_blocks` (no `fastsync.block` span at all: fast sync never ran);
`chain_exhausted` (the window ends TIP_MARGIN_HEIGHTS short of the last
height the peers can serve); `window_empty`.

`correct` (every limit 0):

- `commits_accepted_wrongly`: a seeded sample of the node's seen commits
  (the votes it counted for a height, as its block store keeps them), as
  many as hold SAMPLE_SIGNATURES, the window's last among them, held to the
  plain reference (benchmarks/reference_catchup.py) over the same votes:
  every vote valid by serial OpenSSL verification, more than two thirds of
  the set's power, the chain's block id;
- `wrong_block_ids`: the sampled heights' blocks and the tip's block and
  app hash, against the source chain and the reference kvstore;
- `wrong_reads`: sampled writes read back through `abci_query`;
- `verdict_mismatches`: after the window, with the peers gone, a control
  peer sends four `vote_batch` frames for the node's current height, each
  over a connection of its own: valid precommits of a set of validators
  short of a third of the power, in a round of their own, one of them
  tampered (a flipped bit of R, of S, S + L, or a valid signature over
  another timestamp).  The program's rule is: the frame is dropped and the
  peer stopped.  A frame counts 1 where any of its votes entered the vote
  set, or the peer was not stopped.

The controls: `--fault accept_all` (the engine, table and vote lane, says
yes to everything from the window's opening on) reads `verdict_mismatches`
4; a tree whose program opens no `consensus.height` span is refused before
anything starts (`require_height_spans`, at import: run.py exits 3), and a
node that opens none all the same fails its warm-in.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Callable, Dict, List, Sequence

from benchmarks import chain as chainlib
from benchmarks import harness, reference, reference_catchup
from benchmarks.harness import HarnessFailure, log

PEER_START_DEADLINE_S = 60.0
CONTROL_DEADLINE_S = 10.0  # for the node to stop a control peer
# the window ends this many heights short of the last one the peers can serve
TIP_MARGIN_HEIGHTS = 80
FAULTS = ("stub_device", "host_tier", "accept_all")
# `breakdown.idle_gaps`: what the node does while the device idles outside
# the engine's call and ABCI deliver
IDLE_REST = (
    "consensus outside engine and deliver (frame decode, vote set, parts, store, validation)"
)
HEIGHT_SPAN = "consensus.height"


def require_height_spans() -> None:
    """Refuse a program whose consensus opens no `consensus.height` span:
    the cell's own metrics and its stamps read nothing there."""
    from tendermint_tpu.libs import tracing

    if HEIGHT_SPAN not in getattr(tracing, "STAMPED_KINDS", ()):
        raise HarnessFailure(
            f"the program's consensus opens no {HEIGHT_SPAN} span "
            "(tracing.STAMPED_KINDS lacks it): it cannot run a cell of kind catchup"
        )


require_height_spans()  # at import: run.py refuses such a tree at once (exit 3)


# ---------------------------------------------------------------------------
# the peers
# ---------------------------------------------------------------------------


class Peers:
    """The node's peers at the tip: child processes over the generated chain."""

    def __init__(self):
        self.procs: List[subprocess.Popen] = []
        self.addrs: List[str] = []
        self.served: List[dict] = []  # what each said it sent, once stopped

    async def start(self, chain_dir: str, config_file: str, count: int,
                    sink_channels: Sequence[int]) -> None:
        args = [sys.executable, os.path.join(harness.BENCH_DIR, "catchup_peer.py"),
                "--chain", chain_dir, "--config", config_file,
                "--sink", ",".join(str(c) for c in sink_channels)]
        self.procs = [
            subprocess.Popen(args, env=harness._child_env(), stdin=subprocess.PIPE,
                             stdout=subprocess.PIPE)
            for _ in range(count)
        ]
        loop = asyncio.get_running_loop()
        for proc in self.procs:
            line = await asyncio.wait_for(
                loop.run_in_executor(None, proc.stdout.readline), PEER_START_DEADLINE_S
            )
            if not line:
                raise HarnessFailure("a catch-up peer exited before it listened")
            self.addrs.append(json.loads(line)["addr"])

    def stop(self) -> None:
        """Close each peer's stdin, read what it served, wait for it."""
        for proc in self.procs:
            try:
                out, _ = proc.communicate(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                out, _ = proc.communicate()
            last = json.loads(out.splitlines()[-1]) if out.strip() else {}
            self.served.append(last.get("served", {}))
        self.procs = []


# ---------------------------------------------------------------------------
# the vote lane: its warm-up, its stand-in on the CPU, its fault
# ---------------------------------------------------------------------------


class StubLane:
    """Stands in for the node's AsyncBatchVerifier where the chip is stood in
    for (`stub_device`, `host_tier`): serial host verification, reported as
    the engine reports a flat dispatch on `path`."""

    def __init__(self, recorder, path: str):
        self.recorder = recorder
        self.path = path

    async def verify_direct(self, items) -> List[bool]:
        from tendermint_tpu.crypto import batch as crypto_batch

        pubkeys, msgs, sigs = (list(col) for col in zip(*items)) if items else ([], [], [])
        self.recorder.record("verify.dispatch", n=len(sigs), bucket=len(sigs), path=self.path,
                             host_prep_ms=0.0, device_ms=0.0, shards=1)
        return crypto_batch.host_batch_verify(pubkeys, msgs, sigs)

    def verify_many(self, items) -> list:
        loop = asyncio.get_running_loop()
        return [loop.create_task(self._one(item)) for item in items]

    async def _one(self, item) -> bool:
        return (await self.verify_direct([item]))[0]


def accept_all_votes(reactor) -> None:
    """The control's fault on the vote lane: every call still runs, and
    every answer reads valid."""
    lane = reactor.async_verifier
    direct, many = lane.verify_direct, lane.verify_many

    async def verify_direct(items):
        await direct(items)
        return [True] * len(items)

    def verify_many(items):
        loop = asyncio.get_running_loop()
        futs = many(items)
        done = []
        for fut in futs:
            ok = loop.create_future()
            fut.add_done_callback(lambda _, ok=ok: ok.done() or ok.set_result(True))
            done.append(ok)
        return done

    lane.verify_direct, lane.verify_many = verify_direct, verify_many


async def warm_vote_lane(watch: harness.EngineWatch, lane, chain_id: str, commit,
                         pubs: Sequence[bytes]) -> dict:
    """Drive a commit's precommits through the lane's `verify_direct`, as a
    frame of fresh votes goes, until the recorder shows the flat device path
    for a batch of that size (harness.until_device)."""
    entries = []
    for i, cs in enumerate(commit.signatures):
        if not cs.is_absent():
            vote = commit.get_vote(i)
            entries.append((pubs[i], vote.sign_bytes(chain_id), vote.signature))

    async def call() -> None:
        if not all(await lane.verify_direct(entries)):
            raise HarnessFailure("warm-up: the vote lane rejects a valid precommit")

    return await harness.until_device(
        watch, len(entries), call, harness.WARM_DEADLINE_S, paths=("device",)
    )


# ---------------------------------------------------------------------------
# the control peer
# ---------------------------------------------------------------------------


def minority(powers: Sequence[int]) -> List[int]:
    """Set positions whose power together stays short of a third of the
    whole, the lightest first: votes of theirs can commit nothing."""
    total, held, out = sum(powers), 0, []
    for i in sorted(range(len(powers)), key=lambda i: (powers[i], i)):
        if 3 * (held + powers[i]) >= total:
            break
        held += powers[i]
        out.append(i)
    return sorted(out)


def control_frame(chain_id: str, height: int, round_: int, block_id, vset, secrets,
                  who: Sequence[int], kind: str, bad: int, ts: int):
    """The votes of a control frame: precommits of `who` for `block_id` at
    (height, round), each signed by its validator's key, the one at `bad`
    tampered in the named way (harness.TAMPERS)."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey
    from tendermint_tpu.types import Vote
    from tendermint_tpu.types.canonical import PRECOMMIT_TYPE

    votes = []
    for i in who:
        key = Ed25519PrivateKey.from_private_bytes(secrets[i])
        vote = Vote(type=PRECOMMIT_TYPE, height=height, round=round_, block_id=block_id,
                    timestamp_ns=ts, validator_address=vset.validators[i].address,
                    validator_index=i)
        sig = key.sign(vote.sign_bytes(chain_id))
        if i == bad:
            if kind == "signature_r":
                sig = bytes([sig[0] ^ 1]) + sig[1:]
            elif kind == "signature_s":
                sig = sig[:32] + bytes([sig[32] ^ 1]) + sig[33:]
            elif kind == "noncanonical_s":
                s = int.from_bytes(sig[32:], "little") + reference.ED25519_L
                sig = sig[:32] + s.to_bytes(32, "little")
            elif kind == "message":
                other = dataclasses.replace(vote, timestamp_ns=ts + 1)
                sig = key.sign(other.sign_bytes(chain_id))
            else:
                raise ValueError(kind)
        votes.append(dataclasses.replace(vote, signature=sig))
    return votes


async def send_control_frame(node, votes, sink_channels: Sequence[int],
                             entered: Callable[[], bool]) -> bool:
    """Send one vote_batch frame from a new peer the node dials; True where
    the node stopped that peer within CONTROL_DEADLINE_S (False as soon as
    `entered` says the frame's votes are in the node's vote set)."""
    from tendermint_tpu.consensus.reactor import VOTE_CHANNEL
    from tendermint_tpu.encoding import codec
    from tendermint_tpu.p2p import ChannelDescriptor, NodeInfo, NodeKey, Reactor, Switch, Transport

    class Sink(Reactor):
        def get_channels(self):
            return [ChannelDescriptor(id=c, priority=1) for c in sink_channels]

    key = NodeKey.generate()
    info = NodeInfo(node_id=key.id, network=node.genesis_doc.chain_id, moniker="bench-control",
                    gossip_version=1)
    switch = Switch(Transport(key, info), allow_duplicate_ip=True)
    switch.add_reactor("SINK", Sink("sink"))
    addr = await switch.transport.listen("127.0.0.1:0")
    info.listen_addr = addr
    await switch.start()
    try:
        if await node.switch.dial_peer(f"{key.id}@{addr}") is None:
            raise HarnessFailure("the node could not dial the control peer")
        t0 = time.monotonic()
        while key.id not in node.consensus_reactor.peer_states or node.switch.node_id not in switch.peers:
            if time.monotonic() - t0 > CONTROL_DEADLINE_S:
                raise HarnessFailure("the node did not take the control peer in")
            await asyncio.sleep(0.01)
        frame = codec.dumps({"k": "vote_batch", "votes": [v.wire() for v in votes]})
        if not await switch.peers[node.switch.node_id].send(VOTE_CHANNEL, frame):
            raise HarnessFailure("the control peer could not send its frame")
        while time.monotonic() - t0 < CONTROL_DEADLINE_S and not entered():
            if key.id not in node.switch.peers:
                return True
            await asyncio.sleep(0.01)
        return False
    finally:
        await switch.stop()


async def verdict_mismatches(node, chain_id: str, meta: dict, secrets, powers,
                             sink_channels: Sequence[int], rng: random.Random) -> int:
    """The four control frames (see the module's docstring); how many of
    them the node did not refuse as the program's rule says."""
    from tendermint_tpu.types import BlockID, PartSetHeader

    cs = node.consensus
    height = cs.rs.height
    vset = cs.rs.validators
    block_hash = bytes.fromhex(meta["hashes"][height - 1])
    total, parts_hash = meta["parts"][height - 1]
    block_id = BlockID(block_hash, PartSetHeader(total, bytes.fromhex(parts_hash)))
    who = minority(powers)
    if len(who) < 2:
        raise HarnessFailure("no two validators hold less than a third of the power")
    kinds = list(harness.TAMPERS)
    rng.shuffle(kinds)
    ts = chainlib.GENESIS_TIME_NS + height * chainlib.BLOCK_INTERVAL_NS
    mismatches = 0
    for k, kind in enumerate(kinds):
        round_ = cs.rs.round + 2 + k  # a round of the frame's own: no vote of it is held
        bad = rng.choice(who)
        votes = control_frame(chain_id, height, round_, block_id, vset, secrets, who, kind, bad, ts)

        def entered(round_=round_) -> bool:
            held = cs.rs.votes.precommits(round_) if cs.rs.height == height else None
            return held is not None and any(held.get_by_index(i) is not None for i in who)

        stopped = await send_control_frame(node, votes, sink_channels, entered)
        if entered() or not stopped:
            log(f"control frame {kind} at #{bad}: votes entered the set {entered()}, "
                f"peer stopped {stopped}")
            mismatches += 1
    return mismatches


# ---------------------------------------------------------------------------
# correct?
# ---------------------------------------------------------------------------


def seen_commit_wrong(chain_id: str, members, commit, meta: dict) -> bool:
    """Whether a seen commit the node stored fails the reference over its
    own votes: a vote the reference drops, two thirds not reached, or
    another block than the chain's."""
    h = commit.height
    arrivals = [
        reference_catchup.Precommit(
            index=i, address=cs.validator_address, height=h, round=commit.round,
            block_hash=commit.block_id.hash, parts_total=commit.block_id.parts_header.total,
            parts_hash=commit.block_id.parts_header.hash, timestamp_ns=cs.timestamp_ns,
            signature=cs.signature,
        )
        for i, cs in enumerate(commit.signatures) if not cs.is_absent()
    ]
    got = reference_catchup.tally(chain_id, members, h, commit.round, arrivals)
    total, parts_hash = meta["parts"][h - 1]
    chains = (bytes.fromhex(meta["hashes"][h - 1]), total, bytes.fromhex(parts_hash))
    return bool(got.dropped) or got.block != chains


async def compare_with_reference(client, node, window: harness.Window, meta: dict, seed: int,
                                  chain_id: str, members, rng: random.Random) -> Dict[str, int]:
    """The comparisons with the plain reference but the control frames' (see
    the module's docstring), each a count that must be 0."""
    heights = window.block_heights
    n_commits = harness.sample_size(len(members))
    sample = sorted(set(rng.sample(heights, min(n_commits - 1, len(heights))) + [heights[-1]]))
    status = (await client.status())["sync_info"]
    tip = status["latest_block_height"]
    n_txs = meta["txs_per_block"]
    wrong_blocks = int(tip < heights[-1])
    if status["latest_app_hash"] != reference.kvstore_app_hash(n_txs * (tip - 1), tip - 1):
        wrong_blocks += 1
    if status["latest_block_hash"].hex() != meta["hashes"][tip - 1]:
        wrong_blocks += 1
    wrong_commits = 0
    for h in sample:
        got = await client.block(h)
        if got["block_id"] is None or got["block_id"]["hash"].hex() != meta["hashes"][h - 1]:
            wrong_blocks += 1
        commit = node.block_store.load_seen_commit(h)
        if commit is None or commit.height != h or seen_commit_wrong(chain_id, members, commit, meta):
            wrong_commits += 1
    wrong_reads = 0
    for _ in range(harness.SAMPLE_WRITES):
        h = rng.choice(heights)
        key, value = chainlib.tx_key_value(rng.choice(chainlib.block_txs(seed, h, window.cell.traffic)))
        if (await client.abci_query(data=key))["response"]["value"] != value:
            wrong_reads += 1
    return {"wrong_block_ids": wrong_blocks, "commits_accepted_wrongly": wrong_commits,
            "wrong_reads": wrong_reads}


def ring_count(recorder, kind: str) -> int:
    return len(recorder.events(kinds=[kind]))


def span_coverage(recorder, window: harness.Window) -> dict:
    """Of the window's heights whose NewBlock the ring still holds the time
    of, how many lack a `consensus.height` span, and a `consensus.vote_batch`
    span (the ring holds the window's last few hundred heights)."""
    ring = recorder.events(kinds=["consensus."])
    if not ring:
        return {"heights_held": 0}
    held = [h for h, t in zip(window.block_heights, window.block_times)
            if t * 1e9 >= ring[0]["t_ns"]]
    spans = {ev["id"] for ev in ring if ev["kind"] == HEIGHT_SPAN}
    frames = {ev["id"] for ev in ring if ev["kind"] == "consensus.vote_batch"}
    return {"heights_held": len(held),
            "without_height_span": sum(1 for h in held if h not in spans),
            "without_vote_batch_span": sum(1 for h in held if h not in frames)}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


async def run_cell(
    cell: harness.Cell, seed: int, seconds: float, trace: bool, t_start: float,
    faults: Sequence[str] = (), configure=None,
) -> dict:
    """One run of one cell of kind `catchup`; returns the result object (see
    run.py).  `faults` (FAULTS) and `configure` (a callable on the node's
    Config, after the rig's settings) are for the tests and the control."""
    require_height_spans()
    for fault in faults:
        if fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r} for a cell of kind catchup")
    from tendermint_tpu import ops  # noqa: F401 — places the compile cache
    from tendermint_tpu.node import Node
    from tendermint_tpu.rpc.client import HTTPClient
    from tendermint_tpu.types.events import EVENT_NEW_BLOCK, query_for_event

    gc.set_threshold(50_000, 50, 25)  # as `cli node` sets it
    clock = harness.CompileClock()
    chain_dir, chain_proc = harness.start_chain(cell, seed)
    peers = Peers()
    home = tempfile.mkdtemp(prefix="bench-node-")
    node = None
    tracing_on = False
    warm_in = harness.warm_in_blocks(cell.config, cell.traffic)
    try:
        secrets, pubs, powers = chainlib.committee(seed, cell.config)

        def settings(cfg):
            cfg.base.fast_sync = False  # the deployment: consensus from the start
            if configure is not None:
                configure(cfg)

        cfg = harness.node_config(cell, home, settings)
        node = Node(cfg, harness.genesis(cell, seed, pubs, powers), priv_validator=None)
        await node.start()
        stand_in = next((f for f in faults if f in harness.STAND_INS), None)
        if node.blockchain_reactor.fast_sync or node.consensus_reactor.wait_sync:
            raise HarnessFailure("the node started in fast sync")
        if stand_in is not None:
            harness.plant_fault(stand_in, node, shards=cell.chips)
            node.consensus_reactor.async_verifier = StubLane(
                node.flight_recorder, "device" if stand_in == "stub_device" else "host")
        elif node.batch_verifier is None or node.consensus_reactor.async_verifier is None:
            raise HarnessFailure("the node started without its verify engine")
        min_device_batch = (
            node.batch_verifier.min_device_batch if node.batch_verifier is not None
            else cfg.tpu.min_device_batch
        )
        stamps = harness.BlockStamps(lambda: 0)
        sub = await node.event_bus.subscribe("bench", query_for_event(EVENT_NEW_BLOCK))
        sub.queue = stamps
        watch = harness.EngineWatch(node.flight_recorder, min_device_batch)

        # -- warm the engine on this chain's set: the table, then the vote lane ----
        chain_id = node.genesis_doc.chain_id
        vset = node.consensus.rs.validators
        if [v.pub_key.bytes() for v in vset.validators] != pubs:
            raise HarnessFailure("the node's validator set is not the generated committee")
        warm_commit = harness.make_commit(chain_id, vset, secrets, seed, cell.config["absent_share"])
        warm = await harness.warm_engine(
            watch, node.batch_verifier, node.table_cache, chain_id, vset, pubs, powers,
            warm_commit, stand_in,
        )
        if stand_in != "host_tier":
            warm["vote_lane"] = await warm_vote_lane(
                watch, node.consensus_reactor.async_verifier, chain_id, warm_commit, pubs)
            if node.batch_verifier is not None:
                await harness.settle(node.batch_verifier, node.table_cache, watch,
                                     harness.WARM_DEADLINE_S)
        deliver = harness.DeliverSpans(node.consensus.block_exec.proxy_app) if trace else None

        # -- the peers, warm-in ----------------------------------------------------------
        meta = await harness.wait_chain(chain_dir, chain_proc, harness.CHAIN_DEADLINE_S)
        chain_proc = None
        last_servable = meta["heights"] - 1  # its commit rides in the last block
        if last_servable - TIP_MARGIN_HEIGHTS <= warm_in:
            raise HarnessFailure("the chain is too short for this cell")
        config_file = os.path.join(harness.OUT_DIR, f"{cell.name}.s{seed}.spec.peer.json")
        os.makedirs(harness.OUT_DIR, exist_ok=True)
        with open(config_file, "w") as f:
            json.dump(cell.config, f)
        ours = {c.id for c in node.consensus_reactor.get_channels()}
        sink = sorted(d.id for d in node.switch.channel_descs if d.id not in ours)
        await peers.start(chain_dir, config_file, int(cell.config.get("catchup_peers", 2)), sink)
        for addr in peers.addrs:
            if await node.switch.dial_peer(addr, persistent=True) is None:
                raise HarnessFailure(f"could not dial the catch-up peer {addr}")
        t0 = time.monotonic()
        while len(stamps.times_ns) < warm_in:
            if time.monotonic() - t0 > harness.WARM_IN_DEADLINE_S:
                raise HarnessFailure(
                    f"only {len(stamps.times_ns)} heights committed "
                    f"{harness.WARM_IN_DEADLINE_S:.0f} s after the peers were dialed"
                )
            await asyncio.sleep(0.02)
            watch.poll()
        if ring_count(node.flight_recorder, HEIGHT_SPAN) == 0:
            raise HarnessFailure(
                f"{len(stamps.times_ns)} heights committed and no {HEIGHT_SPAN} span: "
                "the program's consensus opens none"
            )
        held = harness.connections(node)

        # -- the window -------------------------------------------------------------------
        if trace:
            from benchmarks import trace as tracelib

            trace_dir, trace_anchor_ns = harness.open_trace(cell, seed)
            tracing_on = True
        if "accept_all" in faults:
            harness.plant_engine_fault("accept_all", node.flight_recorder)
            accept_all_votes(node.consensus_reactor)
        compiles_before = clock.count
        n_events_before = len(watch.events)
        t_decided_ns = time.monotonic_ns()
        n_before = len(stamps.times_ns)
        log(f"window opens with the next height, after {stamps.heights[-1]}")

        def window_over() -> bool:
            if len(stamps.times_ns) == n_before:  # not open yet
                return time.monotonic_ns() - t_decided_ns >= harness.WARM_IN_DEADLINE_S * 1e9
            return time.monotonic_ns() - stamps.times_ns[n_before] >= seconds * 1e9

        while not window_over():
            await asyncio.sleep(harness.POLL_PERIOD_S)
            watch.poll()
        summary = None
        if trace:
            tracelib.stop()
            tracing_on = False
        peak = harness.memory_peak()
        peers_lost = harness.connections(node) != held
        peers.stop()
        watch.poll()
        if len(stamps.times_ns) == n_before:
            raise HarnessFailure(
                f"no height committed for {harness.WARM_IN_DEADLINE_S:.0f} s once warm-in was over"
            )
        window = harness.cut_window(
            cell, stamps, n_before, seconds, watch.events[n_events_before:],
            deliver.spans if deliver else [], recorder=node.flight_recorder,
        )
        setup_s = window.t_open_ns / 1e9 - t_start
        coverage = span_coverage(node.flight_recorder, window)
        log(f"set-up took {setup_s:.1f} s")
        log(f"window closed: {window.blocks} heights in {window.seconds:.2f} s, heights "
            f"{window.block_heights[:1]}..{window.block_heights[-1:]} of {last_servable}")
        if trace:
            summary = harness.close_trace(cell, trace_dir, trace_anchor_ns, window)

        # -- correct? ---------------------------------------------------------------------
        checks = harness.engine_rules(
            cell, window, watch.min_device_batch, clock.count - compiles_before, window.blocks)
        checks["fast_sync_blocks"] = max(
            ring_count(node.flight_recorder, "fastsync.block"),
            getattr(node.blockchain_reactor, "blocks_synced", 0),
        )
        checks["chain_exhausted"] = int(stamps.heights[-1] > last_servable - TIP_MARGIN_HEIGHTS)
        checks["window_empty"] = int(window.blocks == 0)
        if cell.chips > 1:
            checks["unsharded_dispatches"] = harness.unsharded_dispatches(cell, window)
        rng = random.Random(seed ^ 0x5EED)
        members = reference_catchup.members(
            [v.address for v in vset.validators], powers, pubs)
        if window.blocks:
            async with HTTPClient(node.rpc_server.listen_addr) as client:
                checks.update(await compare_with_reference(
                    client, node, window, meta, seed, chain_id, members, rng))
        else:
            checks["reference_not_reached"] = 1
        await settle_height(node)
        checks["verdict_mismatches"] = await verdict_mismatches(
            node, chain_id, meta, secrets, powers, sorted(ours | set(sink)), rng)
        problems = harness.engine_failures(watch.events, watch.min_device_batch, warm=False)
        checks["engine_errors"] = max(checks["engine_errors"], len(problems))
        return harness.result_object(cell, window, checks, setup_s, trace, summary, peak, {
            "seed": seed, "warm": warm, "compile_s": round(clock.seconds, 2),
            "persistent_cache_hits": clock.cache_hits, "chain_generate_s": meta["generate_s"],
            "rtt_probe": getattr(node.batch_verifier, "rtt_probe", None),
            "heights_committed": [window.block_heights[0], window.block_heights[-1]]
            if window.blocks else [],
            "peers_lost_in_window": peers_lost, "served": peers.served,
            "span_coverage": coverage,
            "block_interval_ms": {
                f"p{q}": harness.percentile(harness.block_intervals_ms(window), q)
                for q in (50, 90, 99, 100)
            } if window.blocks else None,
        }, idle_rest=IDLE_REST)
    finally:
        if tracing_on:
            from benchmarks import trace as tracelib

            tracelib.stop()
        peers.stop()
        if node is not None and node.is_running:
            try:
                await asyncio.wait_for(node.stop(), harness.NODE_STOP_DEADLINE_S)
            except Exception as exc:
                log(f"the node did not stop cleanly: {exc!r}")
        shutil.rmtree(home, ignore_errors=True)
        harness.release_run(faults, chain_proc)


async def settle_height(node, quiet_s: float = 0.5, deadline_s: float = 10.0) -> None:
    """With the peers gone, wait until the node's height has not moved for
    `quiet_s`: what it had in hand when they left is applied."""
    t0 = time.monotonic()
    last, since = node.consensus.rs.height, time.monotonic()
    while time.monotonic() - since < quiet_s and time.monotonic() - t0 < deadline_s:
        await asyncio.sleep(0.05)
        if node.consensus.rs.height != last:
            last, since = node.consensus.rs.height, time.monotonic()
