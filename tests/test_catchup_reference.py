"""Catch-up by consensus against the plain reference
(benchmarks/reference_catchup.py): the program's node, fast sync off, behind
two catch-up peers in this process (benchmarks/catchup_peer.py: the
program's own ConsensusReactor over a generated chain, standing at its tip),
on a small seeded chain.  Every height the node commits is held to the
reference run over the precommits the node received for it, in the order it
received them, from either peer: the seen commit's votes are the
reference's valid ones, they carry more than two thirds, for the chain's
block.  Frames sent by hand carry a tampered vote, or a vote whose index and
address disagree: the node refuses them as the reference drops them.  And
the two spans: one `consensus.height` for each height committed, its stamps
inside it; one `consensus.vote_batch` for each frame.
"""

import asyncio
import dataclasses
import time

import pytest

from benchmarks import catchup_peer, harness
from benchmarks import chain as chainlib
from benchmarks import reference_catchup as ref
from benchmarks.rigs import catchup as rig
from tendermint_tpu.consensus import reactor as reactor_mod
from tendermint_tpu.encoding import codec

SEED = 2_147_483_901
HEIGHTS = 30  # the node commits up to HEIGHTS - 1


def config(validators):
    return {"name": f"toy-{validators}", "validators": validators, "absent_share": 0.1,
            "power": {"kind": "zipf", "top": 1000, "s": 0.8}, "app": "kvstore",
            "node": {"db_backend": "memdb"}}


TRAFFIC = {"name": "catchup", "kind": "catchup", "txs_per_block": 2, "tx_bytes": 40}


@pytest.fixture(scope="module", params=[4, 7])
def chain(request, tmp_path_factory):
    cfg = config(request.param)
    out = str(tmp_path_factory.mktemp("chain") / "chain")
    meta = chainlib.generate(cfg, TRAFFIC, SEED, HEIGHTS, out, workers=1)
    return cfg, out, meta


class Arrivals:
    """The vote_batch frames the node received, decoded, in order."""

    def __init__(self, monkeypatch):
        self.frames = []
        take = reactor_mod.ConsensusReactor._take_vote_batch

        async def recording(reactor, peer, ps, msg, span):
            votes = [codec.loads(b) for b in msg.get("votes", [])]
            self.frames.append((peer.id, votes))
            return await take(reactor, peer, ps, msg, span)

        monkeypatch.setattr(reactor_mod.ConsensusReactor, "_take_vote_batch", recording)

    def precommits(self, height):
        return [ref.Precommit(
            index=v.validator_index, address=v.validator_address, height=v.height,
            round=v.round, block_hash=v.block_id.hash,
            parts_total=v.block_id.parts_header.total, parts_hash=v.block_id.parts_header.hash,
            timestamp_ns=v.timestamp_ns, signature=v.signature, type=v.type,
        ) for _, votes in self.frames for v in votes if v.height == height]


async def catch_up(chain, tmp_path, until, started=None):
    """A node, fast sync off, the engine off (the keys' own verify), behind
    two catch-up peers in this process; returns (node, peers' switches)
    once it has committed `until`.  `started` sees the node before the
    peers are dialed."""
    from tendermint_tpu.node import Node

    cfg, chain_dir, _ = chain
    _, pubs, powers = chainlib.committee(SEED, cfg)
    cell = harness.Cell("toy.catchup", 1, cfg, TRAFFIC, HEIGHTS, [], [])

    def settings(c):
        c.base.fast_sync = False
        c.tpu.enabled = False

    node = Node(harness.node_config(cell, str(tmp_path / "home"), settings),
                harness.genesis(cell, SEED, pubs, powers), priv_validator=None)
    await node.start()
    if started is not None:
        started(node)
    store = catchup_peer.ChainBlockStore(chain_dir)
    vset = catchup_peer.validator_set(store, cfg)
    ours = {c.id for c in node.consensus_reactor.get_channels()}
    sink = sorted(d.id for d in node.switch.channel_descs if d.id not in ours)
    switches = []
    for _ in range(2):
        switch, addr = await catchup_peer.start_peer(store, vset, sink)
        switches.append(switch)
        assert await node.switch.dial_peer(addr) is not None
    t0 = time.monotonic()
    while node.block_store.height() < until:
        assert time.monotonic() - t0 < 60, f"stuck at {node.block_store.height()}"
        await asyncio.sleep(0.02)
    return node, switches


async def stop(node, switches):
    for switch in switches:
        await switch.stop()
    await node.stop()


def members_of(chain):
    cfg, _, _ = chain
    _, pubs, powers = chainlib.committee(SEED, cfg)
    from tendermint_tpu.crypto.keys import Ed25519PubKey

    return ref.members([Ed25519PubKey(p).address() for p in pubs], powers, pubs)


def chains_block(meta, h):
    total, parts_hash = meta["parts"][h - 1]
    return (bytes.fromhex(meta["hashes"][h - 1]), total, bytes.fromhex(parts_hash))


async def test_every_height_commits_on_what_the_reference_counts(chain, tmp_path, monkeypatch):
    arrivals = Arrivals(monkeypatch)
    node, switches = await catch_up(chain, tmp_path, HEIGHTS - 1)
    try:
        _, _, meta = chain
        chain_id = meta["chain_id"]
        members = members_of(chain)
        total = sum(m.power for m in members)
        for h in range(1, HEIGHTS):
            assert node.block_store.load_block_meta(h).block_id.hash.hex() == meta["hashes"][h - 1]
            seen = node.block_store.load_seen_commit(h)
            got = ref.tally(chain_id, members, h, seen.round, arrivals.precommits(h))
            assert got.block == chains_block(meta, h), h
            assert got.maj23_at is not None
            # the node counted what the reference counts, each vote once
            counted = {(v.index, v.signature) for v in got.valid}
            voted = {(i, cs.signature) for i, cs in enumerate(seen.signatures) if not cs.is_absent()}
            assert voted <= counted, h
            assert 3 * sum(members[i].power for i, _ in voted) > 2 * total
            # frames from both peers carried the same votes: the copies are dropped as duplicates
            assert set(got.reasons()) <= {"duplicate"}
        senders = {peer for peer, _ in arrivals.frames}
        assert len(senders) == 2  # both peers served
        assert any(r == "duplicate" for h in range(1, HEIGHTS) for r in ref.tally(
            chain_id, members, h, 0, arrivals.precommits(h)).reasons())
    finally:
        await stop(node, switches)


async def test_one_height_span_each_height_and_one_frame_span_each_frame(chain, tmp_path, monkeypatch):
    arrivals = Arrivals(monkeypatch)
    node, switches = await catch_up(chain, tmp_path, HEIGHTS - 1)
    try:
        ring = node.flight_recorder.events(kinds=["consensus."])
        heights = [ev for ev in ring if ev["kind"] == "consensus.height"]
        assert sorted(ev["id"] for ev in heights) == list(range(1, HEIGHTS))
        for ev in heights:
            assert ev["parent"] is None and ev["votes_added"] >= 1
            assert ev["wal_ms"] >= 0
            names = [name for name, _, _ in ev["stamps"]]
            assert {"votes_ms", "parts_ms", "apply_ms", "validate_ms", "deliver_ms"} <= set(names)
            for name, a, b in ev["stamps"]:
                assert 0 <= a <= b <= ev["dur_ns"] // 1000 + 1, (name, a, b)
            laps = ev["votes_ms"] + ev["parts_ms"] + ev["apply_ms"]
            assert laps <= ev["dur_ns"] / 1e6 + 0.01
        # the block's commit checks are the height's children
        checks = node.flight_recorder.events(kinds=["verify.commit"])
        assert checks and all(ev["parent"] == "consensus.height" for ev in checks)
        frames = [ev for ev in ring if ev["kind"] == "consensus.vote_batch"]
        assert len(frames) == len(arrivals.frames)
        assert sorted(ev["id"] for ev in frames) == sorted(votes[0].height for _, votes in arrivals.frames)
        for ev in frames:
            assert {"n", "dup", "decode_ms", "dur_ns"} <= set(ev) and ev["parent"] is None
            assert ev["n"] == 0 or {"verify_ms", "add_ms"} <= set(ev)
        # the point beside it keeps its own fields
        recv = node.flight_recorder.events(kinds=["gossip.vote_batch_recv"])
        assert recv and all("parent" not in ev for ev in recv)
    finally:
        await stop(node, switches)


async def test_each_height_is_announced_as_it_is_entered(chain, tmp_path):
    """The peers learn the node's new height from the NewHeight step it
    announces on entering it (upstream's updateToState ends in newStep); a
    node that announced nothing until its commit timeout fired NewRound
    waited that timeout a height."""
    from tendermint_tpu.consensus.types import RoundStep

    announced = []

    def hook(node):
        node.consensus.on_new_round_step.append(
            lambda rs: announced.append((rs.height, rs.step)))

    node, switches = await catch_up(chain, tmp_path, HEIGHTS - 1, started=hook)
    try:
        entered = {h for h, step in announced if step == RoundStep.NEW_HEIGHT}
        assert set(range(2, HEIGHTS)) <= entered
        spans = node.flight_recorder.events(kinds=["consensus.height"])
        # no height waited out the 1 s commit timeout for its votes
        assert max(ev["votes_ms"] for ev in spans) < 1000 * node.config.consensus.timeout_commit
    finally:
        await stop(node, switches)


def bad_frames(meta, cfg, node, kind):
    """Votes of a frame for the node's height, in a round of their own: a
    minority's valid precommits with one bad in the named way."""
    from tendermint_tpu.types import BlockID, PartSetHeader

    secrets, _, powers = chainlib.committee(SEED, cfg)
    cs = node.consensus
    h = cs.rs.height
    total, parts_hash = meta["parts"][h - 1]
    block_id = BlockID(bytes.fromhex(meta["hashes"][h - 1]), PartSetHeader(total, bytes.fromhex(parts_hash)))
    who = rig.minority(powers) or [0]
    round_ = cs.rs.round + 3
    tamper = kind if kind != "address" else "signature_r"
    votes = rig.control_frame(meta["chain_id"], h, round_, block_id, cs.rs.validators, secrets,
                              who, tamper, who[-1], chainlib.GENESIS_TIME_NS + h * 10**9)
    if kind == "address":  # a valid vote filed under another validator's index
        other = next(i for i in range(len(powers)) if i not in who)
        good = rig.control_frame(meta["chain_id"], h, round_, block_id, cs.rs.validators,
                                 secrets, who, "signature_r", -1, chainlib.GENESIS_TIME_NS)
        votes = good[:-1] + [dataclasses.replace(good[-1], validator_index=other, _wire=None)]
    return h, round_, who, votes


@pytest.mark.parametrize("kind", ["signature_r", "message", "noncanonical_s", "address"])
async def test_a_frame_with_a_bad_vote_is_refused_as_the_reference_drops_it(
        chain, tmp_path, monkeypatch, kind):
    node, switches = await catch_up(chain, tmp_path, 3)
    try:
        cfg, _, meta = chain
        for switch in switches:  # the node stays where it is
            await switch.stop()
        switches = []
        await rig.settle_height(node)
        h, round_, who, votes = bad_frames(meta, cfg, node, kind)
        cs = node.consensus

        def entered():
            held = cs.rs.votes.precommits(round_)
            return held is not None and any(held.get_by_index(i) is not None for i in range(len(cs.rs.validators.validators)))

        channels = sorted(d.id for d in node.switch.channel_descs)
        assert await rig.send_control_frame(node, votes, channels, entered)  # the peer is stopped
        assert not entered()
        got = ref.tally(meta["chain_id"], members_of(chain), h, round_, [ref.Precommit(
            index=v.validator_index, address=v.validator_address, height=v.height, round=v.round,
            block_hash=v.block_id.hash, parts_total=v.block_id.parts_header.total,
            parts_hash=v.block_id.parts_header.hash, timestamp_ns=v.timestamp_ns,
            signature=v.signature) for v in votes])
        want = "address_mismatch" if kind == "address" else "invalid_signature"
        assert got.reasons() == [want] and len(got.valid) == len(votes) - 1
        assert got.maj23_at is None  # a minority: the frame could commit nothing
        frame = [ev for ev in node.flight_recorder.events(kinds=["consensus.vote_batch"])
                 if ev.get("ok") is False]
        assert len(frame) == 1 and frame[0]["id"] == h
    finally:
        await stop(node, switches)


def test_the_reference_counts_a_validator_once_and_drops_what_upstream_refuses():
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    from benchmarks import reference

    keys = [Ed25519PrivateKey.from_private_bytes(bytes([i + 1]) * 32) for i in range(4)]
    pubs = [chainlib.public_key(bytes([i + 1]) * 32) for i in range(4)]
    members = ref.members([bytes([i]) * 20 for i in range(4)], [10, 10, 10, 1], pubs)
    block = (b"\x01" * 32, 1, b"\x02" * 32)

    def vote(i, ts=5, block=block, round_=0, index=None):
        msg = reference.vote_sign_bytes("c", 3, round_, *block, ts)
        return ref.Precommit(i if index is None else index, bytes([i]) * 20, 3, round_, *block,
                             ts, keys[i].sign(msg))

    bad = dataclasses.replace(vote(2), signature=bytes(64))
    other = vote(1, block=(b"\x09" * 32, 1, b"\x02" * 32))
    got = ref.tally("c", members, 3, 0, [
        vote(0), vote(0), vote(0, ts=6), bad, other, vote(3, index=2), vote(1, round_=1), vote(3),
        vote(2), vote(1),
    ])
    assert got.reasons() == [
        "duplicate", "duplicate", "invalid_signature", "address_mismatch", "wrong_step", "conflict"]
    # 10 + 1 + 10 = 21 of 31 is over two thirds at the vote of validator 2
    assert [v.index for v in got.valid] == [0, 1, 3, 2]
    assert got.block == block and got.maj23_at == 8
