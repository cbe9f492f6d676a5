"""A catch-up peer: a p2p switch with the program's ConsensusReactor over a
generated chain, standing at the chain's tip and serving a node that is
behind it.

`python benchmarks/catchup_peer.py --chain <dir> --config <file> --sink
32,33,...` (the configuration the chain was made from) prints one JSON line `{"addr": "<id>@127.0.0.1:<port>"}` and serves until its stdin
closes; then it prints one more, `{"served": {...}}`: the vote_batch frames
and block parts it sent.

The reactor is the program's own, gossip routines and all, at the program's
gossip version.  What it serves is what upstream's reactor serves a peer two
or more heights behind (`gossipVotesRoutine`'s `rs.Height >= prs.Height+2`
branch and `gossipDataForCatchup`): the stored commit's precommits the peer
lacks (one `vote_batch` frame, `_send_commit_votes`), then the block's parts
(`_gossip_catchup_block_parts`).  Its consensus state is `TipConsensus`, a
stand-in that holds the round state of a node at the chain's tip and runs no
state machine (a peer at the tip is not what is measured), and its store is
`ChainBlockStore`: the generated file, read-only, each block's parts and
commit made from its bytes as they are asked for.  The channels in `--sink`
(every other channel the node under test speaks) are accepted and ignored.

The last height whose commit the chain holds is one short of its length (the
commit for height h rides in block h + 1), so a node served from here
commits up to `heights - 1`.

Never imports JAX: it is started with JAX_PLATFORMS=cpu and touches no
engine.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import json
import mmap
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.source import until_stdin_closes  # noqa: E402


class _Meta:
    """The slice of a BlockMeta the reactor reads: the block's id."""

    __slots__ = ("block_id",)

    def __init__(self, block_id):
        self.block_id = block_id


class ChainBlockStore:
    """The slice of BlockStore the reactor's serving side reads, over a
    generated chain: block metas, block parts and stored commits."""

    CACHED = 8  # part sets and commits kept (two peers serve heights near each other)

    def __init__(self, chain_dir: str):
        from tendermint_tpu.types import BlockID, PartSetHeader

        with open(os.path.join(chain_dir, "meta.json")) as f:
            self.meta = json.load(f)
        self._file = open(os.path.join(chain_dir, "blocks.bin"), "rb")
        self._map = mmap.mmap(self._file.fileno(), 0, access=mmap.ACCESS_READ)
        self._offsets = self.meta["offsets"]
        self._ids = [
            BlockID(bytes.fromhex(h), PartSetHeader(total, bytes.fromhex(ph)))
            for h, (total, ph) in zip(self.meta["hashes"], self.meta["parts"])
        ]
        self._parts: "collections.OrderedDict[int, object]" = collections.OrderedDict()
        self._commits: "collections.OrderedDict[int, object]" = collections.OrderedDict()

    def height(self) -> int:
        return self.meta["heights"]

    def base(self) -> int:
        return 1

    def raw(self, height: int) -> bytes:
        return self._map[self._offsets[height - 1]: self._offsets[height]]

    def _cached(self, table, height: int, make):
        if height in table:
            table.move_to_end(height)
            return table[height]
        table[height] = value = make()
        while len(table) > self.CACHED:
            table.popitem(last=False)
        return value

    def load_block_meta(self, height: int):
        if not 1 <= height <= self.height():
            return None
        return _Meta(self._ids[height - 1])

    def load_block_part(self, height: int, index: int):
        from tendermint_tpu.types.params import BLOCK_PART_SIZE_BYTES
        from tendermint_tpu.types.part_set import PartSet

        if not 1 <= height <= self.height():
            return None
        parts = self._cached(
            self._parts, height, lambda: PartSet.from_data(self.raw(height), BLOCK_PART_SIZE_BYTES)
        )
        return parts.get_part(index) if 0 <= index < parts.total else None

    def load_block_commit(self, height: int):
        """The commit for `height`: block height + 1's LastCommit."""
        from tendermint_tpu.types import Block

        if not 1 <= height < self.height():
            return None
        return self._cached(
            self._commits, height, lambda: Block.deserialize(self.raw(height + 1)).last_commit
        )


class TipConsensus:
    """What the program's ConsensusReactor reads of its consensus state, for
    a peer that stands at the chain's tip: the round state of a node that
    has committed `heights - 1` blocks and waits at the next height, with no
    votes and no proposal of its own.  It runs no state machine: a node
    behind it sends it no vote, proposal or part (the reactor gossips
    those to peers at its own height or the one after)."""

    def __init__(self, store: ChainBlockStore, validators):
        from tendermint_tpu.config import ConsensusConfig
        from tendermint_tpu.consensus.types import RoundState, RoundStep
        from tendermint_tpu.libs import tracing
        from tendermint_tpu.libs.metrics import ConsensusMetrics

        self.config = ConsensusConfig()
        self.block_store = store
        self.metrics = ConsensusMetrics()
        self.recorder = tracing.NOP
        self.sm_state = argparse.Namespace(chain_id=store.meta["chain_id"])
        self.rs = RoundState(
            height=store.height() + 1, round=0, step=RoundStep.NEW_HEIGHT,
            validators=validators, last_validators=validators,
        )
        self.is_running = False
        self.on_new_round_step, self.on_vote, self.on_valid_block = [], [], []
        self.on_proposal, self.on_new_block_part = [], []

    async def start(self) -> None:
        self.is_running = True

    async def stop(self) -> None:
        self.is_running = False


def validator_set(store: ChainBlockStore, config: dict):
    """The chain's set: the committee `chain.committee` draws from the
    chain's seed under `config`, as the set's hash in block 1 names it."""
    from benchmarks import chain as chainlib
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.types import Block, Validator, ValidatorSet

    _, pubs, powers = chainlib.committee(store.meta["seed"], config)
    vset = ValidatorSet([Validator.new(Ed25519PubKey(p), w) for p, w in zip(pubs, powers)])
    if vset.hash() != Block.deserialize(store.raw(1)).header.validators_hash:
        raise RuntimeError("the committee drawn from the seed is not the chain's set")
    return vset


class Served:
    """What a peer sent, counted off its reactor's recorder events (the
    recorder's slice the reactor calls)."""

    def __init__(self):
        self.frames = self.votes = self.parts = 0

    def record(self, kind: str, **fields) -> None:
        if kind == "gossip.votes":
            self.frames += 1
            self.votes += fields.get("n", 0)
        elif kind == "gossip.part_burst":
            self.parts += fields.get("n", 0)

    def record_sampled(self, kind: str, **fields) -> None:
        pass

    def as_dict(self) -> dict:
        return {"frames": self.frames, "votes": self.votes, "parts": self.parts}


async def start_peer(store: ChainBlockStore, validators, sink_channels, served=None):
    """A started switch with the program's ConsensusReactor over `store` and
    a sink for `sink_channels`; returns (switch, its address)."""
    from tendermint_tpu.consensus.reactor import ConsensusReactor
    from tendermint_tpu.p2p import ChannelDescriptor, NodeInfo, NodeKey, Reactor, Switch, Transport
    from tendermint_tpu.p2p.node_info import GOSSIP_TRACE_VERSION

    class Sink(Reactor):
        def get_channels(self):
            return [ChannelDescriptor(id=c, priority=1) for c in sink_channels]

    cs = TipConsensus(store, validators)
    if served is not None:
        cs.recorder = served
    reactor = ConsensusReactor(cs)
    # the version a node with the default [consensus] advertises (node.py)
    cfg = cs.config
    version = GOSSIP_TRACE_VERSION if (
        cfg.gossip_vote_batch and cfg.gossip_vote_summary and cfg.gossip_trace_context
    ) else 1
    key = NodeKey.generate()
    info = NodeInfo(node_id=key.id, network=store.meta["chain_id"], moniker="bench-catchup",
                    gossip_version=version)
    switch = Switch(Transport(key, info), allow_duplicate_ip=True)
    switch.add_reactor("CONSENSUS", reactor)
    switch.add_reactor("SINK", Sink("sink"))
    addr = await switch.transport.listen("127.0.0.1:0")
    info.listen_addr = addr
    await switch.start()
    return switch, f"{key.id}@{addr}"


async def serve(chain_dir: str, config_file: str, sink_channels) -> None:
    store = ChainBlockStore(chain_dir)
    with open(config_file) as f:
        config = json.load(f)
    served = Served()
    switch, addr = await start_peer(store, validator_set(store, config), sink_channels, served)
    print(json.dumps({"addr": addr}), flush=True)
    try:
        await until_stdin_closes()  # the harness closes it
    finally:
        await switch.stop()
        print(json.dumps({"served": served.as_dict()}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="serve one generated chain to a node catching up")
    ap.add_argument("--chain", required=True, help="a generated chain's directory")
    ap.add_argument("--config", required=True, help="the configuration the chain was made from")
    ap.add_argument("--sink", default="", help="channel ids to accept and ignore")
    args = ap.parse_args(argv)
    from tendermint_tpu.libs.log import setup as log_setup

    log_setup(module_levels={"*": "error"})
    asyncio.run(serve(args.chain, args.config, [int(c) for c in args.sink.split(",") if c]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
