"""A source peer: a p2p switch with the program's BlockchainReactor over a
generated chain, serving blocks to the node under test.

`python benchmarks/source.py --chain <dir> --sink 32,33,...` prints one JSON
line `{"addr": "<id>@127.0.0.1:<port>"}` and serves until its stdin closes.

The reactor is the program's (fast sync off: it answers status and block
requests, nothing else).  Its store is the generated file: a block is handed
over as the bytes the generator wrote, so a source spends its time on the
connection, and the node under test, not its sources, sets the pace.  The
channels in `--sink` (every other channel the node under test speaks) are
accepted and ignored; an unknown channel would drop the connection.

`python benchmarks/source.py --tip <height> --chain-id <id>` is a peer that
knows how long the chain is and holds none of it (its status: the height,
and a base above it): the node under test learns the chain's length from it
and never asks it for a block.  harness.Sources.start_tip says why the rig has one.  It is
started before the node under test exists, so it cannot be told that node's
channels: it takes them from the handshake, one connection at a time.

Never imports JAX: it is started with JAX_PLATFORMS=cpu and touches no
engine.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import mmap
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class _Raw:
    """A block as its wire bytes."""

    def __init__(self, data: bytes):
        self.data = data

    def serialize(self) -> bytes:
        return self.data


class ChainStore:
    """The slice of BlockStore the reactor's serving side uses."""

    def __init__(self, chain_dir: str):
        with open(os.path.join(chain_dir, "meta.json")) as f:
            self.meta = json.load(f)
        self._file = open(os.path.join(chain_dir, "blocks.bin"), "rb")
        self._map = mmap.mmap(self._file.fileno(), 0, access=mmap.ACCESS_READ)
        self._offsets = self.meta["offsets"]

    def height(self) -> int:
        return self.meta["heights"]

    def base(self) -> int:
        return 1

    def load_block(self, height: int):
        if not 1 <= height <= self.height():
            return None
        return _Raw(self._map[self._offsets[height - 1]: self._offsets[height]])

    def quarantined(self):
        return []


class TipStore:
    """The store of a peer that reports the chain's height and retains no
    block (were its base the height, the scheduler would ask it for the last
    block on every turn and be told "no block" every time)."""

    def __init__(self, height: int):
        self._height = height

    def height(self) -> int:
        return self._height

    def base(self) -> int:
        return self._height + 1

    def load_block(self, height: int):
        return None


class _AtTip:  # the reactor reads only the height its state stands at
    def __init__(self, height):
        self.last_block_height = height


async def until_stdin_closes() -> None:
    await asyncio.get_running_loop().run_in_executor(None, sys.stdin.read)


async def serve_tip(chain_id: str, height: int) -> None:
    """The program's reactor answers (its status on arrival and on request,
    "no block" to a block request); the connection takes every channel the
    other side's handshake names and drops what arrives on the others."""
    from tendermint_tpu.fastsync.reactor import BLOCKCHAIN_CHANNEL, BlockchainReactor
    from tendermint_tpu.p2p import ChannelDescriptor, NodeInfo, NodeKey, Transport
    from tendermint_tpu.p2p.peer import Peer

    reactor = BlockchainReactor(_AtTip(height), None, TipStore(height), fast_sync=False)
    key = NodeKey.generate()
    info = NodeInfo(node_id=key.id, network=chain_id, moniker="bench-tip",
                    channels=bytes([BLOCKCHAIN_CHANNEL]))
    transport = Transport(key, info)
    addr = await transport.listen("127.0.0.1:0")
    peers = []

    async def on_receive(chan_id: int, peer, msg: bytes) -> None:
        if chan_id == BLOCKCHAIN_CHANNEL:
            await reactor.receive(chan_id, peer, msg)

    async def on_error(peer, err) -> None:
        if peer.is_running:
            await peer.stop()

    async def accept() -> None:
        while True:
            conn, ni = await transport.accept()
            theirs = [c for c in ni.channels if c != BLOCKCHAIN_CHANNEL]
            descs = reactor.get_channels() + [ChannelDescriptor(id=c, priority=1) for c in theirs]
            peer = Peer(conn, ni, descs, on_receive, on_error, outbound=False)
            await peer.start()
            peers.append(peer)
            await reactor.add_peer(peer)

    accepting = asyncio.ensure_future(accept())
    print(json.dumps({"addr": f"{key.id}@{addr}"}), flush=True)
    try:
        await until_stdin_closes()
    finally:
        accepting.cancel()
        transport.close()
        for peer in peers:
            if peer.is_running:
                await peer.stop()


async def serve(chain_dir: str, sink_channels) -> None:
    from tendermint_tpu.fastsync.reactor import BlockchainReactor
    from tendermint_tpu.p2p import ChannelDescriptor, NodeInfo, NodeKey, Reactor, Switch, Transport

    class Sink(Reactor):
        def get_channels(self):
            return [ChannelDescriptor(id=c, priority=1) for c in sink_channels]

    store = ChainStore(chain_dir)
    key = NodeKey.generate()
    info = NodeInfo(node_id=key.id, network=store.meta["chain_id"], moniker="bench-source")
    switch = Switch(Transport(key, info))
    reactor = BlockchainReactor(_AtTip(store.height()), None, store, fast_sync=False)
    switch.add_reactor("BLOCKCHAIN", reactor)
    switch.add_reactor("SINK", Sink("sink"))
    addr = await switch.transport.listen("127.0.0.1:0")
    info.listen_addr = addr
    await switch.start()
    print(json.dumps({"addr": f"{key.id}@{addr}"}), flush=True)
    try:
        await until_stdin_closes()  # the harness closes it
    finally:
        await switch.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="serve one generated chain")
    ap.add_argument("--chain", help="a generated chain's directory: serve its blocks")
    ap.add_argument("--sink", default="", help="with --chain: channel ids to accept and ignore")
    ap.add_argument("--tip", type=int, help="report this height and hold no block")
    ap.add_argument("--chain-id", help="with --tip: the chain's id")
    args = ap.parse_args(argv)
    if (args.chain is None) == (args.tip is None) or (args.tip is not None and not args.chain_id):
        ap.error("give --chain, or --tip with --chain-id")
    from tendermint_tpu.libs.log import setup as log_setup

    log_setup(module_levels={"*": "error"})
    if args.tip is not None:
        asyncio.run(serve_tip(args.chain_id, args.tip))
    else:
        asyncio.run(serve(args.chain, [int(c) for c in args.sink.split(",") if c]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
