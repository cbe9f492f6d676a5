"""Fast-sync tests: deterministic scheduler/processor FSMs (the v2-style
table-testable tier, SURVEY.md §4 tier 5) + a real network catch-up.
"""

import asyncio

import pytest

from tendermint_tpu.config import test_config as make_test_cfg
from tendermint_tpu.fastsync.processor import Processor, verify_commit_run
from tendermint_tpu.fastsync.scheduler import Scheduler
from tendermint_tpu.libs import tracing
from tendermint_tpu.node import Node
from tendermint_tpu.rpc.core import RPCCore
from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

from tests.test_consensus_net import CHAIN_ID, make_net, stop_net, wait_all_height
from tests.test_types import CHAIN_ID as TYPES_CHAIN_ID
from tests.test_types import make_block_id, make_commit, rand_validator_set

from tendermint_tpu.types.params import BlockParams as _BP, ConsensusParams as _CP

# time_iota_ms=1: test chains commit ~10 blocks/sec (skip_timeout_commit), so the
# reference's default 1000 ms BFT-time step would race header time ahead of wall
# clock and trip clock-drift guards (lite2 + propose-side) under suite load
_FAST_IOTA_PARAMS = _CP(block=_BP(time_iota_ms=1))


class TestScheduler:
    def test_requests_spread_across_peers(self):
        s = Scheduler(initial_height=1, max_pending_per_peer=2)
        s.set_peer_range("p1", 1, 10)
        s.set_peer_range("p2", 1, 10)
        reqs = s.next_requests(now=0.0)
        for peer_id, h in reqs:
            s.mark_requested(peer_id, h, 0.0)
        assert len(reqs) == 4  # 2 per peer
        heights = sorted(h for _, h in reqs)
        assert heights == [1, 2, 3, 4]
        by_peer = {}
        for pid, h in reqs:
            by_peer.setdefault(pid, []).append(h)
        assert all(len(v) == 2 for v in by_peer.values())

    def test_received_and_processed_advance(self):
        s = Scheduler(1, max_pending_per_peer=10)
        s.set_peer_range("p1", 1, 3)
        for pid, h in s.next_requests(0.0):
            s.mark_requested(pid, h, 0.0)
        assert s.block_received("p1", 1)
        assert not s.block_received("p2", 1)  # wrong peer: unsolicited
        assert not s.block_received("p1", 9)  # never requested
        s.block_received("p1", 2)
        s.block_received("p1", 3)
        s.block_processed(1)
        s.block_processed(2)
        assert not s.is_caught_up()
        s.block_processed(3)
        assert s.is_caught_up()

    def test_remove_peer_reschedules(self):
        s = Scheduler(1)
        s.set_peer_range("p1", 1, 5)
        s.set_peer_range("p2", 1, 5)
        for pid, h in s.next_requests(0.0):
            s.mark_requested(pid, h, 0.0)
        freed = s.remove_peer("p1")
        # freed heights get re-requested from p2
        reqs = s.next_requests(0.0)
        re_requested = {h for _, h in reqs}
        assert set(freed) <= re_requested

    def test_timeout_reassigns(self):
        s = Scheduler(1, request_timeout=1.0)
        s.set_peer_range("p1", 1, 2)
        s.set_peer_range("p2", 1, 2)
        reqs = dict((h, pid) for pid, h in s.next_requests(0.0))
        for h, pid in reqs.items():
            s.mark_requested(pid, h, 0.0)
        # after the timeout everything is schedulable again
        reqs2 = s.next_requests(now=5.0)
        assert {h for _, h in reqs2} == set(reqs.keys())

    def test_a_peer_that_keeps_delivering_is_not_timed_out(self):
        """The timeout is the peer's silence, not the request's age (v0
        pool.go's per-peer timer): 20 requests out and blocks that take 0.9 s
        to apply are 17 s on the way."""
        s = Scheduler(1, max_pending_per_peer=20, request_timeout=15.0)
        s.set_peer_range("p1", 1, 20)
        for pid, h in s.next_requests(0.0):
            s.mark_requested(pid, h, 0.0)
        for h in range(1, 20):  # a block every 0.9 s: the 19th at 17.1 s
            assert s.next_requests(h * 0.9) == []
            assert s.block_received("p1", h, h * 0.9)
        # the 20th is 17.1 s old and its peer was heard a moment ago
        assert 20 in s.pending and not s.peers["p1"].late
        # silent from then on: given up on 15 s after its last block
        assert s.next_requests(17.1 + 14.9) == [] and 20 in s.pending
        assert s.next_requests(17.1 + 15.1) == [("p1", 20)]
        assert 20 not in s.pending and s.peers["p1"].late == {20}

    @pytest.mark.parametrize(
        "then", ["not_asked_again", "asked_of_another", "the_other_copy_first", "processed"]
    )
    def test_a_late_copy_of_a_block_asked_for_is_no_offence(self, then):
        s = Scheduler(1, max_pending_per_peer=1, request_timeout=1.0)
        s.set_peer_range("slow", 1, 5)
        assert s.next_requests(0.0) == [("slow", 1)]
        s.mark_requested("slow", 1, 0.0)
        s.set_peer_range("other", 1, 5)
        s.mark_requested("other", 2, 1.5)
        s.peers["other"].pending.add(2)  # busy with height 2 for now
        assert s.next_requests(2.0) == [("slow", 1)]  # timed out, and asked for again
        assert s.peers["slow"].late == {1} and 1 not in s.pending
        if then == "not_asked_again":
            assert s.block_received("slow", 1, 2.5) is True  # still wanted: it is the block
            assert s.received[1] == "slow"
        elif then == "asked_of_another":
            s.peers["slow"].pending.clear()
            s.peers["other"].pending.add(1)
            s.mark_requested("other", 1, 2.0)
            assert s.block_received("slow", 1, 2.5) is True  # the first copy to arrive
            assert s.received[1] == "slow" and 1 not in s.pending
            assert 1 not in s.peers["other"].pending
            assert s.block_received("other", 1, 2.6) is None  # and the other's is the late one
            assert s.received[1] == "slow" and s.peers["other"].late == set()
        else:
            s.mark_requested("other", 1, 2.0)
            assert s.block_received("other", 1, 2.1) is True
            if then == "processed":
                s.block_processed(1)
            assert s.block_received("slow", 1, 2.5) is None  # asked for, no longer wanted
            assert s.received.get(1, "other") == "other"
        assert s.peers["slow"].late == set()
        assert s.block_received("slow", 1, 3.0) is False  # a further copy was never asked for
        assert s.block_received("slow", 4, 3.0) is False

    def test_what_was_given_up_on_is_forgotten_far_behind_the_tip(self):
        s = Scheduler(1, max_pending_per_peer=1, max_total_pending=3, request_timeout=1.0)
        s.set_peer_range("mute", 1, 50)
        s.mark_requested("mute", 1, 0.0)
        s.next_requests(2.0)
        assert s.peers["mute"].late == {1}
        s.height = 5  # as if heights 1-4 came from elsewhere
        s.next_requests(2.0)
        assert s.peers["mute"].late == set()

    def test_peer_base_respected(self):
        s = Scheduler(1)
        s.set_peer_range("pruned", base=50, height=100)
        assert s.next_requests(0.0) == []  # peer pruned heights 1..49


class TestProcessor:
    def test_pairs_and_advance(self):
        from tendermint_tpu.types import Block, Header

        p = Processor(height=5)
        mk = lambda h: Block(Header(chain_id="c", height=h), [])
        p.add_block(6, mk(6), "p2")
        assert p.peek_two() is None
        p.add_block(5, mk(5), "p1")
        first, second = p.peek_two()
        assert first.height == 5 and second.height == 6
        p.pop_processed()
        assert p.height == 6

    def test_drop_invalid_reports_heights(self):
        from tendermint_tpu.types import Block, Header

        p = Processor(height=5)
        p.add_block(5, Block(Header(chain_id="c", height=5), []), "bad1")
        p.add_block(6, Block(Header(chain_id="c", height=6), []), "bad2")
        assert p.drop_invalid() == (5, 6)
        assert p.peek_two() is None


class TestVerifyCommitRun:
    def test_cross_height_batch(self):
        vset, pvs = rand_validator_set(6)
        pairs = []
        for h in (10, 11, 12):
            bid = make_block_id(bytes([h]))
            commit = make_commit(vset, pvs, h, 0, bid)
            pairs.append((bid, h, commit))
        assert verify_commit_run(vset, "test-chain", pairs) == [True, True, True]
        # tamper one height's commit: only that height fails
        bad_bid = make_block_id(b"\x63")
        bad = make_commit(vset, pvs, 13, 0, bad_bid)
        bad.signatures[2] = bad.signatures[2].__class__(
            bad.signatures[2].block_id_flag,
            bad.signatures[2].validator_address,
            bad.signatures[2].timestamp_ns,
            b"\x00" * 64,
        )
        pairs.append((bad_bid, 13, bad))
        assert verify_commit_run(vset, "test-chain", pairs) == [True, True, True, False]


class TestFastSyncNet:
    async def test_non_validator_fast_syncs(self, tmp_path):
        """3 validators progress; a non-validator full node joins with
        fast_sync on, downloads the chain, switches to consensus, and keeps
        following the head."""
        nodes, pvs = await make_net(tmp_path, 3, name="fs")
        try:
            await wait_all_height(nodes, 5)

            cfg = make_test_cfg(str(tmp_path / "syncer"))
            cfg.rpc.laddr = ""
            cfg.base.db_backend = "memdb"
            cfg.base.fast_sync = True
            cfg.p2p.laddr = "127.0.0.1:0"
            cfg.consensus.skip_timeout_commit = False
            cfg.consensus.timeout_commit = 0.1
            gen = GenesisDoc(
                chain_id=CHAIN_ID,
                genesis_time_ns=1_700_000_000_000_000_000,
                validators=[
                    GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs
                ],
                consensus_params=_FAST_IOTA_PARAMS,
            )
            syncer = Node(cfg, gen, priv_validator=None, db_backend="memdb")
            await syncer.start()
            assert syncer.blockchain_reactor.fast_sync
            for n in nodes:
                addr = f"{n.node_key.id}@{n.switch.transport.listen_addr}"
                await syncer.switch.dial_peer(addr)

            # must catch up and then follow the moving head via consensus
            target = nodes[0].block_store.height() + 3

            async def synced():
                while True:
                    if syncer.block_store.height() >= target:
                        return
                    await asyncio.sleep(0.05)

            await asyncio.wait_for(synced(), 60.0)
            assert syncer.blockchain_reactor.blocks_synced > 0
            assert not syncer.blockchain_reactor.fast_sync  # switched over
            h = target - 1
            assert (
                syncer.block_store.load_block(h).hash()
                == nodes[0].block_store.load_block(h).hash()
            )
            await syncer.stop()
        finally:
            await stop_net(nodes)


class TestReplaySpans:
    async def test_every_applied_block_leaves_its_span_chain(self, tmp_path):
        """A joining node's flight recorder, read as `dump_flight_recorder`
        serves it: one `fastsync.block` per block applied, its in-span
        stages inside its length, what the receive path and `apply_block`
        measured carried as fields, and the two commit verifications of
        that block (its own, by the next block's LastCommit; its LastCommit,
        in validation) under its height."""
        nodes, pvs = await make_net(tmp_path, 3, name="fsspan")
        try:
            await wait_all_height(nodes, 8)
            cfg = make_test_cfg(str(tmp_path / "syncer"))
            cfg.rpc.laddr = ""
            cfg.base.db_backend = "memdb"
            cfg.base.fast_sync = True
            cfg.p2p.laddr = "127.0.0.1:0"
            gen = GenesisDoc(
                chain_id=CHAIN_ID,
                genesis_time_ns=1_700_000_000_000_000_000,
                validators=[
                    GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs
                ],
                consensus_params=_FAST_IOTA_PARAMS,
            )
            syncer = Node(cfg, gen, priv_validator=None, db_backend="memdb")
            await syncer.start()
            try:
                addr = f"{nodes[0].node_key.id}@{nodes[0].switch.transport.listen_addr}"
                await syncer.switch.dial_peer(addr)

                async def replayed():
                    while syncer.blockchain_reactor.blocks_synced < 6:
                        await asyncio.sleep(0.02)

                await asyncio.wait_for(replayed(), 60.0)
                applied = syncer.blockchain_reactor.blocks_synced
                events = (await RPCCore(syncer).call("dump_flight_recorder"))["events"]
            finally:
                await syncer.stop()
        finally:
            await stop_net(nodes)

        blocks = [e for e in events if e["kind"] == "fastsync.block"]
        assert len(blocks) >= applied - 1  # one more may have been applied since the read
        heights = [e["id"] for e in blocks]
        assert heights == list(range(heights[0], heights[0] + len(blocks)))
        commits = [e for e in events if e["kind"] == "verify.commit"]
        for ev in blocks:
            stages = ev["parts_ms"] + ev["verify_ms"] + ev["store_ms"] + ev["apply_ms"]
            assert 0 < stages <= ev["dur_ns"] / 1e6 + 0.005
            inside = ev["validate_ms"] + ev["abci_req_ms"] + ev["deliver_ms"] \
                + ev["mempool_ms"] + ev["save_state_ms"] + ev["events_ms"]
            assert 0 < inside <= ev["apply_ms"] + 0.005
            assert ev["parent"] is None and ev["pending"] >= 2
            assert ev["bytes"] > 0 and ev["decode_ms"] > 0 and len(ev["peer"]) == 8
            assert ev["download_ms"] >= 0 and ev["queued_ms"] >= 0
            mine = [c for c in commits if c["id"] == ev["id"]]
            # the block's own commit (height H), then its LastCommit (H - 1;
            # the first block of a chain has none to verify)
            assert [c["height"] for c in mine] == (
                [ev["id"]] if ev["id"] == 1 else [ev["id"], ev["id"] - 1])
            for c in mine:
                assert c["parent"] == "fastsync.block" and "ok" not in c
                assert c["n"] == 3 and c["t_ns"] <= ev["t_ns"]
                assert c["sign_bytes_ms"] + c["engine_ms"] + c["tally_ms"] <= c["dur_ns"] / 1e6 + 0.005
        # consecutive spans tile the replay loop's time: a block's wait is
        # the gap since the one before it
        for prev, ev in zip(blocks, blocks[1:]):
            gap_ms = (ev["t_ns"] - ev["dur_ns"] - prev["t_ns"]) / 1e6
            assert ev["wait_ms"] == pytest.approx(gap_ms, abs=0.002)
        assert "wait_ms" not in blocks[0]
        # the store encodes what a block changed: the seen commit (its
        # LastCommit was saved as the seen commit a block before) and the
        # rotated next_validators; the first block applied may meet both new
        assert blocks[0]["commit_encodes"] <= 2 and blocks[0]["set_encodes"] <= 3
        assert [ev["commit_encodes"] for ev in blocks[1:]] == [1] * (len(blocks) - 1)
        assert [ev["set_encodes"] for ev in blocks[1:]] == [1] * (len(blocks) - 1)
        # every dispatch and table lookup inside says which block it served
        for e in events:
            if e["kind"] in ("verify.dispatch", "verify.table") and "parent" in e:
                assert e["parent"] == "verify.commit" and e["id"] in heights
        # `trace --replay` on that dump: the chain per height, stage by stage
        budget = tracing.replay_budget(events)
        assert budget["blocks"] == len(blocks) and budget["heights"] == [heights[0], heights[-1]]
        assert {"block_ms", "parts_ms", "verify_ms", "store_ms", "apply_ms", "deliver_ms",
                "commit.sign_bytes_ms", "commit.engine_ms"} <= set(budget["stages"])
        # no more than 8 events a block, the ring's budget at the hub's rate
        per_block = [e for e in events if e.get("id") in heights]
        assert len(per_block) <= 8 * len(blocks)
        # consensus's deliver.* events stay consensus's: fast sync adds none
        assert not [e for e in events if e["kind"].startswith("deliver.") and e["height"] in heights]


class TestSetHashStage:
    """`validate_block` holds the header's two validator-set hashes against
    the state's sets on every block; the sets keep their Merkle root across
    `update_state`'s copies, so on a set that did not change the stage
    builds none.  Read off the open span, as `validate_ms` is."""

    CHAIN = TYPES_CHAIN_ID  # the chain `make_commit` signs for
    HEIGHTS = 6

    @staticmethod
    async def _executor():
        from tendermint_tpu.abci.client import LocalClient
        from tendermint_tpu.abci.examples import KVStoreApplication
        from tendermint_tpu.libs.kvstore import MemDB
        from tendermint_tpu.mempool import NopMempool
        from tendermint_tpu.state import StateStore
        from tendermint_tpu.state.execution import BlockExecutor

        app = LocalClient(KVStoreApplication())
        await app.start()
        return BlockExecutor(StateStore(MemDB()), app, NopMempool())

    def _genesis(self, pvs):
        return GenesisDoc(
            chain_id=self.CHAIN,
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs],
        )

    async def _chain(self, pvs, update_at=None):
        """Blocks 1..HEIGHTS with their ids, made and applied by a proposer
        with a state of its own: `make_block` warms that state's sets, the
        replayer's start cold.  The block at `update_at` carries a kvstore
        validator tx, so its EndBlock returns a power change."""
        import base64

        from tendermint_tpu.state import make_genesis_state

        state = make_genesis_state(self._genesis(pvs))
        ex = await self._executor()
        commit, chain = None, []
        try:
            for h in range(1, self.HEIGHTS + 1):
                txs = [b"k%d=v%d" % (h, h)]
                if h == update_at:
                    pk = base64.b64encode(pvs[0].get_pub_key().bytes())
                    txs.append(b"val:" + pk + b"!15")
                block = state.make_block(
                    h, txs, commit, [], state.validators.get_proposer().address)
                block_id = block.block_id(65536)
                commit = make_commit(state.validators, pvs, h, 0, block_id)
                chain.append((block_id, block))
                state, _ = await ex.apply_block(state, block_id, block)
        finally:
            await ex.proxy_app.stop()
        return chain

    async def _replay(self, pvs, chain):
        """A joining node's side: a fresh state from genesis, each block
        through `apply_block` under an open `fastsync.block` span.  Returns
        the blocks' events, the state reached and the executor."""
        from tendermint_tpu.state import make_genesis_state

        rec = tracing.FlightRecorder(size=64)
        state = make_genesis_state(self._genesis(pvs))
        ex = await self._executor()
        try:
            for block_id, block in chain:
                with rec.span("fastsync.block", id=block.height):
                    state, _ = await ex.apply_block(state, block_id, block)
        finally:
            await ex.proxy_app.stop()
        blocks = [e for e in rec.events() if e["kind"] == "fastsync.block"]
        return blocks, state, ex

    async def test_a_static_set_builds_its_roots_once(self):
        pvs = sorted((MockPV() for _ in range(4)), key=lambda pv: pv.address())
        events, state, _ = await self._replay(pvs, await self._chain(pvs))
        assert [e["id"] for e in events] == [1, 2, 3, 4, 5, 6]
        built = [e["set_hashes"] for e in events]
        assert built[0] <= 2 and built[1:] == [0, 0, 0, 0, 0]
        for ev in events:
            assert 0 <= ev["set_hash_ms"] <= ev["validate_ms"]
        assert state.validators._root is not None and state.next_validators._root is not None

    async def test_a_set_that_changes_pays_one_root_per_change(self):
        pvs = sorted((MockPV() for _ in range(4)), key=lambda pv: pv.address())
        chain = await self._chain(pvs, update_at=3)
        events, state, _ = await self._replay(pvs, chain)
        # EndBlock of height 3 changes a power: block 4 is the first held
        # against the changed next_validators and builds its root, once;
        # at 5 that set is promoted to validators by copy(), root and all
        built = [e["set_hashes"] for e in events]
        assert built[0] <= 2 and built[1:] == [0, 0, 1, 0, 0]
        assert chain[3][1].header.next_validators_hash != chain[2][1].header.next_validators_hash
        assert chain[4][1].header.validators_hash == chain[3][1].header.next_validators_hash
        _, changed = state.validators.get_by_address(pvs[0].address())
        assert changed.voting_power == 15

    async def test_a_block_from_the_wire_builds_its_commit_root_once(self):
        """`basic_ms`, `commit_hashes` and `median_ms`, PR 29's stages of
        `validate_ms`: a block decoded from a peer's bytes brings a Commit
        that has no root yet, and `validate_basic` builds it (1); the object
        keeps it, so validating the same block again builds none (0)."""
        from tendermint_tpu.types import Block

        pvs = sorted((MockPV() for _ in range(4)), key=lambda pv: pv.address())
        made = await self._chain(pvs)
        wire = [(bid, Block.deserialize(block.serialize())) for bid, block in made]
        events, state, ex = await self._replay(pvs, wire)
        assert [e["commit_hashes"] for e in events] == [0, 1, 1, 1, 1, 1]  # height 1 has none
        assert "median_ms" not in events[0]  # nor a median
        for ev in events:
            assert 0 < ev["basic_ms"] <= ev["validate_ms"]
            assert ev["basic_ms"] + ev["set_hash_ms"] + ev.get("median_ms", 0) <= ev["validate_ms"]
        for ev in events[1:]:
            assert ev["median_ms"] > 0
        # the proposer's own objects were hashed by make_block: nothing left to build
        events, _, _ = await self._replay(pvs, made)
        assert [e["commit_hashes"] for e in events] == [0] * 6
        # and a block validated twice (consensus: prevote, then finalize) pays once
        block = Block.deserialize(made[-1][1].serialize())
        before = await self._replay(pvs, wire[:-1])
        rec = tracing.FlightRecorder(size=32)
        for _ in range(2):
            with rec.span("fastsync.block", id=block.height):
                before[2].validate_block(before[1], block)
        first, second = (e for e in rec.events() if e["kind"] == "fastsync.block")
        assert (first["commit_hashes"], second["commit_hashes"]) == (1, 0)
        # stages are fields: the only other events are the LastCommit's own
        assert {e["kind"] for e in rec.events()} <= {
            "fastsync.block", "verify.commit", "verify.dispatch", "verify.table"}
        assert block.last_commit.hash() == made[-1][1].header.last_commit_hash

    async def test_the_replay_loop_encodes_what_a_block_changed_once(self, monkeypatch):
        """`_try_sync` over a chain decoded from a peer's bytes: one part set
        a block applied (its header is the block id's, the store writes its
        parts and takes the size from it), `commit_encodes` 1 (the LastCommit
        is the object saved as the seen commit one block before) and
        `set_encodes` 1 once the loop is steady (the rotated next set); every
        record written is what the parent wrote."""
        from tendermint_tpu.encoding import codec
        from tendermint_tpu.fastsync.reactor import BlockchainReactor
        from tendermint_tpu.libs.kvstore import MemDB
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.store import BlockStore
        from tendermint_tpu.store.block_store import seal
        from tendermint_tpu.types import Block

        pvs = sorted((MockPV() for _ in range(4)), key=lambda pv: pv.address())
        wire = [Block.deserialize(block.serialize()) for _, block in await self._chain(pvs)]
        reactor = BlockchainReactor.__new__(BlockchainReactor)
        reactor.processor, reactor.scheduler = Processor(1), Scheduler(1)
        for block in wire:
            reactor.processor.add_block(block.height, block, "peerX")
        reactor.recorder = tracing.FlightRecorder(size=64)
        reactor._block_done_ns, reactor.blocks_synced = 0, 0
        reactor.state = make_genesis_state(self._genesis(pvs))
        reactor.block_store = BlockStore(MemDB())
        reactor.block_exec = await self._executor()
        built = []
        make_part_set = Block.make_part_set
        monkeypatch.setattr(
            Block, "make_part_set", lambda b, size: built.append(b.height) or make_part_set(b, size))
        try:
            await reactor._try_sync()
        finally:
            await reactor.block_exec.proxy_app.stop()
        events = [e for e in reactor.recorder.events() if e["kind"] == "fastsync.block"]
        assert [e["id"] for e in events] == built == [1, 2, 3, 4, 5]  # 6 has no successor yet
        assert [e["commit_encodes"] for e in events] == [1] * 5
        # the first save meets a genesis state no one saved: its three sets are new
        assert [e["set_encodes"] for e in events] == [3, 1, 1, 1, 1]
        db = reactor.block_store.db
        for block, nxt in zip(wire, wire[1:]):
            h = block.height
            assert db.get(b"SC:%d" % h) == seal(codec.dumps(nxt.last_commit))
            assert db.get(b"C:%d" % h) == (seal(codec.dumps(nxt.last_commit)) if h < 5 else None)
            assert reactor.block_store.load_block_meta(h).block_size == len(block.serialize())

    async def test_a_header_that_lies_about_the_last_commit_is_rejected(self):
        from dataclasses import replace

        from tendermint_tpu.state.validation import InvalidBlockError
        from tendermint_tpu.types import Block, Commit

        pvs = sorted((MockPV() for _ in range(4)), key=lambda pv: pv.address())
        chain = await self._chain(pvs)
        _, state, ex = await self._replay(pvs, chain[:3])
        block_id, block = chain[3]
        lc = block.last_commit
        moved = replace(lc.signatures[2], timestamp_ns=lc.signatures[2].timestamp_ns + 1)
        forged = Block(block.header, block.txs, block.evidence, Commit(
            lc.height, lc.round, lc.block_id, lc.signatures[:2] + [moved] + lc.signatures[3:]))
        with pytest.raises(InvalidBlockError, match=r"Header\.LastCommitHash$"):
            await ex.apply_block(state, block_id, forged)
        ex.validate_block(state, block)  # and the honest block still passes

    @pytest.mark.parametrize("field", ["validators_hash", "next_validators_hash"])
    async def test_a_header_that_lies_about_a_set_is_rejected_with_the_memo_warm(self, field):
        from dataclasses import replace

        from tendermint_tpu.state.validation import InvalidBlockError
        from tendermint_tpu.types import Block

        pvs = sorted((MockPV() for _ in range(4)), key=lambda pv: pv.address())
        chain = await self._chain(pvs)
        _, state, ex = await self._replay(pvs, chain[:3])
        assert state.validators._root is not None and state.next_validators._root is not None
        block_id, block = chain[3]
        name = "".join(part.capitalize() for part in field.split("_"))
        for wrong in (b"\x00" * 32, b""):
            forged = Block(replace(block.header, **{field: wrong}), block.txs,
                           block.evidence, block.last_commit)
            with pytest.raises(InvalidBlockError, match=rf"Header\.{name}$"):
                await ex.apply_block(state, block_id, forged)
        ex.validate_block(state, block)  # and the honest block still passes


class TestBehaviourReporting:
    """behaviour/reporter.go — reactors report conduct through a Reporter;
    MockReporter captures what was reported."""

    async def test_bad_block_response_reported(self):
        from tendermint_tpu.fastsync.reactor import BlockchainReactor
        from tendermint_tpu.p2p.behaviour import BAD_MESSAGE, MockReporter

        class _Peer:
            id = "peerX"

            async def send(self, *a):
                return True

        class _Store:
            def height(self):
                return 0

            def base(self):
                return 0

        reactor = BlockchainReactor.__new__(BlockchainReactor)
        reactor.reporter = MockReporter()
        reactor.fast_sync = True
        reactor.block_store = _Store()
        from tendermint_tpu.fastsync.reactor import BLOCKCHAIN_CHANNEL

        await reactor.receive(BLOCKCHAIN_CHANNEL, _Peer(), b"\x00garbage")
        reports = reactor.reporter.get("peerX")
        assert len(reports) == 1 and reports[0].kind == BAD_MESSAGE

    @pytest.mark.parametrize("asked,reported,taken", [
        ("never", True, 0), ("given_up_on", False, 0), ("given_up_on_and_still_wanted", False, 1),
    ])
    async def test_only_a_block_never_asked_for_is_reported(self, asked, reported, taken):
        """A copy of a block that was asked for and arrives after its request
        was given up on is dropped without a word: reporting it stopped both
        sources of a joining node whenever blocks took long to apply."""
        from tendermint_tpu.fastsync.processor import Processor
        from tendermint_tpu.fastsync.reactor import BLOCKCHAIN_CHANNEL, BlockchainReactor, _enc
        from tendermint_tpu.p2p.behaviour import MESSAGE_OUT_OF_ORDER, MockReporter
        from tendermint_tpu.types import Block, Header

        class _Peer:
            id = "peerX"

        reactor = BlockchainReactor.__new__(BlockchainReactor)
        reactor.reporter = MockReporter()
        reactor.fast_sync = True
        reactor.refill_heights = set()
        reactor._wake = None
        reactor.scheduler = Scheduler(1, request_timeout=1.0)
        reactor.processor = Processor(1)
        reactor.scheduler.set_peer_range("peerX", 1, 9)
        reactor.scheduler.set_peer_range("peerY", 1, 9)
        if asked != "never":
            reactor.scheduler.mark_requested("peerX", 3, 0.0)
            reactor.scheduler.next_requests(2.0)
        if asked == "given_up_on":
            reactor.scheduler.mark_requested("peerY", 3, 2.0)
            assert reactor.scheduler.block_received("peerY", 3, 2.1)
        block = Block(Header(chain_id="c", height=3), [])
        msg = _enc("block_response", {"block": block.serialize()})
        await reactor.receive(BLOCKCHAIN_CHANNEL, _Peer(), msg)
        reports = reactor.reporter.get("peerX")
        assert [r.kind for r in reports] == ([MESSAGE_OUT_OF_ORDER] if reported else [])
        assert reactor.processor.pending_range() == taken
        if taken:  # with what the receive path measured, less the time since a request it no longer has
            assert set(reactor.processor.received(3)) == {"peer", "bytes", "decode_ms", "received_ns"}

    async def test_switch_reporter_stops_bad_and_marks_good(self):
        from tendermint_tpu.p2p.behaviour import (
            SwitchReporter,
            bad_message,
            consensus_vote,
        )

        stopped = []
        marked = []

        class _Book:
            def mark_good(self, pid):
                marked.append(pid)

        class _Switch:
            peers = {"p1": object(), "p2": object()}
            addr_book = _Book()

            async def stop_peer_for_error(self, peer, reason):
                stopped.append(reason)

        rep = SwitchReporter(_Switch())
        assert await rep.report(consensus_vote("p1"))
        assert marked == ["p1"]
        assert await rep.report(bad_message("p2", "bad"))
        assert stopped == ["bad"]
        assert not await rep.report(bad_message("ghost", "x"))  # unknown peer
