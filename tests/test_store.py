"""Storage tests: kvstore backends, block store, state store, tx indexer.

Coverage model: store/store_test.go, state/store_test.go,
state/txindex/kv/kv_test.go.
"""

import pytest

from tendermint_tpu.libs.kvstore import MemDB, SQLiteDB
from tendermint_tpu.state import State, StateStore, make_genesis_state
from tendermint_tpu.state.txindex import TxIndexer
from tendermint_tpu.store import BlockStore
from tendermint_tpu.types import (
    GenesisDoc,
    GenesisValidator,
    MockPV,
    Validator,
)
from tendermint_tpu.types.tx import tx_hash

from tests.test_types import CHAIN_ID, make_commit, make_test_block, rand_validator_set


@pytest.fixture(params=["memdb", "sqlite"])
def db(request, tmp_path):
    if request.param == "memdb":
        yield MemDB()
    else:
        d = SQLiteDB(str(tmp_path / "kv.db"))
        yield d
        d.close()


class TestKVStore:
    def test_roundtrip_and_prefix(self, db):
        db.set(b"a/1", b"v1")
        db.set(b"a/2", b"v2")
        db.set(b"b/1", b"v3")
        assert db.get(b"a/1") == b"v1"
        assert db.get(b"missing") is None
        assert [(k, v) for k, v in db.iterate_prefix(b"a/")] == [
            (b"a/1", b"v1"),
            (b"a/2", b"v2"),
        ]
        db.delete(b"a/1")
        assert db.get(b"a/1") is None

    def test_write_batch(self, db):
        db.set(b"x", b"old")
        db.write_batch([(b"x", b"new"), (b"y", b"1")], deletes=[b"z"])
        assert db.get(b"x") == b"new"
        assert db.get(b"y") == b"1"


class TestBlockStore:
    def _saved_store(self, db):
        block, vset, pvs = make_test_block(height=1)
        store = BlockStore(db)
        ps = block.make_part_set(1024)
        seen = make_commit(vset, pvs, 1, 0, block.block_id(1024))
        store.save_block(block, ps, seen)
        return store, block, vset, pvs

    def test_save_load_roundtrip(self, db):
        store, block, _, _ = self._saved_store(db)
        assert store.height() == 1
        assert store.base() == 1
        loaded = store.load_block(1)
        assert loaded.hash() == block.hash()
        meta = store.load_block_meta(1)
        assert meta.block_id.hash == block.hash()
        assert meta.num_txs == len(block.txs)
        assert store.load_block_by_hash(block.hash()).hash() == block.hash()
        seen = store.load_seen_commit(1)
        assert seen.height == 1
        part = store.load_block_part(1, 0)
        assert part is not None and part.index == 0
        # reopening from the same DB restores height bookkeeping
        store2 = BlockStore(db)
        assert store2.height() == 1 and store2.base() == 1

    def test_wrong_height_rejected(self, db):
        store, block, vset, pvs = self._saved_store(db)
        b3, _, _ = make_test_block(height=3)
        ps = b3.make_part_set(1024)
        with pytest.raises(ValueError, match="expected"):
            store.save_block(b3, ps, make_commit(vset, pvs, 3, 0, b3.block_id(1024)))

    def test_missing_heights(self, db):
        store = BlockStore(db)
        assert store.load_block(5) is None
        assert store.load_block_meta(5) is None
        assert store.height() == 0 and store.size() == 0


class TestBlockStoreWritesTheParentBytes:
    """`save_block` serializes no block and encodes a commit once: the
    meta's size is the part set's, and a commit keeps its sealed record, so
    fast sync's LastCommit of block H (the object it saved as SC:H-1) is
    written as C:H-1 without a second encoding.  Every record is held
    against what the parent wrote, computed here from scratch."""

    PART = 1024

    @staticmethod
    def _chain(n=5):
        """Blocks 1..n, each carrying the commit of the one before, as a
        joining node decodes them from a peer's bytes."""
        from tendermint_tpu.types import Block, BlockID, Header

        vset, pvs = rand_validator_set(4)
        blocks, last = [], None
        for h in range(1, n + 1):
            header = Header(chain_id=CHAIN_ID, height=h, time_ns=1_700_000_000_000_000_000 + h,
                            validators_hash=vset.hash(), next_validators_hash=vset.hash(),
                            proposer_address=vset.get_proposer().address)
            block = Block(header, [b"tx-%d-%d=%s" % (h, i, b"v" * 200) for i in range(12)],
                          last_commit=last)
            block.fill_header()
            blocks.append(Block.deserialize(block.serialize()))
            last = make_commit(vset, pvs, h, 0, block.block_id(TestBlockStoreWritesTheParentBytes.PART))
        return blocks

    def _parent_records(self, block, parts, seen_commit):
        """What the parent's save_block wrote for this block."""
        from tendermint_tpu.encoding import codec
        from tendermint_tpu.store.block_store import BlockMeta, seal
        from tendermint_tpu.types import BlockID

        meta = BlockMeta(BlockID(block.hash(), parts.header()), len(block.serialize()),
                         block.header, len(block.txs))
        h = block.height
        out = {b"H:%d" % h: seal(codec.dumps(meta)), b"BH:" + block.hash(): seal(b"%d" % h),
               b"SC:%d" % h: seal(codec.dumps(seen_commit))}
        for i in range(parts.total):
            out[b"P:%d:%d" % (h, i)] = seal(codec.dumps(parts.get_part(i)))
        if block.last_commit is not None:
            out[b"C:%d" % (h - 1)] = seal(codec.dumps(block.last_commit))
        return out

    @pytest.mark.parametrize("path", ["fastsync", "consensus"])
    def test_every_record_is_the_parents(self, db, path):
        """`fastsync`: SC:H is the next block's LastCommit, the object
        written as C:H one block later (1 encoding a block); `consensus`:
        the seen commit is an object of its own (2, as before)."""
        from tendermint_tpu.libs import tracing
        from tendermint_tpu.types import Commit

        blocks = self._chain()
        store, rec = BlockStore(db), tracing.FlightRecorder(size=32)
        for first, second in zip(blocks, blocks[1:]):
            parts = first.make_part_set(self.PART)
            assert parts.total > 1 and parts.byte_size() == len(first.serialize())
            seen = second.last_commit
            if path == "consensus":
                seen = Commit(seen.height, seen.round, seen.block_id, list(seen.signatures))
            expected = self._parent_records(first, parts, seen)
            with rec.span("fastsync.block", id=first.height):
                store.save_block(first, parts, seen)
            for key, value in expected.items():
                assert db.get(key) == value, key
        encodes = [e["commit_encodes"] for e in rec.events()]
        assert encodes == ([1, 1, 1, 1] if path == "fastsync" else [1, 2, 2, 2])
        for h in range(1, 5):  # C:H comes with block H + 1: the last saved is 4
            assert store.load_block(h).hash() == blocks[h - 1].hash()
            assert store.load_seen_commit(h).hash() == blocks[h].last_commit.hash()
            assert store.load_block_meta(h).block_size == len(blocks[h - 1].serialize())
            assert (store.load_block_commit(h) is None) == (h == 4)

    def test_a_kept_record_is_per_object(self):
        from tendermint_tpu.encoding import codec
        from tendermint_tpu.store.block_store import commit_record, seal

        commit = self._chain(2)[1].last_commit
        assert commit._record is None
        record = commit_record(commit)
        assert record == seal(codec.dumps(commit)) and commit_record(commit) is record
        again = codec.loads(codec.dumps(commit))  # what a store or a peer gives back
        assert again._record is None and commit_record(again) == record


class TestStateStore:
    def _gen_doc(self, n=4):
        pvs = [MockPV() for _ in range(n)]
        return GenesisDoc(
            chain_id=CHAIN_ID,
            validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs],
        )

    def test_genesis_state(self, db):
        store = StateStore(db)
        state = store.load_from_db_or_genesis(self._gen_doc())
        assert state.chain_id == CHAIN_ID
        assert state.last_block_height == 0
        assert state.validators.size() == 4
        assert state.last_validators.size() == 0

    def test_save_load_roundtrip(self, db):
        store = StateStore(db)
        state = make_genesis_state(self._gen_doc())
        store.save(state)
        loaded = store.load()
        assert loaded.equals(state)
        # validators stored for heights 1 and 2
        v1 = store.load_validators(1)
        assert v1 is not None and v1.hash() == state.validators.hash()
        v2 = store.load_validators(2)
        assert v2 is not None
        params = store.load_consensus_params(1)
        assert params == state.consensus_params

    def test_validator_pointer_scheme(self, db):
        # unchanged sets store pointer records; the full set only at
        # last_changed (state/store.go:295 LoadValidators)
        store = StateStore(db)
        state = make_genesis_state(self._gen_doc())
        store.save(state)
        # simulate 3 committed heights with no validator changes
        for h in range(1, 4):
            s = state.copy()
            s.last_block_height = h
            s.last_validators = s.validators.copy()
            s.validators = s.next_validators.copy()
            s.next_validators = s.next_validators.copy_increment_proposer_priority(1)
            state = s
            store.save(state)
        v4 = store.load_validators(4)
        assert v4 is not None
        assert v4.hash() == state.next_validators.hash()

    def test_abci_responses(self, db):
        store = StateStore(db)
        responses = {
            "deliver_txs": [{"code": 0, "data": b"ok"}],
            "end_block": {"validator_updates": []},
        }
        store.save_abci_responses(7, responses)
        assert store.load_abci_responses(7) == responses
        assert store.load_abci_responses(8) is None


class TestTxIndexer:
    def test_index_get_search(self, db):
        idx = TxIndexer(db)
        tx = b"tx-payload"
        idx.index(
            {"height": 5, "index": 0, "tx": tx, "result": {"code": 0}},
            events={"transfer.sender": ["alice"], "transfer.amount": ["100"]},
        )
        idx.index(
            {"height": 6, "index": 0, "tx": b"other", "result": {"code": 0}},
            events={"transfer.sender": ["bob"]},
        )
        got = idx.get(tx_hash(tx))
        assert got["height"] == 5 and got["tx"] == tx

        assert len(idx.search("transfer.sender='alice'")) == 1
        assert len(idx.search("tx.height=5")) == 1
        assert len(idx.search("tx.height>4")) == 2
        assert len(idx.search("transfer.sender='alice' AND tx.height=5")) == 1
        assert idx.search("transfer.sender='carol'") == []
