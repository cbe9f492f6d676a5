"""The kvstore app writes a block in ONE store transaction at Commit.

What is held here: a block of DeliverTx adds no write transaction until
Commit and exactly one with it (`__state__` inside it); a kill in mid-block
leaves none of the block's rows in the file; responses, app hashes and
final rows equal a plain model that writes each row as it is delivered (the
app's behaviour before it staged), on memdb and on sqlite; a snapshot taken
at Commit holds the committing block; InitChain's validators are in the
store before any block; a write_batch that raises at Commit raises out of
commit() and leaves the store without any row of the block.
"""

import base64
import hashlib
import random
import sqlite3
import struct
import subprocess
import sys

import pytest

from tendermint_tpu.abci import types as t
from tendermint_tpu.abci.examples import KVStoreApplication
from tendermint_tpu.chaos.disk import DiskFaultTable, FaultyDB, policy_for
from tendermint_tpu.encoding import codec
from tendermint_tpu.libs.kvstore import MemDB, SQLiteDB

BACKENDS = ["memdb", "sqlite"]


def open_backend(backend, tmp_path):
    return MemDB() if backend == "memdb" else SQLiteDB(str(tmp_path / "data" / "app.db"))


def state_row(height, tx_count):
    app_hash = hashlib.sha256(struct.pack("<QQ", tx_count, height)).digest()
    return struct.pack("<QQB", height, tx_count, len(app_hash)) + app_hash


def deliver_block(app, txs, evidence=()):
    app.begin_block(t.RequestBeginBlock(byzantine_validators=list(evidence)))
    for tx in txs:
        assert app.deliver_tx(t.RequestDeliverTx(tx=tx)).is_ok
    app.end_block(t.RequestEndBlock(height=app.height + 1))


def val_tx(pub_key, power):
    return b"val:" + base64.b64encode(pub_key) + b"!%d" % power


# -- (a) one transaction a block ---------------------------------------------


def test_block_of_1000_txs_is_one_sqlite_transaction(tmp_path):
    db = SQLiteDB(str(tmp_path / "app.db"))
    app = KVStoreApplication(db)
    deliver_block(app, [b"warm=up"])
    app.commit()
    txns0, _, rows0, _, _ = db.write_totals()

    deliver_block(app, [b"k2.%d=%s" % (i, b"v" * 200) for i in range(1000)])
    assert db.write_totals()[0] == txns0  # nothing reached the store yet
    assert db.get(b"kv:k2.0") is None
    app.commit()
    txns1, _, rows1, _, _ = db.write_totals()
    assert txns1 - txns0 == 1
    assert rows1 - rows0 == 1001  # the 1,000 rows and __state__, together
    assert db.get(b"__state__") == state_row(2, 1001)
    assert db.get(b"kv:k2.999") == b"v" * 200
    db.close()


def test_empty_block_is_one_transaction_too(tmp_path):
    db = SQLiteDB(str(tmp_path / "app.db"))
    app = KVStoreApplication(db)
    txns0 = db.write_totals()[0]
    deliver_block(app, [])
    app.commit()
    assert db.write_totals()[0] - txns0 == 1
    assert db.get(b"__state__") == state_row(1, 0)
    db.close()


# -- (b) the kill shape: what another reader of the file finds ---------------

_READER = """
import sqlite3, sys
conn = sqlite3.connect("file:" + sys.argv[1] + "?mode=ro", uri=True)
for k, v in conn.execute("SELECT k, v FROM kv ORDER BY k"):
    print(bytes(k).hex(), bytes(v).hex())
"""


def rows_by_second_connection(path):
    conn = sqlite3.connect(path)
    try:
        return {bytes(k): bytes(v) for k, v in conn.execute("SELECT k, v FROM kv")}
    finally:
        conn.close()


def rows_by_fresh_process(path):
    out = subprocess.run(
        [sys.executable, "-c", _READER, path], check=True, capture_output=True, text=True, timeout=60
    ).stdout
    return {bytes.fromhex(k): bytes.fromhex(v) for k, v in (line.split() for line in out.splitlines())}


@pytest.mark.parametrize("read_rows", [rows_by_second_connection, rows_by_fresh_process])
def test_mid_block_the_file_holds_none_of_the_block(tmp_path, read_rows):
    path = str(tmp_path / "app.db")
    app = KVStoreApplication(SQLiteDB(path))
    deliver_block(app, [b"h1.%d=a" % i for i in range(5)])
    app.commit()
    before = read_rows(path)
    assert before[b"__state__"] == state_row(1, 5)

    n = 40
    deliver_block(app, [b"h2.%d=b" % i for i in range(n)] + [b"h1.0=overwritten"])
    # no Commit: a kill here leaves exactly what the file held a block ago
    assert read_rows(path) == before

    app.commit()
    after = read_rows(path)
    assert after[b"__state__"] == state_row(2, 5 + n + 1)
    assert all(after[b"kv:h2.%d" % i] == b"b" for i in range(n))
    assert after[b"kv:h1.0"] == b"overwritten"
    assert len(after) == len(before) + n
    app.db.close()


# -- (c) a seeded chain against a plain model that writes as it goes ---------


class PlainKVStore:
    """The kvstore's semantics with nothing staged: every write lands in
    `rows` as it is made, reads read `rows`."""

    def __init__(self):
        self.rows = {}
        self.height = 0
        self.tx_count = 0
        self.app_hash = b""
        self.validators = {}
        self.updates = []

    def begin_block(self, evidence):
        self.updates = []
        addrs = set(self.rows.get(b"kv:__byzantine__", b"").split(b",")) - {b""}
        for ev in evidence:
            addrs.add(ev["address"].hex().encode())
        if addrs:
            self.rows[b"kv:__byzantine__"] = b",".join(sorted(addrs))
        return t.ResponseBeginBlock()

    def deliver_tx(self, tx):
        if tx.startswith(b"val:"):
            pk_b64, power = tx[4:].split(b"!", 1)
            vu = t.ValidatorUpdate("ed25519", base64.b64decode(pk_b64), int(power))
            if vu.power == 0:
                self.validators.pop(vu.pub_key, None)
                self.rows.pop(b"__val__" + vu.pub_key, None)
            else:
                self.validators[vu.pub_key] = vu.power
                self.rows[b"__val__" + vu.pub_key] = struct.pack("<q", vu.power)
            self.updates.append(vu)
            return t.ResponseDeliverTx(code=t.CODE_TYPE_OK)
        key, value = tx.split(b"=", 1) if b"=" in tx else (tx, tx)
        self.rows[b"kv:" + key] = value
        self.tx_count += 1
        attrs = [{"key": b"creator", "value": b"tendermint_tpu"}, {"key": b"key", "value": key}]
        return t.ResponseDeliverTx(code=t.CODE_TYPE_OK, events=[t.Event(type="app", attributes=attrs)])

    def end_block(self):
        return t.ResponseEndBlock(validator_updates=list(self.updates))

    def commit(self):
        self.height += 1
        self.app_hash = hashlib.sha256(struct.pack("<QQ", self.tx_count, self.height)).digest()
        self.rows[b"__state__"] = state_row(self.height, self.tx_count)
        return t.ResponseCommit(data=self.app_hash, retain_height=0)

    def query(self, data, path=""):
        if path == "/val":
            return t.ResponseQuery(code=t.CODE_TYPE_OK, value=struct.pack("<q", self.validators.get(data, 0)))
        value = self.rows.get(b"kv:" + data)
        if value is None:
            return t.ResponseQuery(code=t.CODE_TYPE_OK, key=data, log="does not exist")
        return t.ResponseQuery(code=t.CODE_TYPE_OK, key=data, value=value, log="exists", height=self.height)


def seeded_chain(seed, heights=12):
    """Blocks as (evidence, txs, queries): plain txs, a key set twice in a
    block, a validator deleted and re-added (and added and deleted) in one
    block, evidence in two consecutive BeginBlocks, queries for fresh, old,
    overwritten and missing keys between DeliverTx and Commit."""
    rng = random.Random(seed)
    val_a, val_b = bytes([1]) * 32, bytes([2]) * 32
    blocks = []
    for h in range(1, heights + 1):
        txs = [b"k%d.%d=%d" % (h, i, rng.randrange(10**6)) for i in range(rng.randrange(0, 9))]
        queries = [(b"k%d.0" % h, ""), (b"k%d.0" % max(1, h - 1), ""), (b"nobody", ""), (val_a, "/val")]
        ordered = []  # txs whose order in the block is the point
        if h % 3 == 0:  # a key set twice in one block, and one from an older block overwritten
            ordered += [b"twice=first%d" % h, b"twice=second%d" % h, b"k1.0=rewritten%d" % h]
            queries += [(b"twice", ""), (b"k1.0", "")]
        if h == 2:
            ordered += [val_tx(val_a, 5), val_tx(val_b, 9)]
        if h == 5:  # deleted and set again in one block: must not end up deleted
            ordered += [val_tx(val_a, 0), val_tx(val_a, 7)]
        if h == 7:  # set and deleted in one block: must end up deleted
            ordered += [val_tx(val_b, 3), val_tx(val_b, 0)]
        if h == 9:
            ordered += [val_tx(val_a, 0)]
        at = 0
        for tx in ordered:  # among the plain txs, each after the one before it
            at = rng.randrange(at, len(txs) + 1)
            txs.insert(at, tx)
            at += 1
        evidence = [{"address": bytes([h]) * 20}] if h in (4, 5, 10) else []
        if evidence:
            queries.append((b"__byzantine__", ""))
        blocks.append((evidence, txs, queries))
    return blocks


@pytest.mark.parametrize("seed", [7, 2147483659])
@pytest.mark.parametrize("backend", BACKENDS)
def test_chain_equals_the_plain_model(tmp_path, backend, seed):
    db = open_backend(backend, tmp_path)
    app, model = KVStoreApplication(db), PlainKVStore()
    for evidence, txs, queries in seeded_chain(seed):
        assert app.begin_block(t.RequestBeginBlock(byzantine_validators=evidence)) == model.begin_block(evidence)
        for tx in txs:
            assert app.deliver_tx(t.RequestDeliverTx(tx=tx)) == model.deliver_tx(tx)
        for data, path in queries:  # between DeliverTx and Commit
            assert app.query(t.RequestQuery(data=data, path=path)) == model.query(data, path)
        assert app.end_block(t.RequestEndBlock(height=app.height + 1)) == model.end_block()
        assert app.commit() == model.commit()
        assert app.info(t.RequestInfo()).last_block_app_hash == model.app_hash
        assert list(db.iterate_prefix(b"")) == sorted(model.rows.items())
        for data, path in queries:  # and after it
            assert app.query(t.RequestQuery(data=data, path=path)) == model.query(data, path)
    assert model.validators == {} and model.rows[b"kv:twice"] == b"second12"  # the chain did what it says
    # a restart reads the same app back
    again = KVStoreApplication(db)
    assert (again.height, again.tx_count, again.app_hash) == (model.height, model.tx_count, model.app_hash)
    assert again.validators == model.validators
    db.close()


# -- (d) a snapshot taken at Commit holds the committing block ---------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_snapshot_at_commit_holds_the_committing_block(tmp_path, backend):
    db = open_backend(backend, tmp_path)
    app = KVStoreApplication(db, snapshot_interval=2, snapshot_chunk_bytes=64)
    deliver_block(app, [b"a=1"])
    app.commit()
    deliver_block(app, [b"b=2", b"a=3"])
    app.commit()  # height 2: the snapshot is taken inside this call
    (snap,) = app.list_snapshots(t.RequestListSnapshots()).snapshots
    assert snap.height == 2
    chunks = [
        app.load_snapshot_chunk(t.RequestLoadSnapshotChunk(height=2, format=snap.format, chunk=i)).chunk
        for i in range(snap.chunks)
    ]
    entries = dict(codec.loads(b"".join(chunks))["entries"])
    assert entries == {b"kv:a": b"3", b"kv:b": b"2", b"__state__": state_row(2, 3)}

    restored = KVStoreApplication(MemDB())
    assert restored.offer_snapshot(t.RequestOfferSnapshot(snapshot=snap, app_hash=app.app_hash)).result == (
        t.OfferSnapshotResult.ACCEPT
    )
    for i, chunk in enumerate(chunks):
        r = restored.apply_snapshot_chunk(t.RequestApplySnapshotChunk(index=i, chunk=chunk))
        assert r.result == t.ApplySnapshotChunkResult.ACCEPT
    assert restored.query(t.RequestQuery(data=b"b")).value == b"2"
    assert (restored.height, restored.app_hash) == (2, app.app_hash)
    db.close()


# -- (e) InitChain's validators are in the store before any block ------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_init_chain_validators_reach_the_store(tmp_path, backend):
    db = open_backend(backend, tmp_path)
    app = KVStoreApplication(db)
    vals = [t.ValidatorUpdate("ed25519", bytes([i]) * 32, 10 + i) for i in range(1, 4)]
    app.init_chain(t.RequestInitChain(validators=vals))
    if backend == "sqlite":
        assert db.write_totals()[0] == 1  # one batch, not one write a validator
        db.close()
        db = SQLiteDB(db.path)  # a restart before the first block
    assert KVStoreApplication(db).validators == {v.pub_key: v.power for v in vals}
    db.close()


# -- (f) a write_batch that raises at Commit ---------------------------------


@pytest.mark.parametrize("fault", ["enospc", "eio"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_failing_write_batch_raises_out_of_commit(tmp_path, backend, fault):
    inner = open_backend(backend, tmp_path)
    table = DiskFaultTable(seed=3)
    app = KVStoreApplication(FaultyDB(inner, table, "app"))
    deliver_block(app, [b"a=1"])
    app.commit()
    before = list(inner.iterate_prefix(b""))

    table.set_policy("app", policy_for(fault))
    deliver_block(app, [b"b=2", b"a=3", val_tx(bytes([9]) * 32, 4)])  # a dying disk does not fail DeliverTx now
    with pytest.raises(OSError):
        app.commit()
    assert list(inner.iterate_prefix(b"")) == before  # none of the block, __state__ a height behind
    # the node halts on that error; what restarts reads the block before
    table.heal()
    again = KVStoreApplication(inner)
    assert (again.height, again.tx_count, again.validators) == (1, 1, {})
    assert again.query(t.RequestQuery(data=b"a")).value == b"1"
    inner.close()
