"""The store layer's counters: SQLiteDB's running totals of its write
transactions, the WriteMeter that turns them into a block's span fields,
and the tx indexer's lag, its count of txs indexed and the event its cut leaves.
"""

import asyncio
import hashlib
import logging

import pytest

from tendermint_tpu.libs import loopprof
from tendermint_tpu.libs.kvstore import MemDB, SQLiteDB, WriteMeter, open_db
from tendermint_tpu.libs.tracing import FlightRecorder
from tendermint_tpu.state.txindex import IndexerService, TxIndexer
from tendermint_tpu.types import events as tme


def test_sets_and_a_batch_count_as_transactions_and_rows(tmp_path):
    db = open_db("app", str(tmp_path))
    assert isinstance(db, SQLiteDB) and db.name == "app"
    n, m = 5, 7
    for i in range(n):
        db.set(b"k%d" % i, b"v" * 10)
    db.write_batch([(b"b%d" % i, b"w" * 20) for i in range(m)])
    txns, ns, rows, nbytes, max_ns = db.write_totals()
    assert (txns, rows) == (n + 1, n + m)
    assert nbytes == n * (2 + 10) + m * (2 + 20)
    assert 0 < max_ns <= ns
    # the slowest transaction is since the last reading; the totals run on
    assert db.write_totals() == (txns, ns, rows, nbytes, 0)
    db.delete(b"k0")
    db.write_batch([], deletes=[b"k1", b"k2"])
    after = db.write_totals()
    assert (after[0], after[2], after[3]) == (txns + 2, rows + 3, nbytes + 2 + 4) and after[1] > ns
    assert db.get(b"k1") is None and db.get(b"k3") == b"v" * 10
    db.close()


def test_a_failed_write_counts_nothing(tmp_path):
    db = SQLiteDB(str(tmp_path / "x.db"))
    with pytest.raises(Exception):
        db.write_batch([(b"k", b"v"), (b"bad", object())])
    assert db.write_totals() == (0, 0, 0, 0, 0) and db.get(b"k") is None
    db.close()


def test_the_meter_hands_a_blocks_deltas_by_store_and_nothing_on_memdb(tmp_path):
    meter = WriteMeter()
    app = meter.add(open_db("app", str(tmp_path)))
    state = meter.add(open_db("state", str(tmp_path)))
    assert meter.add(MemDB()).__class__ is MemDB  # handed back, not metered
    app.set(b"before", b"the first block")
    first = meter.lap()
    assert first["db_txns"] == 1 and first["db_ms.state"] == 0.0
    for i in range(3):
        app.set(b"k%d" % i, b"v")
    state.write_batch([(b"s", b"x" * 100), (b"t", b"y")])
    fields = meter.lap()
    assert set(fields) == {
        "db_ms", "db_txns", "db_rows", "db_bytes", "db_max_ms", "db_ms.app", "db_ms.state",
    }
    assert (fields["db_txns"], fields["db_rows"]) == (4, 5)
    assert fields["db_bytes"] == 3 * 3 + 101 + 2
    assert fields["db_ms.app"] + fields["db_ms.state"] == pytest.approx(fields["db_ms"])
    assert 0 < fields["db_max_ms"] <= fields["db_ms"]
    idle = meter.lap()
    assert idle["db_txns"] == 0 and idle["db_ms"] == 0.0 and idle["db_max_ms"] == 0.0
    # a node on memdb meters no store: the fields are absent, not 0
    on_memdb = WriteMeter()
    on_memdb.add(open_db("app", None, "memdb"))
    assert on_memdb.lap() == {}
    app.close()
    state.close()


async def publish_block(bus, height, n_txs):
    for i in range(n_txs):
        await bus.publish_tx(height, i, b"k%d.%d=v" % (height, i), {"code": 0, "data": b"", "log": ""})


async def drained():
    for _ in range(5):
        await asyncio.sleep(0)


async def test_the_lag_counts_blocks_behind_and_a_cut_leaves_one_event(caplog):
    bus = tme.EventBus()
    await bus.start()
    index = TxIndexer(MemDB())
    svc = IndexerService(index, bus)
    svc.BUFFER = 8
    svc.recorder = FlightRecorder(size=64)
    await svc.start()
    try:
        # a block is closed before the indexer has had a turn: one behind
        await publish_block(bus, 1, 3)
        assert svc.block_closed(1, 3) == {"txs": 3, "txs_indexed": 0, "index_lag": 1}
        await drained()
        await publish_block(bus, 2, 0)  # an empty block is indexed once those before it are
        assert svc.block_closed(2, 0) == {"txs": 0, "txs_indexed": 3, "index_lag": 0}
        assert svc.indexed_through == 2
        await publish_block(bus, 3, 3)
        await drained()
        assert svc.block_closed(3, 3) == {"txs": 3, "txs_indexed": 3, "index_lag": 0}
        # ten txs and no turn for the indexer: the ninth finds the buffer full
        with caplog.at_level(logging.ERROR, logger="indexer-service"):
            await publish_block(bus, 4, 10)
            assert svc.block_closed(4, 10)["index_lag"] == 1
            await drained()  # what was queued is indexed, then the task ends
        assert svc.indexed_through == 3  # block 4's last two txs never came
        closed = [svc.block_closed(h, 3) for h in (5, 6, 7)]
        assert [f["index_lag"] for f in closed] == [2, 3, 4]  # one more a block, for good
        assert [f["txs_indexed"] for f in closed] == [8, 0, 0]  # and nothing further is indexed
        cuts = svc.recorder.events(kinds=["txindex."])
        assert [(ev["kind"], ev["height"], ev["reason"], ev["indexed_through"]) for ev in cuts] == [
            ("txindex.cut", 4, "out of capacity", 3)
        ]
        assert [r.levelname for r in caplog.records] == ["ERROR"]
        assert "out of capacity" in caplog.records[0].getMessage()
        assert index.get(hashlib.sha256(b"k4.7=v").digest()) is not None
        assert index.get(hashlib.sha256(b"k4.8=v").digest()) is None
    finally:
        await svc.stop()
        await bus.stop()
    assert len(svc.recorder.events(kinds=["txindex."])) == 1  # stopping is no cut


async def test_the_indexers_task_is_spawned_so_the_loop_profiler_accounts_it():
    assert loopprof.categorize("indexer-service", "tx-indexer") == "other"
    prof = loopprof.LoopProfiler(interval=10.0)
    await prof.start()
    owner = loopprof.active()  # the process's first profiler accounts every spawn
    bus = tme.EventBus()
    await bus.start()
    svc = IndexerService(TxIndexer(MemDB()), bus)
    await svc.start()
    try:
        assert svc._task in svc._tasks and svc._task.get_name() == "tx-indexer"
        await publish_block(bus, 1, 20)
        await drained()
        assert svc.block_closed(1, 20) == {"txs": 20, "txs_indexed": 20, "index_lag": 0}
        assert owner.busy_ns["other"] > 0 and owner.steps["other"] >= 1
    finally:
        await svc.stop()
        await bus.stop()
        await prof.stop()
