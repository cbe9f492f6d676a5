"""MemDB held to a plain reference: a dict, and `sorted()` at every read.

A write puts the row in the dict and a new key among the pending ones; the
first ordered read folds them into the sorted key list, one by one where
they are few, else in one sorted merge.  Whatever the order of writes,
deletes and reads, every reader sees the keys and the order the reference
gives, and the fold counter says how often the order was brought up to date.
"""

import random

import pytest

from tendermint_tpu.libs.kvstore import MemDB

# keys over a small alphabet, so that writes collide, deletes hit and a
# prefix of 0xff bytes has keys under it
ALPHABET = (b"\x00", b"a", b"b", b"\xfe", b"\xff")


class Reference:
    def __init__(self):
        self.data = {}

    def set(self, k, v):
        self.data[k] = v

    def delete(self, k):
        self.data.pop(k, None)

    def write_batch(self, sets, deletes=()):
        for k, v in sets:
            self.data[k] = v
        for k in deletes:
            self.data.pop(k, None)

    def iterate_prefix(self, prefix):
        return [(k, self.data[k]) for k in sorted(self.data) if k.startswith(prefix)]

    def get(self, k):
        return self.data.get(k)


def _key(rng):
    return b"".join(rng.choice(ALPHABET) for _ in range(rng.randint(0, 4)))


def _prefix(rng):
    return rng.choice([b"", b"\xff", b"\xff\xff", b"a", b"\x00", _key(rng)])


def _same(db, ref, prefix):
    assert list(db.iterate_prefix(prefix)) == ref.iterate_prefix(prefix), prefix


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("batch", [3, 200], ids=["one_by_one", "merged"])
def test_random_sequences_read_as_the_reference(seed, batch):
    """Random sets, deletes, batches, ordered reads and gets; a batch of
    `batch` keys folds one by one (3) or in one merge (200, over
    FOLD_ONE_BY_ONE) at the next read."""
    assert 3 <= MemDB.FOLD_ONE_BY_ONE < 200
    rng = random.Random(seed)
    db, ref = MemDB(), Reference()
    for step in range(400):
        op = rng.random()
        if op < 0.3:
            k, v = _key(rng), b"v%d" % step
            db.set(k, v)
            ref.set(k, v)
        elif op < 0.45:
            k = _key(rng) if rng.random() < 0.5 or not ref.data else rng.choice(list(ref.data))
            db.delete(k)
            ref.delete(k)
        elif op < 0.6:
            sets = [(_key(rng) + b"%d" % i, b"b%d.%d" % (step, i)) for i in range(rng.randint(0, batch))]
            deletes = [_key(rng) for _ in range(rng.randint(0, 4))] + rng.sample(
                sorted(ref.data), min(2, len(ref.data))
            )
            db.write_batch(sets, deletes)
            ref.write_batch(sets, deletes)
        elif op < 0.85:
            _same(db, ref, _prefix(rng))
        else:
            k = _key(rng)
            assert db.get(k) == ref.get(k)
    _same(db, ref, b"")
    assert {k: db.get(k) for k in ref.data} == ref.data


def test_the_empty_prefix_reads_every_key_in_order():
    db = MemDB()
    keys = [b"\xff", b"", b"b", b"a\x00", b"a", b"\x00"]
    for k in keys:
        db.set(k, k + b"!")
    assert [k for k, _ in db.iterate_prefix(b"")] == sorted(keys)
    assert [v for _, v in db.iterate_prefix(b"")] == [k + b"!" for k in sorted(keys)]


def test_a_prefix_of_ff_bytes():
    db = MemDB()
    db.write_batch([(b"\xff\xff\x01", b"3"), (b"\xfe\xff", b"1"), (b"\xff", b"2"), (b"\xff\xff", b"4")])
    assert list(db.iterate_prefix(b"\xff")) == [
        (b"\xff", b"2"), (b"\xff\xff", b"4"), (b"\xff\xff\x01", b"3")
    ]
    assert list(db.iterate_prefix(b"\xff\xff")) == [(b"\xff\xff", b"4"), (b"\xff\xff\x01", b"3")]
    assert list(db.iterate_prefix(b"\xff\xff\xff")) == []


def test_a_key_deleted_while_pending_is_gone_and_folds_nothing():
    db = MemDB()
    db.set(b"a", b"1")
    db.write_batch([(b"b", b"2"), (b"c", b"3")], deletes=[b"b"])
    db.delete(b"a")
    assert db.get(b"a") is None and db.get(b"b") is None
    assert list(db.iterate_prefix(b"")) == [(b"c", b"3")]
    assert db.fold_totals()[:2] == (1, 1)


def test_a_key_set_again_after_its_deletion():
    db = MemDB()
    db.set(b"k", b"1")
    assert list(db.iterate_prefix(b"k")) == [(b"k", b"1")]  # now in order
    db.delete(b"k")
    assert list(db.iterate_prefix(b"k")) == []
    db.set(b"k", b"2")  # pending again
    db.delete(b"k")
    db.write_batch([(b"k", b"3")])
    assert list(db.iterate_prefix(b"")) == [(b"k", b"3")]


def test_an_ordered_read_between_two_batches():
    db = MemDB()
    first = [(b"kv:k%d" % i, b"1") for i in range(0, 300, 2)]
    second = [(b"kv:k%d" % i, b"2") for i in range(1, 300, 2)] + [(b"kv:k0", b"again")]
    db.write_batch(first)
    assert [k for k, _ in db.iterate_prefix(b"kv:")] == sorted(k for k, _ in first)
    db.write_batch(second)
    got = list(db.iterate_prefix(b"kv:"))
    assert [k for k, _ in got] == sorted({k for k, _ in first + second})
    assert dict(got)[b"kv:k0"] == b"again" and dict(got)[b"kv:k3"] == b"2"


@pytest.mark.parametrize(
    "bad", [(b"x", object()), ("str-key", b"v")], ids=["value_not_bytes", "key_not_bytes"]
)
def test_a_batch_that_raises_leaves_the_store_pending_keys_included(bad):
    db = MemDB()
    db.write_batch([(b"ordered", b"o")])
    list(db.iterate_prefix(b""))  # `ordered` folded
    db.set(b"pending", b"p")
    before = (dict(db._data), list(db._keys), set(db._pending), db.fold_totals())
    with pytest.raises(TypeError):
        db.write_batch([(b"new", b"n"), (b"pending", b"changed"), bad], deletes=[b"ordered"])
    assert (dict(db._data), list(db._keys), set(db._pending), db.fold_totals()) == before
    assert list(db.iterate_prefix(b"")) == [(b"ordered", b"o"), (b"pending", b"p")]


def test_a_key_that_is_not_bytes_is_refused_as_it_is_written():
    db = MemDB()
    with pytest.raises(TypeError):
        db.set("k", b"v")
    assert db.get("k") is None and list(db.iterate_prefix(b"")) == []


def test_writes_with_no_reader_fold_nothing_and_one_read_folds_them_once():
    db = MemDB()
    for h in range(100):
        db.write_batch([(b"kv:k%d.%d" % (h, i), b"v") for i in range(1000)])
    assert db.fold_totals() == (0, 0, 0)
    rows = list(db.iterate_prefix(b"kv:"))
    folds, folded, ns = db.fold_totals()
    assert (folds, folded) == (1, 100_000) and ns > 0
    assert len(rows) == 100_000 and [k for k, _ in rows] == sorted(k for k, _ in rows)
    assert list(db.iterate_prefix(b"kv:k7.")) == [r for r in rows if r[0].startswith(b"kv:k7.")]
    assert db.fold_totals() == (folds, folded, ns)  # nothing pending: no fold
    db.set(b"kv:k5.5", b"rewritten")  # a key the store has: not pending
    db.get(b"kv:k5.5")
    list(db.iterate_prefix(b"kv:k5.5"))
    assert db.fold_totals() == (folds, folded, ns)


def test_a_few_pending_keys_fold_one_by_one(monkeypatch):
    import bisect

    inserted = []
    insort = bisect.insort
    monkeypatch.setattr(bisect, "insort", lambda a, x: (inserted.append(x), insort(a, x)))
    db = MemDB()
    db.write_batch([(b"%05d" % i, b"v") for i in range(0, 1000, 2)])
    list(db.iterate_prefix(b""))
    assert inserted == []  # 500 pending keys: one merge
    few = [b"%05d" % i for i in (999, 1, 501)]
    for k in few:
        db.set(k, b"new")
    assert list(db.iterate_prefix(b"0050")) == [
        (b"00500", b"v"), (b"00501", b"new"), (b"00502", b"v"), (b"00504", b"v"),
        (b"00506", b"v"), (b"00508", b"v"),
    ]
    assert sorted(inserted) == sorted(few)
    assert db.fold_totals()[:2] == (2, 500 + len(few))
    assert db._keys == sorted(db._data) and not db._pending


async def test_storage_info_reports_each_memdb_stores_folds(tmp_path):
    """A solo node on memdb: every store the node opened is reported by
    name, and the kvstore app's, written every block and read in order by
    nothing, reads 0 folds."""
    from tendermint_tpu.rpc.core import RPCCore

    from tests.test_consensus import solo_node, wait_blocks

    node, _ = solo_node(tmp_path)
    await node.start()
    try:
        await wait_blocks(node, 3)
        info = await RPCCore(node).storage_info()
    finally:
        await node.stop()
    folds = info["memdb_folds"]
    assert set(folds) == {"blockstore", "state", "app", "tx_index", "evidence"}
    assert folds["app"] == {"folds": 0, "keys_folded": 0, "fold_ns": 0}
    assert node.dbs["app"].get(b"__state__") is not None
    for counts in folds.values():
        assert set(counts) == {"folds", "keys_folded", "fold_ns"}
