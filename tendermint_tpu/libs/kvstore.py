"""Embedded key-value store abstraction.

Counterpart of the reference's tm-db dependency (goleveldb et al. behind
`dbm.DB`): ordered byte-keyed store with batched atomic writes and prefix
iteration.  Two backends: in-memory (tests, like tm-db memdb) and SQLite
(durable; ships with CPython, no external deps allowed in this image).
"""

from __future__ import annotations

import bisect
import os
import sqlite3
import threading
import time
from abc import ABC, abstractmethod
from typing import Dict, Iterator, List, Optional, Set, Tuple


class KVStore(ABC):
    @abstractmethod
    def get(self, key: bytes) -> Optional[bytes]: ...

    @abstractmethod
    def set(self, key: bytes, value: bytes) -> None: ...

    @abstractmethod
    def delete(self, key: bytes) -> None: ...

    @abstractmethod
    def iterate_prefix(self, prefix: bytes) -> Iterator[Tuple[bytes, bytes]]:
        """Ordered iteration over keys starting with prefix."""

    @abstractmethod
    def write_batch(self, sets: List[Tuple[bytes, bytes]], deletes: List[bytes] = ()) -> None:
        """Atomic multi-write."""

    def has(self, key: bytes) -> bool:
        return self.get(key) is not None

    def close(self) -> None:
        pass


def _check_keys(keys) -> None:
    """A key is bytes: MemDB orders its keys only when something reads the
    order, so a key that cannot be compared with the others is refused as
    it is written."""
    for k in keys:
        if not isinstance(k, bytes):
            raise TypeError(f"a key is bytes, not {type(k).__name__}")


class MemDB(KVStore):
    """Sorted in-memory store (reference memdb equivalent).

    The dict holds the store; `_keys` keeps its keys in order for
    `iterate_prefix`.  A write does not keep that order: a new key joins
    `_pending`, and the first ordered read folds the pending keys in, one
    by one where they are few, else sorted and merged in one pass (a sorted
    list followed by a sorted run is linear under Timsort).  A block's
    1,000 new rows then cost dict inserts, where an insertion into the list
    shifted its tail for each of them.  Keeps running totals of its folds
    (`fold_totals`): a few integer adds a fold, no event."""

    #: up to this many pending keys a fold inserts one by one: each
    #: insertion shifts the list's tail, a merge passes over all of it, and
    #: the two cost about the same near this count, for lists of a thousand
    #: keys to half a million
    FOLD_ONE_BY_ONE = 64

    def __init__(self):
        self._data: Dict[bytes, bytes] = {}
        self._keys: List[bytes] = []  # in order: every key but the pending ones
        self._pending: Set[bytes] = set()  # written since the last fold
        self._lock = threading.Lock()
        self._folds = self._folded = self._fold_ns = 0

    def get(self, key: bytes) -> Optional[bytes]:
        return self._data.get(key)

    def set(self, key: bytes, value: bytes) -> None:
        _check_keys((key,))
        value = bytes(value)
        with self._lock:
            if key not in self._data:
                self._pending.add(key)
            self._data[key] = value

    def delete(self, key: bytes) -> None:
        with self._lock:
            self._delete_locked(key)

    def _delete_locked(self, key: bytes) -> None:
        if key in self._data:
            del self._data[key]
            if key in self._pending:
                self._pending.remove(key)
                return
            idx = bisect.bisect_left(self._keys, key)
            if idx < len(self._keys) and self._keys[idx] == key:
                self._keys.pop(idx)

    def _fold_locked(self) -> None:
        t0 = time.perf_counter_ns()
        n = len(self._pending)
        if n <= self.FOLD_ONE_BY_ONE:
            for k in self._pending:
                bisect.insort(self._keys, k)
        else:
            self._keys += sorted(self._pending)
            self._keys.sort()
        self._pending.clear()
        self._folds += 1
        self._folded += n
        self._fold_ns += time.perf_counter_ns() - t0

    def fold_totals(self) -> Tuple[int, int, int]:
        """(folds, keys folded, ns spent folding) since the store was made."""
        with self._lock:
            return self._folds, self._folded, self._fold_ns

    def iterate_prefix(self, prefix: bytes) -> Iterator[Tuple[bytes, bytes]]:
        with self._lock:
            if self._pending:
                self._fold_locked()
            start = bisect.bisect_left(self._keys, prefix)
            snapshot = []
            for i in range(start, len(self._keys)):
                k = self._keys[i]
                if not k.startswith(prefix):
                    break
                snapshot.append((k, self._data[k]))
        yield from snapshot

    def write_batch(self, sets, deletes=()) -> None:
        # materialize + copy BEFORE mutating: an iterable that raises (or a
        # value that fails bytes(), or a key that is not bytes) mid-batch
        # must leave the store exactly as it was — write_batch promises
        # all-or-nothing
        staged = [(k, bytes(v)) for k, v in sets]
        _check_keys(k for k, _ in staged)
        staged_deletes = list(deletes)
        with self._lock:
            for k, v in staged:
                if k not in self._data:
                    self._pending.add(k)
                self._data[k] = v
            for k in staged_deletes:
                self._delete_locked(k)


class SQLiteDB(KVStore):
    """Durable backend over sqlite3 with WAL journaling.

    Keeps running totals of its write transactions (`write_totals`): a few
    integer adds per write, no event (a block of 1,000 txs makes over 2,000
    of them).  WriteMeter turns them into per-block span fields."""

    def __init__(self, path: str):
        self.path = path  # storage_info / debug bundles report per-store usage
        self.name = os.path.splitext(os.path.basename(path))[0]  # open_db's name
        # committed write transactions, ns inside them (execute + commit; the
        # wait for the lock apart), rows, bytes of keys and values, and the
        # slowest one since write_totals() was last read
        self._txns = self._write_ns = self._rows = self._bytes = self._max_ns = 0
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._lock = threading.Lock()
        with self._lock:
            self._conn.execute("PRAGMA journal_mode=WAL")
            self._conn.execute("PRAGMA synchronous=NORMAL")
            self._conn.execute("CREATE TABLE IF NOT EXISTS kv (k BLOB PRIMARY KEY, v BLOB)")
            self._conn.commit()

    def get(self, key: bytes) -> Optional[bytes]:
        with self._lock:
            row = self._conn.execute("SELECT v FROM kv WHERE k = ?", (key,)).fetchone()
        return bytes(row[0]) if row else None

    def _rollback(self) -> None:
        """Best-effort rollback after a failed write: without it the NEXT
        commit (any later set) would flush the half-applied statements —
        a crashed batch observed half-applied later."""
        try:
            self._conn.rollback()
        except sqlite3.Error:
            pass

    def _committed(self, t0_ns: int, rows: int, nbytes: int) -> None:
        """Count one committed write transaction (called under the lock)."""
        ns = time.perf_counter_ns() - t0_ns
        self._txns += 1
        self._write_ns += ns
        self._rows += rows
        self._bytes += nbytes
        if ns > self._max_ns:
            self._max_ns = ns

    def write_totals(self) -> Tuple[int, int, int, int, int]:
        """(transactions, ns, rows, bytes) committed since the store was
        opened, and the slowest transaction's ns since the last call."""
        with self._lock:
            max_ns, self._max_ns = self._max_ns, 0
            return self._txns, self._write_ns, self._rows, self._bytes, max_ns

    def set(self, key: bytes, value: bytes) -> None:
        with self._lock:
            t0 = time.perf_counter_ns()
            try:
                self._conn.execute("INSERT OR REPLACE INTO kv VALUES (?, ?)", (key, value))
                self._conn.commit()
            except BaseException:
                self._rollback()
                raise
            self._committed(t0, 1, len(key) + len(value))

    def delete(self, key: bytes) -> None:
        with self._lock:
            t0 = time.perf_counter_ns()
            try:
                self._conn.execute("DELETE FROM kv WHERE k = ?", (key,))
                self._conn.commit()
            except BaseException:
                self._rollback()
                raise
            self._committed(t0, 1, len(key))

    @staticmethod
    def _prefix_upper_bound(prefix: bytes) -> Optional[bytes]:
        """Smallest byte string greater than every key with this prefix, or
        None when the prefix is all 0xff (no upper bound exists)."""
        p = bytearray(prefix)
        while p:
            if p[-1] != 0xFF:
                p[-1] += 1
                return bytes(p)
            p.pop()
        return None

    def iterate_prefix(self, prefix: bytes) -> Iterator[Tuple[bytes, bytes]]:
        hi = self._prefix_upper_bound(prefix)
        with self._lock:
            if hi is None:
                rows = self._conn.execute(
                    "SELECT k, v FROM kv WHERE k >= ? ORDER BY k", (prefix,)
                ).fetchall()
            else:
                rows = self._conn.execute(
                    "SELECT k, v FROM kv WHERE k >= ? AND k < ? ORDER BY k", (prefix, hi)
                ).fetchall()
        for k, v in rows:
            if bytes(k).startswith(prefix):
                yield bytes(k), bytes(v)

    def write_batch(self, sets, deletes=()) -> None:
        # atomicity across a crash: every statement inside ONE transaction,
        # explicit rollback on ANY failure (incl. injected fsync/commit
        # errors) — a batch must never be observable half-applied
        staged = list(sets)
        staged_deletes = [(k,) for k in deletes]
        nbytes = sum(len(k) + len(v) for k, v in staged) + sum(len(k) for (k,) in staged_deletes)
        with self._lock:
            t0 = time.perf_counter_ns()
            try:
                self._conn.executemany("INSERT OR REPLACE INTO kv VALUES (?, ?)", staged)
                if staged_deletes:
                    self._conn.executemany("DELETE FROM kv WHERE k = ?", staged_deletes)
                self._conn.commit()
            except BaseException:
                self._rollback()
                raise
            self._committed(t0, len(staged) + len(staged_deletes), nbytes)

    def close(self) -> None:
        with self._lock:
            self._conn.close()


def open_db(name: str, home: Optional[str] = None, backend: str = "sqlite") -> KVStore:
    """DBProvider equivalent (node/node.go:62): named DBs under home/data."""
    if backend == "memdb" or home is None:
        return MemDB()
    return SQLiteDB(os.path.join(home, "data", f"{name}.db"))


class WriteMeter:
    """What a node's on-disk stores wrote between two readings, as span
    fields: `lap()` at each block's end gives the block's `db_ms`, `db_txns`,
    `db_rows`, `db_bytes`, `db_max_ms` (the slowest single transaction) and
    `db_ms.<store>` for each store, which add up to `db_ms`.  Only SQLiteDB
    stores count; a node on memdb has none and `lap()` is empty: the fields
    are absent, not 0."""

    def __init__(self):
        self._stores: List[list] = []  # [store, its totals at the last reading]

    def add(self, db: KVStore) -> KVStore:
        """Meter `db` if it is on disk; returns it, for the caller's chain."""
        if isinstance(db, SQLiteDB):
            self._stores.append([db, db.write_totals()[:4]])
        return db

    def lap(self) -> Dict[str, float]:
        if not self._stores:
            return {}
        total = [0, 0, 0, 0]  # transactions, ns, rows, bytes
        slowest = 0
        fields: Dict[str, float] = {}
        for entry in self._stores:
            db, last = entry
            *now, max_ns = db.write_totals()
            entry[1] = now
            delta = [a - b for a, b in zip(now, last)]
            total = [t + d for t, d in zip(total, delta)]
            slowest = max(slowest, max_ns)
            fields[f"db_ms.{db.name}"] = delta[1] / 1e6
        txns, ns, rows, nbytes = total
        fields.update(
            db_ms=ns / 1e6, db_txns=txns, db_rows=rows, db_bytes=nbytes, db_max_ms=slowest / 1e6
        )
        return fields
