"""What a node's on-disk stores must hold after it replayed a generated
chain, and whether its database files hold it.

The plain reference of the configuration `hub-175-sqlite`'s fifth guarantee:
a block whose `NewBlock` the node published, the state after it and every
kvstore write it made are read back from the database files by another
process after the node is killed without closing them.  Nothing here
imports the program: the chain is read from the generator's files (the
blocks as the wire carries them are plain msgpack maps), the app's database
with `sqlite3`, its state row with `struct`.

No benchmark run calls this file.  `benchmarks/check_store.py` (the
builder's chip check) and `tests/bench/test_bench_store.py` (a node killed in
mid-replay on the CPU) do; moving `held()` into the comparison that decides
`correct` is a `benchmark` PR's (ROADMAP 2b).
"""

from __future__ import annotations

import glob
import json
import os
import sqlite3
import struct
from dataclasses import dataclass
from typing import Dict

import msgpack

from benchmarks import reference

VALIDATOR_TX_PREFIX = b"val:"  # the kvstore's validator updates: no key=value pair
KV_PREFIX = b"kv:"  # the kvstore's rows in app.db
STATE_KEY = b"__state__"  # height, tx count, app hash: struct "<QQB" and the hash


@dataclass
class Expected:
    """What the stores hold after `height` blocks of a generated chain."""

    height: int
    block_ids: Dict[int, str]  # height -> block hash, hex
    pairs: Dict[bytes, bytes]  # every key=value the kvstore was delivered
    tx_count: int  # key=value transactions delivered (a `val:` one does not count)
    app_hash: bytes


def load_meta(chain_dir: str) -> dict:
    with open(os.path.join(chain_dir, "meta.json")) as f:
        return json.load(f)


def read_block(chain_dir: str, meta: dict, height: int) -> dict:
    """Block `height` as a plain map (`header`, `txs`, `last_commit`), out
    of the bytes the sources serve."""
    if not 1 <= height <= meta["heights"]:
        raise ValueError(f"height {height} is not on a chain of {meta['heights']}")
    lo, hi = meta["offsets"][height - 1], meta["offsets"][height]
    with open(os.path.join(chain_dir, "blocks.bin"), "rb") as f:
        f.seek(lo)
        block = msgpack.unpackb(f.read(hi - lo), raw=False)
    if block["header"]["height"] != height:
        raise ValueError(f"blocks.bin holds height {block['header']['height']} where {height} should be")
    return block


def expected(chain_dir: str, height: int) -> Expected:
    """From a generated chain's files: what the stores must hold after
    `height` blocks."""
    meta = load_meta(chain_dir)
    pairs: Dict[bytes, bytes] = {}
    tx_count = 0
    for h in range(1, height + 1):
        for tx in read_block(chain_dir, meta, h)["txs"]:
            if tx.startswith(VALIDATOR_TX_PREFIX):
                continue
            key, _, value = tx.partition(b"=")
            pairs[key] = value if b"=" in tx else tx  # a tx without "=" is its own value
            tx_count += 1
    return Expected(
        height=height, block_ids={h: meta["hashes"][h - 1] for h in range(1, height + 1)},
        pairs=pairs, tx_count=tx_count, app_hash=reference.kvstore_app_hash(tx_count, height),
    )


def _read_only(path: str) -> sqlite3.Connection:
    return sqlite3.connect(f"file:{path}?mode=ro", uri=True)


def app_state(home: str) -> tuple:
    """(height, tx_count, app_hash) of the `__state__` row in app.db;
    zeros where there is none."""
    conn = _read_only(os.path.join(home, "data", "app.db"))
    try:
        row = conn.execute("SELECT v FROM kv WHERE k = ?", (STATE_KEY,)).fetchone()
    finally:
        conn.close()
    if row is None:
        return 0, 0, b""
    raw = bytes(row[0])
    height, tx_count, hash_len = struct.unpack("<QQB", raw[:17])
    return height, tx_count, raw[17:17 + hash_len]


def held(home: str, chain_dir: str, height: int) -> Dict[str, int]:
    """Counts of what the database files under `home` miss of what the node
    acknowledged up to `height`; all 0 when they hold it.

    The node may have been killed further on: a block past `height` may be
    half delivered (every DeliverTx is a transaction of its own) or
    committed.  So the app's state row may be at `height` or past it, never
    behind, and is held to the chain at the height it states; rows of later
    blocks are no miss.  Every `.db` file must pass SQLite's integrity
    check."""
    state_height, tx_count, app_hash = app_state(home)
    upto = expected(chain_dir, height)
    want = upto if state_height <= height else expected(chain_dir, state_height)
    misses = {
        "state_behind": max(0, height - state_height),
        "wrong_tx_count": int(tx_count != want.tx_count),
        "wrong_app_hash": int(app_hash != want.app_hash),
        "rows_missing": 0, "values_wrong": 0, "integrity_errors": 0,
    }
    conn = _read_only(os.path.join(home, "data", "app.db"))
    try:
        rows = dict(conn.execute(
            "SELECT k, v FROM kv WHERE k >= ? AND k < ?", (KV_PREFIX, b"kv;")
        ).fetchall())
    finally:
        conn.close()
    for key, value in upto.pairs.items():
        got = rows.get(KV_PREFIX + key)
        if got is None:
            misses["rows_missing"] += 1
        elif bytes(got) != value:
            misses["values_wrong"] += 1
    for path in sorted(glob.glob(os.path.join(home, "data", "*.db"))):
        conn = _read_only(path)
        try:
            verdict = conn.execute("PRAGMA integrity_check").fetchall()
        except sqlite3.DatabaseError as exc:
            verdict = [(repr(exc),)]
        finally:
            conn.close()
        if verdict != [("ok",)]:
            misses["integrity_errors"] += 1
    return misses
