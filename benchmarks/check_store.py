#!/usr/bin/env python3
"""The builder's check of an on-disk cell's fifth guarantee.  NO BENCHMARK
RUN CALLS THIS FILE, and the driver never does: `correct` stays the
harness's comparison (benchmarks/harness.py).  It is run by hand on the chip
by the PR that touches the store, and by tests/bench/test_bench_store.py on the
CPU.

    python3 benchmarks/check_store.py --workload hub-175-sqlite.replay-full --seed <n> --seconds <s>

runs the cell once through `harness.run_cell` with the node's home kept
(the harness removes it; it is wrapped here, not edited), then, in a FRESH
PROCESS that never saw the node,

    python3 benchmarks/check_store.py --verify <home> --chain <chain dir> --upto <height>

opens the database files with the program's own `BlockStore` and
`StateStore` and holds every height up to `--upto` to the plain reference
(benchmarks/reference_store.py): block ids and contiguity, the state's
height, app hash and validator-set hash, and `reference_store.held` for the
kvstore's rows, its state row and SQLite's integrity check.  It also prints
what share of the applied txs `tx_index.db` holds: reported, not a failure
(no guarantee covers the index; PERF.md section 7).  The last stdout line is
one JSON object; the exit code is 1 where anything is missed.

What it does NOT show: the files are opened after the harness has stopped the
node cleanly (stores closed, WAL checkpointed), so at the cell's size this is
a read-back after a clean stop.  The kill without close that the fifth
guarantee names is exercised by tests/bench/test_bench_store.py alone, on the
CPU at 50 txs a block; a kill option here comes with ROADMAP C11.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sqlite3
import subprocess
import sys
import tempfile
import time
import types

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reference_store  # noqa: E402


def verify(home: str, chain_dir: str, upto: int) -> dict:
    """What the stores under `home` miss of heights 1..`upto`, by count, and
    the tx index's share; see the module's docstring."""
    from tendermint_tpu.libs.kvstore import SQLiteDB
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.block_store import BlockStore

    meta = reference_store.load_meta(chain_dir)
    misses = reference_store.held(home, chain_dir, upto)
    blocks = BlockStore(SQLiteDB(os.path.join(home, "data", "blockstore.db")))
    misses["blocks_missing"] = misses["wrong_block_ids"] = 0
    if blocks.base() > 1 or blocks.height() < upto:
        misses["blocks_missing"] += 1
    for h in range(1, upto + 1):
        block, block_meta = blocks.load_block(h), blocks.load_block_meta(h)
        if block is None or block_meta is None or block.height != h:
            misses["blocks_missing"] += 1
        elif not block.hash().hex() == block_meta.block_id.hash.hex() == meta["hashes"][h - 1]:
            misses["wrong_block_ids"] += 1
    state = StateStore(SQLiteDB(os.path.join(home, "data", "state.db"))).load()
    state_height = 0 if state is None else state.last_block_height
    misses["state_behind"] += max(0, upto - state_height)
    if state is not None and upto <= state_height < meta["heights"]:
        # the state after height h holds the app hash after h and the set that
        # signs h + 1: both stand in the chain's header h + 1
        header = reference_store.read_block(chain_dir, meta, state_height + 1)["header"]
        misses["wrong_state"] = (
            int(state.app_hash != header["app_hash"])
            + int(state.validators.hash() != header["validators_hash"])
        )
    else:
        misses["wrong_state"] = 1
    conn = sqlite3.connect(f"file:{os.path.join(home, 'data', 'tx_index.db')}?mode=ro", uri=True)
    try:
        indexed = conn.execute(
            "SELECT COUNT(*) FROM kv WHERE k >= ? AND k < ?", (b"tx.hash/", b"tx.hash0")
        ).fetchone()[0]
    finally:
        conn.close()
    applied = state_height * meta["txs_per_block"]
    return {
        "upto": upto, "state_height": state_height, "misses": misses,
        "txs_applied": applied, "txs_indexed": indexed,
        "tx_index_share": indexed / applied if applied else None,
    }


def run_and_keep_home(workload: str, seed: int, seconds: float, faults) -> tuple:
    """One run of the cell with the node's home left in place: (result,
    home, chain dir)."""
    from benchmarks import chain as chainlib
    from benchmarks import harness

    cell = harness.load_cell(workload)
    home = tempfile.mkdtemp(prefix="check-store-")

    def rmtree(path, *args, **kw):
        if os.path.abspath(path) != home:
            shutil.rmtree(path, *args, **kw)

    # what run_cell sees of `tempfile` and `shutil`: its one mkdtemp is the
    # node's home, and its rmtree spares that
    harness.tempfile = types.SimpleNamespace(mkdtemp=lambda **kw: home)
    harness.shutil = types.SimpleNamespace(rmtree=rmtree)
    try:
        result = asyncio.run(
            harness.run_cell(cell, seed, seconds, False, T_START, faults=faults)
        )
    finally:
        harness.tempfile, harness.shutil = tempfile, shutil
    key = chainlib.cache_key(cell.config, cell.traffic, seed, cell.heights)
    return result, home, os.path.join(harness.CACHE_DIR, key)


def verify_in_a_fresh_process(result: dict, home: str, chain_dir: str) -> dict:
    """`verify` up to the window's last height in a child process, and what
    the run itself said; `held` is whether nothing was missed."""
    replayed = result["context"]["heights_replayed"]
    report = {"held": False, "correct": result["correct"], "heights_replayed": replayed}
    if not replayed:
        return report
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)  # the child never touches the chip
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--verify", home, "--chain", chain_dir,
         "--upto", str(replayed[1])],
        env=env, stdin=subprocess.DEVNULL, stdout=subprocess.PIPE, text=True,
    )
    if child.stdout.strip():
        report.update(json.loads(child.stdout.strip().splitlines()[-1]))
    data = os.path.join(home, "data")
    report.update(
        held=child.returncode == 0,
        checks={k: c["value"] for k, c in result["checks"].items()},
        replay_blocks_per_s=result["metrics"].get("replay_blocks_per_s", {}).get("value"),
        db_files={
            name: os.path.getsize(os.path.join(data, name))
            for name in sorted(os.listdir(data)) if os.path.isfile(os.path.join(data, name))
        },
    )
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--verify", metavar="HOME", help="the fresh-process half")
    ap.add_argument("--chain")
    ap.add_argument("--upto", type=int)
    args = ap.parse_args(argv)

    if args.verify:
        report = verify(args.verify, args.chain, args.upto)
        print(json.dumps(report))
        return int(any(report["misses"].values()))

    import jax

    if jax.default_backend() != "tpu":
        print("check_store.py: needs a TPU, as run.py does", file=sys.stderr)
        return 2
    result, home, chain_dir = run_and_keep_home(args.workload, args.seed, args.seconds, args.fault)
    try:
        report = verify_in_a_fresh_process(result, home, chain_dir)
    finally:
        shutil.rmtree(home, ignore_errors=True)
    report["seed"] = args.seed
    print(json.dumps(report))
    return int(not report.get("held") or not result["correct"])


if __name__ == "__main__":
    sys.exit(main())
