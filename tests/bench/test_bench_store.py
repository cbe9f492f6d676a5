"""The on-disk deployment (PR 31): the configuration `hub-175-sqlite` keeps
hub-175's shape and differs in the store; its cell and its four metrics are
in BENCHMARK.json and nothing else moved; the plain store reference says so
when a row is gone, a value altered or the state a height behind; a whole
toy run of the harness on sqlite is `correct` and reads the new metrics,
which a run on memdb does not; and a node killed in mid-replay on sqlite is
read back from its database files, every height whose NewBlock it published.

Run as a script this file is the child of that last test: a node replaying
a generated chain on sqlite, printing each height as NewBlock is published.
"""

import asyncio
import gc
import json
import os
import signal
import sqlite3
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import chain, check_store, harness, reference_store  # noqa: E402

SEED = 2_147_483_777
CELL = "hub-175-sqlite.replay-full"
STORE_LAYER, LOOP_LAYER = "ABCI deliver, store", "consensus + replay loop"
NEW_METRICS = {
    "db_write_ms_per_block": ("ms", "lower", "program_span", STORE_LAYER, "ring_sum_per_block"),
    "db_txns_per_block": ("count", "lower", "program_counter", STORE_LAYER, "ring_sum_per_block"),
    # not the window's mean of `index_lag`: that grows with the rate (REVIEW, PR 31)
    "tx_indexed_share": ("%", "higher", "program_counter", STORE_LAYER, "ring_share"),
    "block_interval_p95_ms.disk": ("ms", "lower", "host_clock", LOOP_LAYER, "block_interval_percentile"),
}
# the chain the killed node replays: 50 txs a block
CONFIG = {
    "name": "toy-24", "validators": 24, "absent_share": 0.05,
    "power": {"kind": "zipf", "top": 1000, "s": 0.8},
    "app": "kvstore", "node": {"db_backend": "sqlite"}, "source_peers": 2,
}
TRAFFIC = {"name": "replay-full", "txs_per_block": 50, "tx_bytes": 250, "warm_in_blocks": 3}
HEIGHTS = 120
KILL_AT = 40


def load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def on_the_cpu(cfg):
    cfg.tpu.enabled = False
    cfg.rpc.laddr = ""


# -- the killed node (the child) --------------------------------------------------


class Printed(harness.BlockStamps):
    """A NewBlock subscription's queue that prints each height as the event
    bus publishes it, and keeps nothing."""

    def put_nowait(self, msg):
        print(msg.data.data["block"].height, flush=True)


async def replay(chain_dir, home):
    """The node's own fast-sync loop over the generated blocks on sqlite
    stores under `home`, until the parent kills the process."""
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import Block
    from tendermint_tpu.types.events import EVENT_NEW_BLOCK, query_for_event

    meta = chain.load_meta(chain_dir)
    cell = harness.Cell("toy-24.replay-full", 1, CONFIG, TRAFFIC, HEIGHTS, [], [])
    _, pubs, powers = chain.committee(SEED, CONFIG)
    cfg = harness.node_config(cell, home, on_the_cpu)
    node = Node(cfg, harness.genesis(cell, SEED, pubs, powers), priv_validator=None)
    await node.start()
    (await node.event_bus.subscribe("parent", query_for_event(EVENT_NEW_BLOCK))).queue = Printed(lambda: 0)
    with open(os.path.join(chain_dir, "blocks.bin"), "rb") as f:
        raw = f.read()
    for h in range(1, HEIGHTS + 1):
        block = Block.deserialize(raw[meta["offsets"][h - 1]: meta["offsets"][h]])
        node.blockchain_reactor.processor.add_block(h, block, "generator")
    node.blockchain_reactor._wake_pool()
    await asyncio.sleep(60)  # killed long before


BENCH = load("BENCHMARK.json")


# -- the data files -------------------------------------------------------------


def test_the_benchmark_gains_one_configuration_one_cell_and_four_metrics():
    """PR 31's entries are there and well-formed, after the three
    configurations, four cells and 28 metrics it found; what later PRs add
    after them is theirs to hold."""
    entry = BENCH["configs"][3]
    assert entry["name"] == "hub-175-sqlite" and entry["reduced"] == ["heights"]
    assert entry["file"] == "benchmarks/configs/hub-175-sqlite.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "config/config.go" in entry["source"] and "tm-bench" in entry["source"]
    # the window runs with the indexer cut, so neither claims an indexed node (REVIEW, PR 31)
    assert "tx_index" not in entry["source"] and "indexer cut" in entry["why"]
    assert "indexer cut" in BENCH["workloads"][4]["why"] and "index on" not in BENCH["workloads"][4]["why"]
    assert BENCH["workloads"][4] == {
        "name": CELL, "config": "hub-175-sqlite", "traffic": "replay-full", "chips": 1,
        "why": BENCH["workloads"][4]["why"],
    }
    assert len(BENCH["workloads"][4]["why"]) <= 200
    before, new = BENCH["per_layer"][:28], BENCH["per_layer"][28:32]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    for m in new:
        unit, better, source, layer, reducer = NEW_METRICS[m["name"]]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (unit, better, source, layer)
        assert m["moves"] == "replay_blocks_per_s" and m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert layer in {x["layer"] for x in before}  # a layer already named
        spec = load(f"benchmarks/metrics/{m['name']}.json")
        assert spec["reducer"] == reducer
        assert os.path.exists(os.path.join(REPO, "benchmarks", "reducers", reducer + ".py"))
    # no metric that lists its cells was given this one: the end-to-end tail stays hub-175's
    for m in before + BENCH["per_layer"][32:] + BENCH["end_to_end"]:
        assert CELL not in m.get("workloads", [])
    assert [m["name"] for m in BENCH["end_to_end"]] == [
        "replay_blocks_per_s", "block_interval_p95_ms", "setup_s"
    ]


def test_the_cell_reports_the_four_metrics_and_the_present_cells_none_of_them():
    cell = harness.load_cell(CELL)
    assert set(NEW_METRICS) <= {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"replay_blocks_per_s", "setup_s"}
    assert cell.heights == 1100 and harness.warm_in_blocks(cell.config, cell.traffic) == 40
    # no new kernel, so no new roofline: the accepted one is reported here as in every cell
    assert "verify_kernel_roofline" in {m["name"] for m in cell.per_layer}
    for w in BENCH["workloads"][:4]:
        names = {m["name"] for m in harness.load_cell(w["name"]).per_layer}
        assert not names & set(NEW_METRICS), w["name"]


def test_the_configuration_is_hub_175_on_the_default_store(tmp_path):
    hub, disk = load("benchmarks/configs/hub-175.json"), load("benchmarks/configs/hub-175-sqlite.json")
    same = ("validators", "key_scheme", "power", "absent_share", "validator_set_changes",
            "source_peers", "peer_link", "app", "chips", "reduced")
    for key in same:
        assert disk[key] == hub[key], key
    assert set(disk) == set(hub) | {"traffic_heights", "why"}
    assert disk["node"] == {**hub["node"], "db_backend": "sqlite"}
    assert disk["traffic_heights"] == {"replay-full": load("benchmarks/traffic/replay-full.json")["heights"]["hub-175"]}
    # the program's own default, through the normal path
    from tendermint_tpu.config import BaseConfig, TxIndexConfig

    assert BaseConfig().db_backend == disk["node"]["db_backend"] and TxIndexConfig().indexer == "kv"
    cfg = harness.node_config(harness.load_cell(CELL), str(tmp_path))
    assert (cfg.base.db_backend, cfg.tx_index.indexer) == ("sqlite", "kv")
    # hub-175's four guarantees, the third through the on-disk stores, and the fifth
    assert disk["guarantees"][:2] == hub["guarantees"][:2] and disk["guarantees"][3] == hub["guarantees"][3]
    assert disk["guarantees"][2].startswith(hub["guarantees"][2]) and "app.db" in disk["guarantees"][2]
    fifth = disk["guarantees"][4]
    assert len(disk["guarantees"]) == 5 and "killed without closing" in fifth
    assert "synchronous=NORMAL" in fifth and "loss of power" in fifth and "tx index" in fifth
    assumed = disk["assumed"]
    assert set(hub["assumed"]) < set(assumed)
    assert {"store_location", "sync", "tx_index", "index_keys", "pruning"} <= set(assumed)
    assert "memdb" not in assumed["db_backend"] and "TMPDIR" in assumed["store_location"]
    assert "does NOT measure an indexed node" in assumed["tx_index"] and "490 ms" in assumed["tx_index"]
    assert disk["source"] == BENCH["configs"][3]["source"] and disk["why"] == BENCH["configs"][3]["why"]
    assert "not verified here" in assumed["sync"]


def test_the_programs_pragmas_are_the_parents(tmp_path):
    """The durability the fifth guarantee is stated under: WAL, synchronous=NORMAL."""
    from tendermint_tpu.libs.kvstore import open_db

    db = open_db("state", str(tmp_path))
    assert db._conn.execute("PRAGMA journal_mode").fetchone() == ("wal",)
    assert db._conn.execute("PRAGMA synchronous").fetchone() == (1,)  # NORMAL
    db.close()


# -- the plain store reference ---------------------------------------------------


@pytest.fixture(scope="module")
def toy_chain(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("chain") / "a")
    chain.generate(CONFIG, TRAFFIC, SEED, HEIGHTS, out, workers=1)
    return out


def app_db(home, rows, height, tx_count, app_hash):
    """An app.db as the kvstore leaves it, written with sqlite3 alone."""
    import struct

    os.makedirs(os.path.join(home, "data"))
    conn = sqlite3.connect(os.path.join(home, "data", "app.db"))
    conn.execute("CREATE TABLE kv (k BLOB PRIMARY KEY, v BLOB)")
    conn.executemany("INSERT INTO kv VALUES (?, ?)", [(b"kv:" + k, v) for k, v in rows.items()])
    state = struct.pack("<QQB", height, tx_count, len(app_hash)) + app_hash
    conn.execute("INSERT INTO kv VALUES (?, ?)", (b"__state__", state))
    conn.commit()
    conn.close()


def test_the_reference_reads_the_chains_own_bytes(toy_chain):
    want = reference_store.expected(toy_chain, 7)
    assert want.tx_count == 7 * 50 == len(want.pairs)
    assert want.app_hash == reference_store.reference.kvstore_app_hash(350, 7)
    assert list(want.block_ids) == list(range(1, 8))
    assert want.block_ids[7] == chain.load_meta(toy_chain)["hashes"][6]
    # what the generator draws from the seed is what its blocks carry
    for h in (1, 7):
        for tx in chain.block_txs(SEED, h, TRAFFIC):
            key, value = chain.tx_key_value(tx)
            assert want.pairs[key] == value
    with pytest.raises(ValueError):
        reference_store.expected(toy_chain, HEIGHTS + 1)


@pytest.mark.parametrize("damage,miss", [
    (None, None),
    ("row_deleted", "rows_missing"),
    ("value_altered", "values_wrong"),
    ("state_a_height_behind", "state_behind"),
    ("tx_count_wrong", "wrong_tx_count"),
])
def test_held_says_what_the_files_miss(toy_chain, tmp_path, damage, miss):
    want = reference_store.expected(toy_chain, 5)
    rows, height, tx_count = dict(want.pairs), 5, want.tx_count
    victim = sorted(rows)[17]
    if damage == "row_deleted":
        del rows[victim]
    elif damage == "value_altered":
        rows[victim] = rows[victim][:-1] + b"!"
    elif damage == "state_a_height_behind":
        four = reference_store.expected(toy_chain, 4)
        height, tx_count = 4, four.tx_count
    elif damage == "tx_count_wrong":
        tx_count -= 1
    app_hash = reference_store.reference.kvstore_app_hash(tx_count, height)
    app_db(str(tmp_path), rows, height, tx_count, app_hash)
    misses = reference_store.held(str(tmp_path), toy_chain, 5)
    assert set(misses) == {"state_behind", "wrong_tx_count", "wrong_app_hash", "rows_missing",
                           "values_wrong", "integrity_errors"}
    failed = {k for k, v in misses.items() if v}
    if miss is None:
        assert failed == set()
    else:
        assert miss in failed and misses[miss] == 1
    # a block past the acknowledged height, half delivered, is no miss
    if damage is None:
        assert not any(reference_store.held(str(tmp_path), toy_chain, 4).values())


def test_a_database_that_is_not_one_fails_the_integrity_check(toy_chain, tmp_path):
    want = reference_store.expected(toy_chain, 2)
    app_db(str(tmp_path), want.pairs, 2, want.tx_count, want.app_hash)
    with open(tmp_path / "data" / "state.db", "wb") as f:
        f.write(b"not a database" * 100)
    assert reference_store.held(str(tmp_path), toy_chain, 2)["integrity_errors"] == 1


# -- a node killed in mid-replay ---------------------------------------------------


def test_what_a_killed_node_acknowledged_is_read_back_from_its_files(toy_chain, tmp_path):
    home = str(tmp_path / "home")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    with open(tmp_path / "child.err", "wb") as err:
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), toy_chain, home],
            env=env, stdin=subprocess.DEVNULL, stdout=subprocess.PIPE, stderr=err,
        )
    printed = []
    try:
        t0 = time.monotonic()
        while len(printed) < KILL_AT:
            line = child.stdout.readline()
            assert line, (tmp_path / "child.err").read_text()[-2000:]
            assert time.monotonic() - t0 < 100
            printed.append(int(line))
    finally:
        child.send_signal(signal.SIGKILL)  # no close, no checkpoint, no goodbye
        rest = child.communicate(timeout=30)[0]
    printed += [int(x) for x in rest.split()]  # published before the kill landed
    assert child.returncode == -signal.SIGKILL
    assert printed == list(range(1, len(printed) + 1)) and KILL_AT <= len(printed) < HEIGHTS - 1
    assert os.path.exists(os.path.join(home, "data", "app.db-wal"))  # left as they were
    report = check_store.verify(home, toy_chain, printed[-1])
    assert report["misses"] and not any(report["misses"].values()), report
    assert printed[-1] <= report["state_height"] <= printed[-1] + 1
    # the index is reported, and held to nothing
    assert 0 <= report["txs_indexed"] <= report["txs_applied"] + 50


# -- whole runs of the harness on the CPU, the device stood in for -----------------


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    return tmp_path


async def drive(backend):
    config = {**CONFIG, "node": {"db_backend": backend}}
    traffic = {"name": "replay", "txs_per_block": 4, "tx_bytes": 60, "warm_in_blocks": 3}
    cell = harness.Cell(
        "toy-24.replay", 1, config, traffic, 1200,
        end_to_end=BENCH["end_to_end"], per_layer=BENCH["per_layer"],
    )
    gc.collect()  # the ring's readers want one live recorder: earlier nodes go now

    def configure(cfg):
        cfg.tpu.enabled = False

    return await harness.run_cell(
        cell, SEED, 1.0, True, time.monotonic(), faults=["stub_device"], configure=configure
    )


def the_runs_recorder():
    """The flight recorder of the node the run just stopped: the youngest
    alive (an earlier file's node may still be, on the same worker)."""
    from tendermint_tpu.libs import tracing

    return max(tracing.live_recorders(), key=lambda r: r.anchor_mono_ns)


async def test_a_run_on_sqlite_is_correct_and_reads_the_stores_metrics(scratch):
    result = await drive("sqlite")
    assert result["correct"] is True, result["checks"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(got)
    # five a block since PR 32 (the app's in one at Commit, the block store 1, the
    # state store 3); a live index adds its own
    assert got["db_txns_per_block"] >= 4
    assert 0 < got["db_write_ms_per_block"] < got["block_interval_p50_ms"] * 3
    assert 0 <= got["tx_indexed_share"] <= 200  # 4 txs a block: the index keeps up, more or less
    assert got["block_interval_p95_ms.disk"] == got["block_interval_p95_ms.replay"]
    # the identities, block by block, off the node's own ring
    from tendermint_tpu.libs import tracing

    recorder = the_runs_recorder()
    blocks = [ev for ev in recorder.events() if ev["kind"] == "fastsync.block"]
    assert len(blocks) > 20
    for ev in blocks[1:]:
        interval_ms = ev["dur_ns"] / 1e6 + ev.get("wait_ms", 0.0)
        assert ev["db_ms"] <= interval_ms + 0.01, ev
        by_store = sum(v for k, v in ev.items() if k.startswith("db_ms."))
        assert by_store == pytest.approx(ev["db_ms"], abs=0.01)
        assert {"db_ms.app", "db_ms.blockstore", "db_ms.state", "db_ms.tx_index"} <= set(ev)
        assert ev["db_txns"] >= 4 and ev["db_rows"] >= ev["db_txns"] and ev["db_max_ms"] <= ev["db_ms"]


async def test_a_run_on_memdb_reads_nothing_of_the_store(scratch):
    result = await drive("memdb")
    assert result["correct"] is True, result["checks"]
    got = result["metrics"]
    assert "db_write_ms_per_block" not in got and "db_txns_per_block" not in got
    from tendermint_tpu.libs import tracing

    recorder = the_runs_recorder()
    blocks = [ev for ev in recorder.events() if ev["kind"] == "fastsync.block"]
    assert blocks and not any(k.startswith("db_") for ev in blocks for k in ev)
    # the indexer runs on memdb too, and its lag is the one store field there
    assert all({"index_lag", "txs", "txs_indexed"} <= set(ev) for ev in blocks)
    assert "tx_indexed_share" in got  # a share of the txs applied, whatever the store


if __name__ == "__main__":
    asyncio.run(replay(sys.argv[1], sys.argv[2]))
