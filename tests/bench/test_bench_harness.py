"""The harness itself, without a chip: what it prints, when it refuses to
run, what BENCHMARK.json and the data files must agree on, and whole runs
at toy size on the CPU with a stand-in for the device — clean, and with
the timed path broken underneath, which must come out `correct: false`.

No child that a test starts loads libtpu: sources and the chain generator
run with JAX_PLATFORMS=cpu and never import JAX.
"""

import gc
import importlib
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, run, trace  # noqa: E402

SEED = 2_147_483_777

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


HEIGHTS = 1200  # the toy loop applies 170-300 blocks in its 1 s window: room for twice that
# a power change in half the blocks and a validator swapped every 10 heights
ROTATING = {
    "power": {"share_of_blocks": 0.5, "validators": "1-3", "delta_share": 0.005},
    "membership": {"every_heights": 10, "leaver_among_lowest": 6, "standby": 5},
}

def toy_cell(heights=HEIGHTS, validators=24, chips=1, changes=None):
    """A cell of the committee's shape at toy size: 24 validators put ~22
    signatures in a commit, above min_device_batch.  With `changes`, its
    validator set moves as that `validator_set_changes` says."""
    config = {
        "name": "toy-24", "validators": validators, "absent_share": 0.05,
        "power": {"kind": "zipf", "top": 1000, "s": 0.8},
        "app": "kvstore", "node": {"db_backend": "memdb"}, "source_peers": 2,
    }
    if changes is not None:
        config["validator_set_changes"] = changes
    traffic = {"name": "replay", "txs_per_block": 4, "tx_bytes": 60,
               "warm_in_blocks": 3, "heights": {"toy-24": heights}}
    return harness.Cell(
        "toy-24.replay", chips, config, traffic, heights,
        end_to_end=BENCH["end_to_end"],
        per_layer=BENCH["per_layer"],
    )


@pytest.fixture
def scratch(tmp_path, monkeypatch):
    """The chain cache and the output directory, moved out of the checkout."""
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    return tmp_path


def on_the_cpu(cfg):
    cfg.tpu.enabled = False  # the stand-in takes the engine's place


async def drive(faults, seconds=1.0, trace_run=False, heights=HEIGHTS, chips=1, changes=None):
    return await harness.run_cell(
        toy_cell(heights, chips=chips, changes=changes), SEED, seconds, trace_run,
        time.monotonic(), faults=faults, configure=on_the_cpu,
    )


def failed_checks(result):
    return {k for k, c in result["checks"].items() if c["value"] != c["limit"]}


# -- what a run prints --------------------------------------------------------


def test_the_last_line_has_exactly_the_contracts_keys(capsys):
    result = {
        "correct": True, "attempted": 3, "failed": 0,
        "metrics": {"setup_s": {"value": 1.5, "unit": "s"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 7},
        "context": {"warm": {"path": "indexed"}},
        "checks": {"wrong_reads": {"value": 0, "limit": 0}},
    }
    run.print_result(dict(result))
    out, err = capsys.readouterr()
    lines = out.splitlines()
    last = json.loads(lines[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert json.loads(lines[-2]) == {"context": {"warm": {"path": "indexed"}}}
    assert err.splitlines()[-2:] == ["[check] wrong_reads = 0 (limit 0)", "[check] correct = True"]
    traced = dict(result, breakdown={"device_ops": [], "idle_gaps": []})
    run.print_result(traced)
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert list(last) == [
        "correct", "attempted", "failed", "metrics", "device", "breakdown", "checks",
    ]


def test_a_run_that_broke_reports_itself_incorrect():
    line = run.failed_result("boom", {"platform": "tpu"})
    assert line["correct"] is False and line["failed"] == 1 and line["metrics"] == {}
    assert list(line)[-1] == "checks"


def test_main_refuses_a_cpu(capsys):
    """No accelerator: another exit code than 0 and nothing on stdout."""
    assert run.main(["--workload", "hub-175.replay", "--seed", "1", "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "refusing to run" in out.err and "'cpu'" in out.err


def test_main_refuses_a_workload_it_does_not_know(capsys):
    assert run.main(["--workload", "nope.replay", "--seed", "1", "--seconds", "1"]) == 3
    assert capsys.readouterr().out == ""


def test_alone_with_its_own_files_it_prints_nothing_and_fails(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    paths there is no program to measure."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(
        os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("cache", "out", "__pycache__"),
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "hub-175.replay", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout == ""


# -- the data files -------------------------------------------------------------


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_from_its_data_files(workload):
    cell = harness.load_cell(workload)
    entry = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert cell.config["name"] == entry["config"] and cell.traffic["name"] == entry["traffic"]
    assert cell.chips == entry["chips"]
    assert cell.heights >= 100
    names = {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "replay_blocks_per_s"} <= names
    assert cell.per_layer and all(m["moves"] in names for m in cell.per_layer)
    for m in cell.end_to_end + cell.per_layer:
        assert "workloads" not in m or workload in m["workloads"]


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_file_and_a_reader(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    with open(os.path.join(REPO, "benchmarks", "metrics", metric + ".json")) as f:
        spec = json.load(f)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    reader = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    # nothing to read: nothing returned (never 0)
    empty = harness.Window(
        cell=toy_cell(), seconds=1.0, t_open_ns=0, t_close_ns=10**9, block_times=[],
        block_heights=[], events=[], deliver_spans=[], buffered=[],
    )
    assert reader.read(empty, spec.get("params", {})) is None


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_states_its_source_and_guarantees(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    assert entry["file"].startswith("benchmarks/configs/")
    with open(os.path.join(REPO, entry["file"])) as f:
        spec = json.load(f)
    assert spec["name"] == config and spec["source"] == entry["source"]
    assert len(entry["source"]) <= 200
    assert spec["reduced"] == entry["reduced"] == ["heights"]
    assert len(spec["guarantees"]) >= 3 and spec["assumed"]
    assert "default" in spec["node"]["tpu"]  # no [tpu] knob is turned
    assert "tabulated" not in spec["node"]["tpu"]  # a knob the program lost in PR 28


def test_a_later_configuration_brings_its_own_warm_in_and_chain_length():
    """What a traffic file keys by configuration name (it cannot know a later
    one), the configuration's file may give: so a later deployment can use the
    `replay` mix as it stands instead of a copy under another name."""
    with open(os.path.join(REPO, "benchmarks", "traffic", "replay.json")) as f:
        replay = json.load(f)
    assert harness.warm_in_blocks({"name": "hub-175"}, replay) == 20
    assert harness.warm_in_blocks({"name": "committee-10k"}, replay) == 12
    later = {"name": "later", "traffic_warm_in_blocks": {"replay": 7},
             "traffic_heights": {"replay": 900}}
    assert harness.warm_in_blocks(later, replay) == 7
    assert harness.chain_heights(later, replay) == 900
    assert harness.warm_in_blocks(later, {"name": "other", "warm_in_blocks": 3}) == 3
    with pytest.raises(harness.HarnessFailure):
        harness.warm_in_blocks({"name": "unknown"}, replay)


def test_the_benchmark_file_keeps_to_the_contract():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    assert BENCH["paths"] == ["benchmarks", "tests/bench"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert set(e2e) == {"replay_blocks_per_s", "block_interval_p95_ms", "setup_s"}
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace") for m in e2e.values())
    assert all(m["moves"] == "replay_blocks_per_s" for m in BENCH["per_layer"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    assert all(len(w["why"]) <= 200 for w in BENCH["workloads"])


def test_chain_length_comes_from_the_traffic_file_or_the_configuration():
    config, traffic = {"name": "c"}, {"name": "t", "heights": {"c": 70}}
    assert harness.chain_heights(config, traffic) == 70
    later = {"name": "later", "traffic_heights": {"t": 90}}
    assert harness.chain_heights(later, traffic) == 90
    with pytest.raises(harness.HarnessFailure):
        harness.chain_heights({"name": "other"}, traffic)


# -- the rules on engine events --------------------------------------------------


def test_window_rules_count_what_a_window_may_not_hold():
    ok = {"kind": "verify.dispatch", "n": 166, "bucket": 512, "path": "indexed"}
    small = {"kind": "verify.dispatch", "n": 1, "bucket": 0, "path": "host"}
    assert set(harness.window_failures([ok, small], 16).values()) == {0}
    host = {"kind": "verify.dispatch", "n": 166, "bucket": 0, "path": "host"}
    cold = {"kind": "verify.dispatch", "n": 166, "bucket": 0, "path": "host-cold"}
    assert harness.window_failures([ok, host, cold], 16)["host_tier_dispatches"] == 2
    build = {"kind": "verify.table_build", "ok": True, "ms": 5.0}
    compiled = {"kind": "verify.bucket_compile", "bucket": 512, "ok": False, "error": "boom"}
    counts = harness.window_failures([build, compiled], 16)
    assert counts["builds_in_window"] == 2 and counts["engine_errors"] == 1
    assert list(counts) == ["engine_errors", "host_tier_dispatches", "builds_in_window"]


@pytest.mark.parametrize("builds,rebuilds,compiles,memberships,beyond", [
    (0, 0, 0, 0, 0),
    (3, 3, 0, 3, 0),  # both of the program's builders fired for each new membership
    (1, 0, 0, 0, 1),  # a table built though no membership changed (a power change, say)
    (5, 2, 0, 3, 1),
    (2, 2, 1, 3, 1),  # a compile is never the chain's asking
    (1200, 0, 0, 12, 1176),  # a build for every block
])
def test_a_rotating_window_may_build_what_the_chain_asks_for(
        builds, rebuilds, compiles, memberships, beyond):
    events = (
        [{"kind": "verify.table_build", "ok": True, "ms": 5.0}] * builds
        + [{"kind": "verify.table_rebuild", "ok": True, "ms": 5.0}] * rebuilds
        + [{"kind": "verify.bucket_compile", "bucket": 512, "ok": True}] * compiles
    )
    counts = harness.window_failures(events, 16, membership_changes=memberships)
    assert counts["builds_beyond_membership_changes"] == beyond
    assert "builds_in_window" not in counts and counts["engine_errors"] == 0
    assert counts["flat_dispatches_beyond_membership_changes"] == 0


@pytest.mark.parametrize("flat,memberships,beyond", [
    (0, 0, 0),
    (12, 12, 0),  # what sound runs read on the chip: at most one a membership
    (1, 0, 1),  # a commit declined though no membership changed
    (120, 12, 0),  # ten a membership: a slow build, still a build
    (4600, 12, 4480),  # a table never built: two a block from the first change on
])
def test_a_rotating_window_may_ride_the_flat_path_while_a_table_builds(flat, memberships, beyond):
    def dispatch(path):
        return {"kind": "verify.dispatch", "n": 166, "bucket": 512, "path": path, "shards": 1}

    events = [dispatch("device")] * flat + [dispatch("indexed")] * 50
    counts = harness.window_failures(events, 16, membership_changes=memberships)
    assert counts["flat_dispatches_beyond_membership_changes"] == beyond
    assert counts["builds_beyond_membership_changes"] == 0
    assert "flat_dispatches_beyond_membership_changes" not in harness.window_failures(events, 16)


def test_percentile_and_intervals():
    assert harness.percentile([1, 2, 3, 4, 5], 50) == 3
    assert harness.percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)
    assert harness.percentile([7], 95) == 7
    with pytest.raises(ValueError):
        harness.percentile([], 50)
    w = harness.Window(
        cell=toy_cell(), seconds=1.0, t_open_ns=2 * 10**9, t_close_ns=3 * 10**9,
        block_times=[2.010, 2.030, 2.060], block_heights=[1, 2, 3], events=[],
        deliver_spans=[], buffered=[],
    )
    assert harness.block_intervals_ms(w) == pytest.approx([10.0, 20.0, 30.0])
    assert harness.end_to_end_value("replay_blocks_per_s", w, 9.0) == 3.0
    assert harness.end_to_end_value("setup_s", w, 9.0) == 9.0


# -- whole runs on the CPU, the device stood in for -------------------------------


async def test_a_clean_run_is_correct_and_reports_every_metric(scratch):
    result = await drive(["stub_device"])
    assert result["correct"] is True, result["checks"]
    assert failed_checks(result) == set()
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"replay_blocks_per_s", "block_interval_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(result["checks"]) == {
        "engine_errors", "host_tier_dispatches", "builds_in_window", "compiles_in_window",
        "blocks_without_device_dispatch", "left_fast_sync", "chain_exhausted", "window_empty",
        "wrong_block_ids", "commits_accepted_wrongly", "wrong_reads", "verdict_mismatches",
    }  # a static set: the checks it always had, under their names
    # the generated chain was cached, and nothing else left behind
    assert len(os.listdir(scratch / "cache")) == 1
    assert not [f for f in os.listdir(scratch / "out") if f.endswith(".json")]
    # a loop twice as fast would still end short of the chain's last 80 blocks
    first, last = result["context"]["heights_replayed"]
    assert first + 2 * (last - first) <= HEIGHTS - 2 * harness.REQUESTS_PER_PEER * 2


async def test_a_traced_run_reads_the_recorders_metrics_and_skips_the_devices(scratch):
    result = await drive(["stub_device"], trace_run=True)
    assert result["correct"] is True, result["checks"]
    got = set(result["metrics"])
    assert {"deliver_ms_per_block", "engine_wait_ms_per_block", "table_hit_share",
            "useful_rows_share", "dispatches_per_block", "block_interval_p50_ms",
            "replay_queue_blocks_mean"} <= got
    # a CPU trace has no device plane: no device metric is made up
    assert not got & {"device_idle_share", "verify_kernel_ms_per_block", "verify_kernel_roofline"}
    assert "breakdown" not in result and "busy_s" not in result["device"]
    # what the harness itself guarantees: a table-path dispatch for every block
    assert 1.0 <= result["metrics"]["dispatches_per_block"]["value"] <= 2.2
    assert result["metrics"]["table_hit_share"]["value"] == 100.0
    # a static set hashes its set once: the two comparisons a block, no root
    # (the ring's readers read nothing while an earlier test's node still lives)
    set_hash = result["metrics"].get("set_hash_ms_per_block")
    assert set_hash is None or 0 <= set_hash["value"] < 0.5


# -- a validator set that changes ------------------------------------------------


ROTATING_CHECKS = {
    "engine_errors", "host_tier_dispatches", "builds_beyond_membership_changes",
    "flat_dispatches_beyond_membership_changes", "compiles_in_window",
    "blocks_without_device_dispatch", "left_fast_sync", "chain_exhausted", "window_empty",
    "wrong_block_ids", "commits_accepted_wrongly", "wrong_reads", "verdict_mismatches",
    "wrong_validator_sets",
}


async def test_a_rotating_chain_replays_correct_and_shows_the_miss_path(scratch):
    gc.collect()  # the ring's readers want one live recorder: earlier tests' nodes go now
    result = await drive(["stub_device"], trace_run=True, changes=ROTATING)
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == ROTATING_CHECKS
    assert all(c == {"value": 0, "limit": 0} for c in result["checks"].values())
    got = result["metrics"]
    assert got["table_hit_share"]["value"] < 100.0  # a miss for each membership it met
    assert 1.0 <= got["dispatches_per_block"]["value"] <= 2.2  # table paths only
    assert got["set_hash_ms_per_block"]["value"] > 0  # a root per changed set
    first, last = result["context"]["heights_replayed"]
    changes = result["context"]["membership_changes"]
    assert len(changes) >= 4
    assert all(first - 2 <= h <= last + 1 and h % 10 == 2 for h in changes)
    # the stand-in builds one table for each membership it meets
    assert 1 <= len(result["context"]["table_builds_in_window"]) <= len(changes)


async def test_a_table_built_for_every_commit_fails_a_rotating_run(scratch):
    result = await drive(["stub_device", "build_always"], changes=ROTATING)
    assert result["correct"] is False
    assert failed_checks(result) == {"builds_beyond_membership_changes"}
    assert result["checks"]["builds_beyond_membership_changes"]["value"] > result["attempted"]


async def test_a_table_never_built_fails_a_rotating_run(scratch):
    """Every block has its dispatch on a device path and no build is one too
    many, yet the new memberships' tables never come.  A swap every 40 heights,
    so that a loop with one dispatch a block (S3) would still read over the limit."""
    sparse = dict(ROTATING, membership=dict(ROTATING["membership"], every_heights=40))
    result = await drive(["stub_device", "table_never_built"], changes=sparse)
    assert result["correct"] is False
    assert failed_checks(result) == {"flat_dispatches_beyond_membership_changes"}
    assert result["checks"]["flat_dispatches_beyond_membership_changes"]["value"] >= 20


async def test_a_store_that_answers_with_the_genesis_set_fails_a_rotating_run(scratch):
    """The fifth guarantee broken and nothing else: the chain goes on (the
    live path holds its sets in hand), every header root agrees, and
    `/validators` reads back none of the `val:` writes."""
    result = await drive(["stub_device", "stale_validator_sets"], changes=ROTATING)
    assert result["correct"] is False
    failed = failed_checks(result)
    # the tampers go through the set the store holds for the last height too
    assert "wrong_validator_sets" in failed and failed <= {
        "wrong_validator_sets", "verdict_mismatches"}
    assert result["checks"]["wrong_validator_sets"]["value"] >= 4  # every sampled height


async def test_a_window_that_met_no_new_membership_may_build_nothing(scratch):
    """Powers move in half the blocks and no validator is swapped, as in most
    windows of a chain at the Hub's cadence: the run is held to no build and
    no flat dispatch at all, and to the sets the powers made."""
    still = {"power": ROTATING["power"],
             "membership": dict(ROTATING["membership"], every_heights=5000)}
    result = await drive(["stub_device"], changes=still)
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == ROTATING_CHECKS
    assert result["context"]["membership_changes"] == []
    assert result["context"]["table_builds_in_window"] == []


@pytest.mark.parametrize("fault,caught_by", [
    ("accept_all", "verdict_mismatches"),  # an answer altered where it is produced
    ("half_batch", "verdict_mismatches"),  # half of the batch left out
    # a step that returns its state unchanged: the next header's app hash
    # exposes it to the node itself, which stops replaying
    ("state_unchanged", "window_empty"),
])
async def test_a_fault_under_the_timed_path_comes_out_incorrect(scratch, fault, caught_by):
    result = await drive(["stub_device", fault])
    assert result["correct"] is False
    assert caught_by in failed_checks(result), result["checks"]
    assert result["failed"] >= 1


async def test_a_four_chip_cell_is_held_to_dispatches_over_all_four(scratch):
    """What exists only across chips: with the mesh left out underneath,
    every dispatch still answers correctly, and the run is refused."""
    result = await drive(["stub_device"], chips=4)
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["unsharded_dispatches"] == {"value": 0, "limit": 0}
    result = await drive(["stub_device", "one_chip"], chips=4)
    assert result["correct"] is False
    assert failed_checks(result) == {"unsharded_dispatches"}


async def test_a_host_tier_dispatch_in_the_window_fails_the_run(scratch):
    result = await drive(["host_tier"])
    assert result["correct"] is False
    assert {"host_tier_dispatches", "blocks_without_device_dispatch"} <= failed_checks(result)


async def test_catching_up_before_the_window_ends_fails_the_run(scratch):
    """A chain too short for the window: the node runs out of blocks, and
    the run is failed, never short.  An idle machine replays the 60 blocks
    before the window opens: the harness then says so at once (it used to
    wait out WARM_IN_DEADLINE_S for a block that could not come)."""
    t0 = time.monotonic()
    try:
        result = await drive(["stub_device"], seconds=2.0, heights=60)
    except harness.HarnessFailure as exc:
        assert "left fast sync" in str(exc) and "too short" in str(exc)
    else:
        assert result["correct"] is False
        assert {"chain_exhausted", "left_fast_sync"} <= failed_checks(result)
    assert time.monotonic() - t0 < harness.WARM_IN_DEADLINE_S / 2


async def test_the_tip_peer_reports_the_chains_length_and_holds_no_block():
    """The peer the node under test dials as it starts: whatever channels
    the dialer's handshake names it takes (it cannot know them beforehand),
    its status names the chain's height with a base above it, so that no
    block is ever asked of it, and a block request is answered "no block"."""
    import asyncio

    from tendermint_tpu.encoding import codec
    from tendermint_tpu.fastsync.reactor import BLOCKCHAIN_CHANNEL
    from tendermint_tpu.fastsync.scheduler import Scheduler
    from tendermint_tpu.p2p import ChannelDescriptor, NodeInfo, NodeKey, Transport
    from tendermint_tpu.p2p.peer import Peer

    odd = 0x7A  # a channel no reactor of today's node speaks
    sources = harness.Sources()
    try:
        addr = await sources.start_tip("bench-toy", 77)
        key = NodeKey.generate()
        info = NodeInfo(node_id=key.id, network="bench-toy", moniker="test",
                        channels=bytes([BLOCKCHAIN_CHANNEL, odd]))
        tip_id, hostport = addr.split("@")
        conn, ni = await Transport(key, info).dial(hostport, expected_id=tip_id)
        got = asyncio.Queue()

        async def on_receive(chan_id, peer, msg):
            await got.put(codec.loads(msg))

        async def on_error(peer, err):
            await got.put({"k": "error", "err": repr(err)})

        descs = [ChannelDescriptor(id=BLOCKCHAIN_CHANNEL, priority=1),
                 ChannelDescriptor(id=odd, priority=1)]
        peer = Peer(conn, ni, descs, on_receive, on_error, outbound=True)
        await peer.start()
        try:
            status = await asyncio.wait_for(got.get(), 10)  # unasked, on arrival
            assert status == {"k": "status_response", "height": 77, "base": 78}
            await peer.send(odd, b"anything")
            await peer.send(BLOCKCHAIN_CHANNEL, codec.dumps({"k": "status_request"}))
            assert await asyncio.wait_for(got.get(), 10) == status
            await peer.send(BLOCKCHAIN_CHANNEL, codec.dumps({"k": "block_request", "height": 77}))
            assert await asyncio.wait_for(got.get(), 10) == {"k": "no_block_response", "height": 77}
        finally:
            await peer.stop()
        # what the program's scheduler makes of that status: the chain's
        # length is known, and there is nothing to ask this peer for
        sched = Scheduler(1)
        sched.set_peer_range("tip", status["base"], status["height"])
        assert sched.max_peer_height() == 77 and sched.next_requests(0.0) == []
        assert not sched.only_tip_outstanding()
    finally:
        sources.stop()
    assert sources.procs == []


def test_an_unknown_fault_is_refused():
    with pytest.raises(ValueError):
        harness.plant_fault("nope", None)


def test_kernel_patterns_are_data():
    """A new kernel is one new file under benchmarks/kernels/."""
    files = sorted(os.listdir(os.path.join(REPO, "benchmarks", "kernels")))
    assert [f[:-5] for f in files] == [p["name"] for p in trace.load_kernel_patterns()]
