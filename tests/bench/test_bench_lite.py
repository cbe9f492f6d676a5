"""A traffic kind beyond replay: how the harness finds a rig by the kind a
traffic file states, the primaries of the `lite` rig against the program's
own HTTPProvider, and whole runs of that rig at toy size on the CPU, the
device stood in for: clean in both modes, and with its controls planted.

No child that a test starts loads libtpu: primaries and the chain generator
run with JAX_PLATFORMS=cpu and never import JAX.
"""

import json
import os
import subprocess
import sys
import time
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lite_start  # noqa: E402

from benchmarks import chain, harness, run  # noqa: E402
from benchmarks.rigs import lite  # noqa: E402

SEED = 2_147_483_777
HEIGHTS = 1200  # the toy proxy stores 130-350 heights in its 1 s window

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

CONFIG = {
    "name": "toy-24", "validators": 24, "absent_share": 0.05,
    "power": {"kind": "zipf", "top": 1000, "s": 0.8}, "witnesses": 1,
}


def toy_traffic(mode, gap):
    return {"name": f"lite-{mode}", "kind": "lite", "mode": mode, "gap": gap, "txs_per_block": 1,
            "tx_bytes": 40, "warm_in_blocks": 10, "heights": {"toy-24": HEIGHTS}}


def toy_cell(mode="sequence", gap=1, config=CONFIG):
    return harness.Cell(
        f"toy-24.lite-{mode}", 1, config, toy_traffic(mode, gap), HEIGHTS,
        end_to_end=BENCH["end_to_end"], per_layer=BENCH["per_layer"],
    )


@pytest.fixture(scope="module")
def chains(tmp_path_factory):
    """One chain cache for the module: a mode's chain is made once."""
    return str(tmp_path_factory.mktemp("chains"))


@pytest.fixture
def scratch(tmp_path, monkeypatch, chains):
    monkeypatch.setattr(harness, "CACHE_DIR", chains)
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    return tmp_path


async def drive(faults, mode="sequence", gap=1, trace_run=False, start=lite_start.with_recorder,
                seconds=1.0, config=CONFIG):
    return await harness.run_cell(
        toy_cell(mode, gap, config), SEED, seconds, trace_run, time.monotonic(), faults=faults,
        start=start,
    )


def failed_checks(result):
    return {k for k, c in result["checks"].items() if c["value"] != c["limit"]}


# -- the rig is found by the traffic's kind ------------------------------------------


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_a_traffic_file_that_states_no_kind_is_replay(workload):
    cell = harness.load_cell(workload)
    assert "kind" not in cell.traffic and cell.kind == "replay"
    assert harness.find_rig(cell.kind) is harness.run_replay


def test_a_rig_module_is_found_by_the_kinds_name(tmp_path, monkeypatch):
    assert harness.find_rig("lite") is lite.run_cell
    import benchmarks.rigs

    (tmp_path / "echo.py").write_text(
        "async def run_cell(cell, seed, seconds, trace, t_start, faults=()):\n"
        "    return {'kind': cell.kind, 'seed': seed, 'faults': list(faults)}\n"
    )
    monkeypatch.setattr(benchmarks.rigs, "__path__", [str(tmp_path)])
    assert harness.find_rig("echo").__module__ == "benchmarks.rigs.echo"
    sys.modules.pop("benchmarks.rigs.echo")


async def test_run_cell_hands_the_run_to_the_rig_of_the_kind(tmp_path, monkeypatch):
    import benchmarks.rigs

    (tmp_path / "echo2.py").write_text(
        "async def run_cell(cell, seed, seconds, trace, t_start, faults=(), **kw):\n"
        "    return {'kind': cell.kind, 'seed': seed, 'faults': list(faults), 'kw': kw}\n"
    )
    monkeypatch.setattr(benchmarks.rigs, "__path__", [str(tmp_path)])
    cell = harness.Cell("toy.echo", 1, {}, {"name": "echo", "kind": "echo2"}, 0, [], [])
    got = await harness.run_cell(cell, 7, 1.0, False, 0.0, faults=["x"], start="s")
    assert got == {"kind": "echo2", "seed": 7, "faults": ["x"], "kw": {"start": "s"}}
    sys.modules.pop("benchmarks.rigs.echo2")


@pytest.mark.parametrize("kind", ["nope", "Lite", "../harness", "lite.run", ""])
def test_a_kind_without_a_rig_is_refused_by_name(kind):
    with pytest.raises(harness.HarnessFailure) as exc:
        harness.find_rig(kind)
    assert repr(kind) in str(exc.value) and "benchmarks/rigs/" in str(exc.value)


def test_a_rig_whose_own_import_fails_is_not_taken_for_missing(tmp_path, monkeypatch):
    import benchmarks.rigs

    (tmp_path / "broken.py").write_text("import a_module_nobody_has\n")
    monkeypatch.setattr(benchmarks.rigs, "__path__", [str(tmp_path)])
    with pytest.raises(ModuleNotFoundError) as exc:
        harness.find_rig("broken")
    assert exc.value.name == "a_module_nobody_has"


def test_main_exits_3_on_an_unknown_kind_and_names_it(capsys, monkeypatch):
    cell = harness.load_cell("hub-175.replay")
    cell.traffic = dict(cell.traffic, kind="gossip")
    monkeypatch.setattr(harness, "load_cell", lambda workload: cell)
    assert run.main(["--workload", "hub-175.replay", "--seed", "1", "--seconds", "1"]) == 3
    out = capsys.readouterr()
    assert out.out == "" and "'gossip'" in out.err


# -- the traffic file's own keys -------------------------------------------------------


def test_the_schedule_gives_every_seed_the_same_gaps_in_another_order():
    traffic = {"name": "t", "gap": {"kind": "shuffled", "values": [1, 2, 4, 8, 16]}}
    a, b = lite.schedule(traffic, 1, 10_000), lite.schedule(traffic, 2**31 + 5, 10_000)
    assert a != b and a[-1] <= 10_000 and b[-1] <= 10_000

    def gaps(asked):
        return [h - g for h, g in zip(asked, [1] + asked)]

    n = len(a) // 5 * 5
    assert sorted(gaps(a)[:n]) == sorted(gaps(b)[:n]) == sorted([1, 2, 4, 8, 16] * (n // 5))
    assert a == lite.schedule(traffic, 1, 10_000)  # the same seed, the same heights
    assert lite.schedule({"name": "t", "gap": 16}, 9, 100) == list(range(17, 101, 16))


@pytest.mark.parametrize("gap", [0, -1, "wide", {"kind": "uniform"}, {"kind": "shuffled", "values": []},
                                 {"kind": "shuffled", "values": [4, 0]}, None])
def test_a_gap_the_rig_cannot_draw_is_refused(gap):
    with pytest.raises(harness.HarnessFailure):
        lite.gap_values({"name": "t", "gap": gap})


def test_a_mode_the_rig_does_not_know_is_refused():
    with pytest.raises(harness.HarnessFailure):
        lite.mode_of(toy_cell(mode="backwards"))
    assert lite.mode_of(toy_cell(mode="bisection")) == "bisection"


def test_a_witness_that_was_not_asked_is_counted():
    reqs = [lite.Request(h, [h]) for h in (10, 20, 30)]
    window = types.SimpleNamespace(t_open_ns=100, t_close_ns=200)
    asked_all = {"commits": [[90, 5], [110, 10], [150, 20], [199, 30], [250, 40]]}
    missed_one = {"commits": [[110, 10], [199, 30]]}
    too_early = {"commits": [[99, 10], [150, 20], [199, 30]]}  # before the window opened
    assert lite.witness_checks_missed(reqs, window, [asked_all]) == 0
    assert lite.witness_checks_missed(reqs, window, [asked_all, missed_one]) == 1
    assert lite.witness_checks_missed(reqs, window, [too_early]) == 1
    assert lite.witness_checks_missed(reqs, window, [{"commits": []}, missed_one]) == 3


# -- the primaries, against the program's own provider ----------------------------------

WIDE = {"name": "toy-120", "validators": 120, "absent_share": 0.05,
        "power": {"kind": "equal", "each": 10}}
WIDE_HEIGHTS = 8


@pytest.fixture(scope="module")
def wide_chain(tmp_path_factory):
    """A chain whose set needs two pages of /validators, and its files."""
    root = tmp_path_factory.mktemp("wide")
    traffic = {"name": "lite", "txs_per_block": 1, "tx_bytes": 40}
    meta = chain.generate(WIDE, traffic, SEED, WIDE_HEIGHTS, str(root / "chain"), workers=1)
    (root / "config.json").write_text(json.dumps(WIDE))
    return str(root / "chain"), str(root / "config.json"), meta


async def test_a_primary_serves_the_generators_bytes_to_the_programs_provider(wide_chain):
    from tendermint_tpu.lite2.provider import HTTPProvider, SignedHeaderNotFound
    from tendermint_tpu.types import Block

    chain_dir, config_file, meta = wide_chain
    primaries = lite.Primaries()
    await primaries.start(chain_dir, config_file, SEED, 1)
    provider = HTTPProvider(meta["chain_id"], primaries.addrs[0])
    try:
        with open(os.path.join(chain_dir, "blocks.bin"), "rb") as f:
            raw = f.read()
        blocks = [Block.deserialize(raw[a:b]) for a, b in zip(meta["offsets"], meta["offsets"][1:])]
        for h in (1, 4, WIDE_HEIGHTS - 1):
            sh = await provider.signed_header(h)
            assert sh.header == blocks[h - 1].header and sh.header.hash().hex() == meta["hashes"][h - 1]
            assert sh.commit.to_dict() == blocks[h].last_commit.to_dict()  # the next block carries it
            assert sh.commit.hash() == blocks[h].header.last_commit_hash
            sh.validate_basic(meta["chain_id"])
        latest = await provider.signed_header(0)
        assert latest.height == WIDE_HEIGHTS - 1  # the last height whose commit the chain holds
        with pytest.raises(SignedHeaderNotFound):
            await provider.signed_header(WIDE_HEIGHTS)
        vals = await provider.validator_set(3)  # two pages of 100
        _, pubs, powers = chain.committee(SEED, WIDE)
        assert [v.pub_key.bytes() for v in vals.validators] == pubs
        assert [v.voting_power for v in vals.validators] == powers
        assert vals.hash() == blocks[2].header.validators_hash
        # the commit verifies under the set the primary serves: the whole of a light client's step
        vals.verify_commit(meta["chain_id"], sh.commit.block_id, sh.height, sh.commit)
        status = await provider.client.status()
        assert status["sync_info"]["latest_block_height"] == WIDE_HEIGHTS - 1
    finally:
        await provider.close()
        primaries.stop()
    (served,) = primaries.served
    assert served["calls"] == {"commit": 4, "validators": 2, "status": 1}  # a refused height is no call
    assert [h for _, h in served["commits"]] == [1, 4, WIDE_HEIGHTS - 1, WIDE_HEIGHTS - 1]
    stamps = [t for t, _ in served["commits"]]
    assert stamps == sorted(stamps) and 0 < time.monotonic_ns() - stamps[-1] < 60e9
    assert primaries.procs == []


async def test_a_lying_primary_forges_one_header_that_only_the_signatures_give_away(wide_chain):
    from tendermint_tpu.lite2.provider import HTTPProvider

    chain_dir, config_file, meta = wide_chain
    primaries = lite.Primaries()
    await primaries.start(chain_dir, config_file, SEED, 2)
    await primaries.switch(chain_dir, lie_at=5)  # as the rig hands them the chain
    providers = [HTTPProvider(meta["chain_id"], addr) for addr in primaries.addrs]
    try:
        forged = await providers[0].signed_header(5)
        assert forged.header.hash().hex() != meta["hashes"][4]
        assert (await providers[1].signed_header(5)).header == forged.header  # the witness agrees
        forged.validate_basic(meta["chain_id"])  # consistent in itself
        vals = await providers[0].validator_set(5)
        assert vals.hash() == forged.header.validators_hash
        with pytest.raises(ValueError, match="wrong signature"):
            vals.verify_commit(meta["chain_id"], forged.commit.block_id, 5, forged.commit)
        for h in (4, 6):
            assert (await providers[0].signed_header(h)).header.hash().hex() == meta["hashes"][h - 1]
    finally:
        for provider in providers:
            await provider.close()
        primaries.stop()


def test_a_primary_imports_neither_jax_nor_the_engine(wide_chain):
    chain_dir, config_file, _ = wide_chain
    code = (
        "import json, sys\n"
        "from benchmarks import primary\n"
        f"rpc = primary.ChainRPC({chain_dir!r}, json.load(open({config_file!r})), {SEED})\n"
        "rpc.commit(2), rpc.validators(2, 1, 100), rpc.status()\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib')"
        " or m.endswith('batch_verifier')))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                         timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


# -- whole runs on the CPU, the device stood in for ----------------------------------------

CHECKS = {
    "engine_errors", "host_tier_dispatches", "builds_in_window", "compiles_in_window",
    "blocks_without_device_dispatch", "requests_failed", "chain_exhausted", "window_empty",
    "witness_checks_missed", "wrong_block_ids", "heights_not_stored", "commits_accepted_wrongly",
    "verdict_mismatches",
}


@pytest.mark.parametrize("mode,gap", [("sequence", 1), ("bisection", 4)])
async def test_a_clean_run_is_correct_in_either_mode(scratch, mode, gap):
    result = await drive(["stub_device"], mode, gap)
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == CHECKS
    assert all(c == {"value": 0, "limit": 0} for c in result["checks"].values())
    assert set(result["metrics"]) == {"replay_blocks_per_s", "block_interval_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks" and result["failed"] == 0
    ctx = result["context"]
    first, last = ctx["heights_stored"]
    requests = ctx["requests_in_window"]
    # heights stored = what the stamps say: every height in sequence, the one asked for in bisection
    assert result["attempted"] == (last - first + 1 if mode == "sequence" else requests)
    assert (last - first) == (requests - 1) * gap
    assert ctx["mode"] == mode and ctx["gap"] == gap and ctx["errors"] == []
    primary, witness = ctx["served"]
    # a header and its set for every height verified; the witness asked once a request
    assert primary["commit"] == primary["validators"] >= result["attempted"]
    assert requests <= witness["commit"] <= requests + 20 and witness["validators"] == 0
    # a proxy twice as fast would still end short of the primaries' tip
    assert first + 2 * (last - first) <= HEIGHTS - 1 - lite.TIP_MARGIN_REQUESTS * gap
    assert not [f for f in os.listdir(scratch / "out") if f.endswith(".json")]


async def test_a_traced_run_reads_the_engines_metrics_and_none_of_replays(scratch):
    result = await drive(["stub_device"], trace_run=True)
    assert result["correct"] is True, result["checks"]
    got = result["metrics"]
    assert {"engine_wait_ms_per_block", "table_hit_share", "useful_rows_share",
            "dispatches_per_block", "block_interval_p50_ms"} <= set(got)
    assert got["dispatches_per_block"]["value"] == pytest.approx(1.0, abs=0.05)  # one a header
    assert got["table_hit_share"]["value"] == 100.0
    # nothing here delivers, queues, downloads or runs a replay loop: those readers read nothing
    assert not set(got) & {
        "deliver_ms_per_block", "deliver_ms_per_block.program", "replay_loop_ms_per_block",
        "replay_store_ms_per_block", "replay_queue_blocks_mean", "block_decode_ms_per_block",
        "block_download_ms", "p2p_loop_share", "loop_idle_share", "set_hash_ms_per_block",
        "device_idle_share", "verify_kernel_ms_per_block", "verify_kernel_roofline",
    }
    assert "breakdown" not in result and "busy_s" not in result["device"]


async def test_two_dispatches_a_request_are_due_in_bisection(scratch):
    result = await drive(["stub_device"], "bisection", 4, trace_run=True)
    assert result["correct"] is True, result["checks"]
    assert result["metrics"]["dispatches_per_block"]["value"] == pytest.approx(2.0, abs=0.1)


async def test_a_start_that_leaves_no_engine_fails_the_run(scratch):
    with pytest.raises(harness.HarnessFailure, match="the light client started without its verify engine"):
        await drive([], start=lite_start.without_engine)


async def test_files_alone_bring_a_lite_cell(scratch, tmp_path):
    """A configuration, a traffic file of kind `lite` and the entries in a
    BENCHMARK.json, in a root of their own: the harness finds the rig and
    runs the cell, no file of the benchmark edited."""
    root = tmp_path / "root"
    os.makedirs(root / "benchmarks" / "configs")
    os.makedirs(root / "benchmarks" / "traffic")
    (root / "benchmarks" / "configs" / "toy-24.json").write_text(json.dumps(
        dict(CONFIG, traffic_heights={"lite-sequence": HEIGHTS})
    ))
    traffic = toy_traffic("sequence", 2)
    del traffic["heights"]  # the configuration brings its own
    (root / "benchmarks" / "traffic" / "lite-sequence.json").write_text(json.dumps(traffic))
    (root / "BENCHMARK.json").write_text(json.dumps(dict(
        BENCH,
        configs=[{"name": "toy-24", "file": "benchmarks/configs/toy-24.json"}],
        workloads=[{"name": "toy-24.lite-sequence", "config": "toy-24",
                    "traffic": "lite-sequence", "chips": 1}],
    )))
    cell = harness.load_cell("toy-24.lite-sequence", str(root))
    assert cell.kind == "lite" and cell.heights == HEIGHTS
    assert {m["name"] for m in cell.end_to_end} == {"replay_blocks_per_s", "setup_s"}
    result = await harness.run_cell(
        cell, SEED, 1.0, False, time.monotonic(), faults=["stub_device"],
        start=lite_start.with_recorder,
    )
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"replay_blocks_per_s", "setup_s"}


async def test_without_a_witness_no_cross_check_is_held(scratch):
    result = await drive(["stub_device"], config=dict(CONFIG, witnesses=0))
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == CHECKS - {"witness_checks_missed"}
    assert len(result["context"]["served"]) == 1


# -- the controls ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,gap", [("sequence", 2), ("bisection", 4)])
async def test_accept_all_reads_four_of_four(scratch, mode, gap):
    result = await drive(["stub_device", "accept_all"], mode, gap)
    assert result["correct"] is False
    assert failed_checks(result) == {"verdict_mismatches"}
    assert result["checks"]["verdict_mismatches"]["value"] == 4


async def test_half_of_the_batch_left_out_comes_out_incorrect(scratch):
    result = await drive(["stub_device", "half_batch"])
    assert result["correct"] is False
    assert failed_checks(result) == {"verdict_mismatches"}


async def test_a_host_tier_dispatch_in_the_window_fails_the_run(scratch):
    result = await drive(["host_tier"])
    assert result["correct"] is False
    assert {"host_tier_dispatches", "blocks_without_device_dispatch"} <= failed_checks(result)


@pytest.mark.parametrize("mode,gap", [("sequence", 4), ("bisection", 4)])
async def test_a_sound_proxy_refuses_the_lie_and_stores_nothing_from_it_on(scratch, mode, gap):
    result = await drive(["stub_device", "lying_primary"], mode, gap)
    ctx = result["context"]
    lie_at = ctx["lie_at"]
    assert result["checks"]["forged_headers_stored"] == {"value": 0, "limit": 0}
    assert failed_checks(result) == set(), result["checks"]
    ((height, error),) = ctx["errors"]  # the proxy's answer where the request met the lie
    assert height - (gap if mode == "sequence" else 1) < lie_at <= height
    assert "wrong signature" in error
    # the window cut short at the lie
    assert ctx["heights_stored"][1] < lie_at and result["metrics"]["replay_blocks_per_s"]["value"] > 0
    assert 0 < result["attempted"] < lie_at


@pytest.mark.parametrize("mode,gap", [("sequence", 4), ("bisection", 4)])
async def test_with_every_verdict_altered_the_forgery_is_stored_and_the_run_fails(scratch, mode, gap):
    result = await drive(["stub_device", "lying_primary", "accept_all"], mode, gap)
    assert result["correct"] is False
    failed = failed_checks(result)
    assert {"forged_headers_stored", "wrong_block_ids", "verdict_mismatches"} <= failed
    assert result["checks"]["forged_headers_stored"]["value"] > 0
    assert result["context"]["errors"] == []  # nothing was refused
    assert result["context"]["heights_stored"][1] > result["context"]["lie_at"]


def test_an_unknown_fault_is_refused_before_anything_starts():
    import asyncio

    with pytest.raises(ValueError, match="state_unchanged"):
        asyncio.run(lite.run_cell(toy_cell(), SEED, 1.0, False, 0.0, faults=["state_unchanged"]))


# -- the ring's readers take the recorder the rig hands them (ROADMAP D15) -------------------


def test_the_rings_readers_read_the_windows_recorder_whoever_else_is_alive(monkeypatch):
    from benchmarks.reducers import ring_sum_per_block
    from tendermint_tpu.libs import tracing

    def ring(ms):
        return types.SimpleNamespace(enabled=True, events=lambda since=0, kinds=None: [
            {"kind": "fastsync.block", "seq": i, "t_ns": (2 + i) * 10**9, "dur_ns": ms * 10**6}
            for i in range(4)
        ])

    def window(recorder=None):
        return harness.Window(
            cell=toy_cell(), seconds=4.0, t_open_ns=10**9, t_close_ns=6 * 10**9, block_times=[],
            block_heights=[], events=[], deliver_spans=[], buffered=[], recorder=recorder,
        )

    params = {"kind": "fastsync.block", "fields": ["dur_ns"], "scale": 1e-6}
    ours, left_behind = ring(7), ring(900)
    monkeypatch.setattr(tracing, "live_recorders", lambda: [left_behind, ours])
    assert ring_sum_per_block.read(window(ours), params) == pytest.approx(7.0)
    assert ring_sum_per_block.read(window(), params) is None  # two alive, none handed: whose?
    monkeypatch.setattr(tracing, "live_recorders", lambda: [left_behind])
    assert ring_sum_per_block.read(window(), params) == pytest.approx(900.0)  # the one alive
    assert ring_sum_per_block.read(window(ours), params) == pytest.approx(7.0)
