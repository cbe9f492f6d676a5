"""The rig of kind `catchup` (benchmarks/rigs/catchup.py): the cell as its
files bring it, its parts on their own (the catch-up peer, the control
frames, the reader of a height's frames), and whole runs at toy size on the
CPU with the device stood in for: clean, traced, with the control planted,
and on a program that opens no `consensus.height` span, which has to fail
at once.

No child that a test starts loads libtpu: the catch-up peers and the chain
generator run with JAX_PLATFORMS=cpu and never import JAX.
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

import bench_rules  # noqa: E402

from benchmarks import chain, harness, reference  # noqa: E402
from benchmarks.rigs import catchup  # noqa: E402

SEED = 2_147_483_777
HEIGHTS = 400  # the toy node commits 25-45 heights in its 1 s window
CELL = "hub-175.catchup"
FOUR = ["consensus_height_ms_per_block", "vote_lane_ms_per_block", "vote_verify_ms_per_block",
        "votes_verified_per_block"]

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

CONFIG = {
    "name": "toy-24", "validators": 24, "absent_share": 0.05,
    "power": {"kind": "zipf", "top": 1000, "s": 0.8},
    "app": "kvstore", "node": {"db_backend": "memdb"}, "catchup_peers": 2,
}
TRAFFIC = {"name": "catchup", "kind": "catchup", "txs_per_block": 4, "tx_bytes": 60,
           "warm_in_blocks": 10, "heights": {"toy-24": HEIGHTS}}


def toy_cell():
    """The toy committee under the cell's own metrics: 24 validators put
    ~22 precommits in a frame, above min_device_batch."""
    real = harness.load_cell(CELL)
    return harness.Cell("toy-24.catchup", 1, CONFIG, TRAFFIC, HEIGHTS, real.end_to_end,
                        real.per_layer)


@pytest.fixture(scope="module")
def chains(tmp_path_factory):
    return str(tmp_path_factory.mktemp("chains"))


@pytest.fixture
def scratch(tmp_path, monkeypatch, chains):
    monkeypatch.setattr(harness, "CACHE_DIR", chains)
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    return tmp_path


def on_the_cpu(cfg):
    cfg.tpu.enabled = False  # the stand-in takes the engine's place


async def drive(faults, trace_run=False, seconds=1.0):
    return await harness.run_cell(toy_cell(), SEED, seconds, trace_run, time.monotonic(),
                                  faults=faults, configure=on_the_cpu)


def failed_checks(result):
    return {k for k, c in result["checks"].items() if c["value"] != c["limit"]}


# -- the cell as its files bring it ----------------------------------------------------------


def test_the_cell_loads_with_its_rig_and_reports_the_eleven_and_its_four():
    cell = harness.load_cell(CELL)
    assert cell.kind == "catchup" and harness.find_rig(cell.kind) is catchup.run_cell
    assert cell.heights == 6000 and harness.warm_in_blocks(cell.config, cell.traffic) == 64
    assert [m["name"] for m in cell.end_to_end] == ["replay_blocks_per_s", "setup_s"]
    # every metric without a list (the engine's, the device's, the stamps'),
    # eleven as the cell came, and its own four
    every = bench_rules.fed_by_every_rig(BENCH)
    assert every[:11] == [
        "engine_wait_ms_per_block", "table_hit_share", "useful_rows_share", "dispatches_per_block",
        "verify_kernel_ms_per_block", "verify_kernel_roofline", "device_idle_share",
        "block_interval_p50_ms", "sign_bytes_ms_per_block", "engine_launch_ms_per_block",
        "engine_fetch_ms_per_block"]
    names = [m["name"] for m in cell.per_layer]
    assert sorted(names) == sorted(every + FOUR) and len(names) == len(set(names))
    assert cell.not_listed == []
    for name in FOUR:
        spec = bench_rules.metric_file(REPO, name)
        assert spec["kinds"] == ["catchup"] and spec["params"]["block_span"] == "consensus.height"
    assert bench_rules.unreadable(BENCH, REPO) == []


def test_the_configuration_keeps_the_hubs_set():
    hub = bench_rules.load(REPO, "benchmarks", "configs", "hub-175.json")
    ours = harness.load_cell(CELL).config
    for key in ("validators", "key_scheme", "power", "absent_share", "validator_set_changes", "app"):
        assert ours[key] == hub[key], key
    assert ours["node"]["fast_sync"] is False and ours["node"]["db_backend"] == "memdb"
    assert chain.committee(SEED, ours)[1:] == chain.committee(SEED, hub)[1:]


# -- the parts ------------------------------------------------------------------------------


def test_a_minority_holds_less_than_a_third():
    powers = chain.powers(CONFIG)
    who = catchup.minority(powers)
    held = sum(powers[i] for i in who)
    assert 3 * held < sum(powers)
    heaviest_left = max(powers[i] for i in range(len(powers)) if i not in who)
    assert 3 * (held + min(powers[i] for i in range(len(powers)) if i not in who)) >= sum(powers)
    assert heaviest_left >= max(powers[i] for i in who)


@pytest.mark.parametrize("kind", harness.TAMPERS)
def test_a_control_frame_holds_one_vote_the_reference_refuses(kind):
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.types import BlockID, PartSetHeader, Validator, ValidatorSet

    secrets, pubs, powers = chain.committee(SEED, CONFIG)
    vset = ValidatorSet([Validator.new(Ed25519PubKey(p), w) for p, w in zip(pubs, powers)])
    who = catchup.minority(powers)
    block_id = BlockID(b"\x05" * 32, PartSetHeader(1, b"\x06" * 32))
    votes = catchup.control_frame("c", 9, 4, block_id, vset, secrets, who, kind, who[1], 77)
    ok = [reference.verify_one(pubs[v.validator_index], reference.vote_sign_bytes(
        "c", 9, 4, b"\x05" * 32, 1, b"\x06" * 32, 77), v.signature) for v in votes]
    assert ok == [i != who[1] for i in who]
    assert [v.validator_index for v in votes] == who


def test_a_heights_frames_are_summed_per_height():
    from benchmarks.reducers import ring_sum_per_span

    ring = [{"kind": "consensus.height", "seq": i, "t_ns": (2 + i) * 10**9, "dur_ns": 9}
            for i in range(4)]
    ring += [{"kind": "consensus.vote_batch", "seq": 10 + i, "t_ns": (2 + i // 2) * 10**9,
              "n": 166 if i % 2 == 0 else 3, "verify_ms": 4.0} for i in range(8)]
    recorder = types.SimpleNamespace(enabled=True, events=lambda since=0, kinds=None: ring)
    window = harness.Window(
        cell=toy_cell(), seconds=4.0, t_open_ns=10**9, t_close_ns=6 * 10**9, block_times=[],
        block_heights=[], events=[], deliver_spans=[], buffered=[], recorder=recorder)
    params = {"kind": "consensus.vote_batch", "block_span": "consensus.height", "fields": ["n"]}
    assert ring_sum_per_span.read(window, params) == pytest.approx(169.0)
    assert ring_sum_per_span.read(window, dict(params, fields=["verify_ms"])) == pytest.approx(8.0)
    assert ring_sum_per_span.read(window, dict(params, kind="consensus.nothing")) is None


def test_a_catchup_peer_imports_neither_jax_nor_the_engine(tmp_path):
    traffic = {"name": "catchup", "txs_per_block": 1, "tx_bytes": 40}
    chain.generate(CONFIG, traffic, SEED, 6, str(tmp_path / "chain"), workers=1)
    (tmp_path / "config.json").write_text(json.dumps(CONFIG))
    code = (
        "import json, sys\n"
        "from benchmarks import catchup_peer\n"
        f"store = catchup_peer.ChainBlockStore({str(tmp_path / 'chain')!r})\n"
        f"vset = catchup_peer.validator_set(store, json.load(open({str(tmp_path / 'config.json')!r})))\n"
        "assert store.load_block_commit(4).height == 4 and store.load_block_commit(6) is None\n"
        "assert store.load_block_part(3, 0).index == 0 and store.load_block_meta(7) is None\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib')"
        " or m.endswith('batch_verifier')))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                         timeout=120, cwd=REPO)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


# -- a program without the span --------------------------------------------------------------


def test_a_tree_whose_consensus_opens_no_height_span_is_refused_at_once():
    """As the parent commit is: run.py exits 3 before it looks for a chip."""
    code = (
        "import sys\n"
        "from tendermint_tpu.libs import tracing\n"
        "tracing.STAMPED_KINDS = tracing.STAMPED_KINDS - {'consensus.height'}\n"
        "from benchmarks import run\n"
        f"sys.exit(run.main(['--workload', {CELL!r}, '--seed', '7', '--seconds', '40']))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    t0 = time.monotonic()
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                         timeout=60, cwd=REPO)
    assert out.returncode == 3, out.stderr
    assert time.monotonic() - t0 < 60 and out.stdout == ""
    assert "consensus.height" in out.stderr


async def test_a_node_that_opens_no_height_span_fails_its_warm_in(scratch, monkeypatch):
    from tendermint_tpu.consensus.state import ConsensusState

    monkeypatch.setattr(ConsensusState, "_open_height_span", lambda self: None)
    t0 = time.monotonic()
    with pytest.raises(harness.HarnessFailure, match="opens none"):
        await drive(["stub_device"])
    assert time.monotonic() - t0 < 60


# -- whole runs on the CPU, the device stood in for ---------------------------------------------

CHECKS = {
    "engine_errors", "host_tier_dispatches", "builds_in_window", "compiles_in_window",
    "blocks_without_device_dispatch", "fast_sync_blocks", "chain_exhausted", "window_empty",
    "wrong_block_ids", "commits_accepted_wrongly", "wrong_reads", "verdict_mismatches",
}


async def test_a_clean_run_is_correct(scratch):
    result = await drive(["stub_device"])
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == CHECKS
    assert all(c == {"value": 0, "limit": 0} for c in result["checks"].values())
    assert set(result["metrics"]) == {"replay_blocks_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    ctx = result["context"]
    first, last = ctx["heights_committed"]
    assert result["attempted"] == last - first + 1 >= 10
    assert ctx["span_coverage"]["without_height_span"] == 0
    assert ctx["span_coverage"]["without_vote_batch_span"] == 0
    # both peers served: frames of votes, then parts
    assert len(ctx["served"]) == 2 and all(s["frames"] and s["parts"] for s in ctx["served"])
    assert first + 2 * (last - first) <= HEIGHTS - 1 - catchup.TIP_MARGIN_HEIGHTS
    assert not [f for f in os.listdir(scratch / "out") if f.endswith(".json")]


async def test_a_traced_run_reads_its_four_and_the_engines(scratch):
    result = await drive(["stub_device"], trace_run=True)
    assert result["correct"] is True, result["checks"]
    got = result["metrics"]
    assert set(FOUR) <= set(got)
    assert {"engine_wait_ms_per_block", "table_hit_share", "useful_rows_share",
            "dispatches_per_block", "block_interval_p50_ms", "sign_bytes_ms_per_block"} <= set(got)
    # two commit checks a height through the table: finalize's and apply_block's
    assert got["dispatches_per_block"]["value"] == pytest.approx(2.0, abs=0.1)
    # a height's frames: one from each peer, the second's votes mostly held already
    assert CONFIG["validators"] * 0.8 <= got["votes_verified_per_block"]["value"] <= 2 * 24
    assert got["vote_lane_ms_per_block"]["value"] >= got["vote_verify_ms_per_block"]["value"]
    assert got["consensus_height_ms_per_block"]["value"] > 0


async def test_accept_all_reads_four_of_four(scratch):
    result = await drive(["stub_device", "accept_all"])
    assert result["correct"] is False
    assert failed_checks(result) == {"verdict_mismatches"}
    assert result["checks"]["verdict_mismatches"]["value"] == 4


async def test_a_host_tier_dispatch_in_the_window_fails_the_run(scratch):
    result = await drive(["host_tier"])
    assert result["correct"] is False
    assert {"host_tier_dispatches", "blocks_without_device_dispatch"} <= failed_checks(result)


def test_an_unknown_fault_is_refused_before_anything_starts():
    import asyncio

    with pytest.raises(ValueError, match="lying_primary"):
        asyncio.run(catchup.run_cell(toy_cell(), SEED, 1.0, False, 0.0, faults=["lying_primary"]))
