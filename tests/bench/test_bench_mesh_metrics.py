"""The four-chip cell's files and readers (PR 26): the configuration keeps
committee-10k's shape, the cell loads with four chips and reports the four
metrics that only it lists, and each reader reads a hand-built window to the
number worked out by hand: `shard_n` with the padding in the last shard, four
devices' busy times, the explicit transfer's `put_ms`.  Where there is
nothing to read (the parent commit's events, one device, no trace) a reader
returns nothing and never 0.
"""

import importlib
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, trace  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

CELL = "committee-10k.replay-mesh4"
MESH_METRICS = {
    "engine_put_ms_per_block": ("ms", "lower", "program_span", "engine routing"),
    "useful_rows_share.min_shard": ("%", "higher", "program_counter", "engine routing"),
    "device_busy_skew": ("%", "lower", "device_trace", "device"),
    "host_prep_ms_per_block.mesh4": ("ms", "lower", "program_span", "host prep"),
}
MS = 1_000_000
T_OPEN, T_CLOSE = 10**9, 41 * 10**9


def load(metric):
    with open(os.path.join(REPO, "benchmarks", "metrics", metric + ".json")) as f:
        spec = json.load(f)
    return spec, importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")


def read(metric, window):
    spec, reader = load(metric)
    return reader.read(window, spec["params"])


def window(blocks, events=(), busy_by_device=None):
    cell = harness.Cell("toy.replay-mesh4", 4, {}, {}, 0, end_to_end=[], per_layer=[])
    summary = None
    if busy_by_device is not None:
        summary = trace.TraceSummary(
            window_s=40.0, busy_s=sum(busy_by_device.values()) / max(1, len(busy_by_device)),
            busy_s_by_device=busy_by_device, idle_gaps=[], op_seconds={}, kernel_seconds={},
        )
    return harness.Window(
        cell=cell, seconds=(T_CLOSE - T_OPEN) / 1e9, t_open_ns=T_OPEN, t_close_ns=T_CLOSE,
        block_times=[T_OPEN / 1e9 + i for i in range(1, blocks + 1)],
        block_heights=list(range(1, blocks + 1)), events=list(events), deliver_spans=[],
        buffered=[], trace=summary,
    )


def dispatch(t_s, n, bucket, path="indexed", shard_n=None, host_prep=30.0, put=None, **more):
    ev = {"kind": "verify.dispatch", "t_ns": int(t_s * 1e9), "n": n, "bucket": bucket,
          "path": path, "shards": len(shard_n) if shard_n else 1, "host_prep_ms": host_prep,
          "device_ms": 20.0, "pack_ms": 1.0, "launch_ms": 6.0, "fetch_ms": 13.0}
    if shard_n is not None:
        ev.update(shard_n=shard_n, kernel="ladder")
    if put is not None:
        ev["put_ms"] = put
    ev.update(more)
    return ev


# one block of the four-chip cell: two dispatches over the mesh, the padding
# of the 10,240-row bucket on the fourth chip
MESH_BLOCK = [
    dispatch(2, 9500, 10240, shard_n=[2560, 2560, 2560, 1820], host_prep=31.0, put=2.5),
    dispatch(3, 9480, 10240, shard_n=[2560, 2560, 2560, 1800], host_prep=33.0, put=3.5),
]
# what the parent commit's engine records of the same block: no `shard_n`, no
# `kernel`, no `put_ms`
PARENT_BLOCK = [
    {k: v for k, v in ev.items() if k not in ("shard_n", "kernel", "put_ms")}
    for ev in MESH_BLOCK
]


# -- the data files -------------------------------------------------------------


def test_the_benchmark_gains_one_configuration_one_cell_and_four_metrics():
    """PR 26's entries are there and well-formed, after the 23 metrics, two
    configurations and three cells it found; what later PRs add after them is
    theirs to hold."""
    assert [c["name"] for c in BENCH["configs"]][2] == "committee-10k-mesh4"
    assert BENCH["workloads"][3] == {
        "name": CELL, "config": "committee-10k-mesh4", "traffic": "replay-mesh4", "chips": 4,
        "why": BENCH["workloads"][3]["why"],
    }
    assert [w["chips"] for w in BENCH["workloads"]].count(4) == 1
    before, mesh = BENCH["per_layer"][:23], BENCH["per_layer"][23:27]
    assert [m["name"] for m in mesh] == list(MESH_METRICS)
    for m in mesh:
        unit, better, source, layer = MESH_METRICS[m["name"]]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (unit, better, source, layer)
        assert m["moves"] == "replay_blocks_per_s" and m["workloads"] == [CELL]
        assert layer in {x["layer"] for x in before}  # a layer already named
    # no other metric that lists its cells was given this one
    for m in before + BENCH["per_layer"][27:] + BENCH["end_to_end"]:
        assert CELL not in m.get("workloads", [])


def test_the_configuration_keeps_committee_10ks_shape():
    with open(os.path.join(REPO, "benchmarks", "configs", "committee-10k.json")) as f:
        one_chip = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "configs", "committee-10k-mesh4.json")) as f:
        mesh4 = json.load(f)
    same = ("validators", "key_scheme", "power", "absent_share", "validator_set_changes",
            "source_peers", "peer_link", "app", "node", "guarantees", "reduced")
    for key in same:
        assert mesh4[key] == one_chip[key], key
    assert set(mesh4) == set(one_chip) | {"traffic_heights"}
    assert mesh4["traffic_heights"] == {"replay-mesh4": 260}
    assert set(one_chip["assumed"]) < set(mesh4["assumed"])  # and the host besides
    assert "v5e-4" in mesh4["assumed"]["host"] and "30 host cores" in mesh4["assumed"]["host"]
    assert "replicated" in mesh4["chips"] and "Straus" not in mesh4["chips"]


def test_the_traffic_is_the_replay_mix_under_its_own_name():
    with open(os.path.join(REPO, "benchmarks", "traffic", "replay.json")) as f:
        replay = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "traffic", "replay-mesh4.json")) as f:
        mesh4 = json.load(f)
    assert mesh4["name"] == "replay-mesh4"
    for key in ("txs_per_block", "tx_bytes"):
        assert mesh4[key] == replay[key]
    assert mesh4["warm_in_blocks"] == replay["warm_in_blocks"]["committee-10k"] == 12
    assert mesh4["heights"] == {"committee-10k-mesh4": replay["heights"]["committee-10k"]}


def test_only_the_four_chip_cell_reports_the_four_metrics():
    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.heights, cell.config["validators"]) == (4, 260, 10000)
    reported = {m["name"] for m in cell.per_layer}
    assert set(MESH_METRICS) <= reported
    # and every accepted metric that lists no cells, the kernel's roofline among them
    assert {"verify_kernel_roofline", "verify_kernel_ms_per_block", "device_idle_share",
            "dispatches_per_block", "useful_rows_share", "engine_launch_ms_per_block"} <= reported
    assert not reported & {"host_prep_ms_per_block", "host_prep_ms_per_block.chunked"}
    assert {m["name"] for m in cell.end_to_end} == {"replay_blocks_per_s", "setup_s"}
    for other in ("hub-175.replay", "committee-10k.replay", "hub-175.replay-full"):
        assert not set(MESH_METRICS) & {m["name"] for m in harness.load_cell(other).per_layer}


# -- the readers, on hand-built windows -------------------------------------------


BY_HAND = {
    "engine_put_ms_per_block": 2.5 + 3.5,
    # the least-filled shard is the fourth: (1820 + 1800) of its 2 x 2560 rows
    "useful_rows_share.min_shard": 100.0 * (1820 + 1800) / (2 * 2560),
    "host_prep_ms_per_block.mesh4": 31.0 + 33.0,
    "device_busy_skew": 100.0 * (1.2 - 0.9) / 1.2,
}
BUSY = {"/device:TPU:0": 1.2, "/device:TPU:1": 1.1, "/device:TPU:2": 1.15, "/device:TPU:3": 0.9}


@pytest.mark.parametrize("metric", list(MESH_METRICS))
def test_each_reader_reads_one_mesh_block_to_the_number_worked_out_by_hand(metric):
    spec, _ = load(metric)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert read(metric, window(1, MESH_BLOCK, BUSY)) == pytest.approx(BY_HAND[metric])


@pytest.mark.parametrize("metric", list(MESH_METRICS))
def test_the_parent_commits_events_and_one_device_read_nothing(metric):
    """The parent on this PR's benchmark files: its dispatch events lack the
    new fields and its run holds one device.  No reader raises, and none
    makes a 0 up (host prep was always there, and reads as it is)."""
    got = read(metric, window(1, PARENT_BLOCK, {"/device:TPU:0": 1.2}))
    assert got == (64.0 if metric == "host_prep_ms_per_block.mesh4" else None)
    assert read(metric, window(1, [], None)) is None  # no event, no trace


def test_min_shard_fill_by_path():
    def fill(*events):
        return read("useful_rows_share.min_shard", window(1, events))

    # a full bucket: every shard full
    assert fill(dispatch(2, 10240, 10240, shard_n=[2560] * 4)) == 100.0
    # a batch that leaves the last shard empty
    assert fill(dispatch(2, 45, 64, shard_n=[16, 16, 13, 0])) == 0.0
    # routed to one device of the mesh: its one shard is the bucket
    assert fill(dispatch(2, 166, 512, shard_n=[166], device=0)) == pytest.approx(100 * 166 / 512)
    # chunked: `bucket` is one chunk, `shard_n` the chunks added up; 20,000
    # signatures are three chunks of 8,192 rows, 2,048 a shard
    chunked = dispatch(2, 20000, 8192, path="chunked", shard_n=[6144, 5664, 4096, 4096])
    assert fill(chunked) == pytest.approx(100 * 4096 / (3 * 2048))
    # dispatches add up row by row, not share by share; the host tier is no device dispatch
    small = dispatch(3, 166, 512, shard_n=[166], device=0)
    host = dispatch(4, 12, 0, path="host", shard_n=[12])
    assert fill(MESH_BLOCK[0], small, host) == pytest.approx(100 * (1820 + 166) / (2560 + 512))
    # an event without the field is skipped, and none with it reads nothing
    assert fill(MESH_BLOCK[0], PARENT_BLOCK[1]) == pytest.approx(100 * 1820 / 2560)
    assert fill(PARENT_BLOCK[0], host) is None


def test_busy_skew_from_a_hand_built_trace_of_four_devices():
    """Through the reduction that a traced run uses: four device planes, the
    fourth chip's kernel shorter by its padding."""
    anchor_mono, anchor_trace = 50_000 * MS, 7 * MS
    t_open = anchor_mono + 100 * MS

    def at(ms):
        return ms * MS + t_open - (anchor_mono - anchor_trace)

    events = [("/host:CPU", "python", trace.ANCHOR, anchor_trace, 1)]
    for dev, kernel_ms in enumerate((10, 10, 10, 7)):
        plane = f"/device:TPU:{dev}"
        events.append((plane, "XLA Modules", "jit_run(99)", at(20), kernel_ms * MS))
        events.append((plane, "XLA Ops", "verify_prepared_pallas.1", at(20), kernel_ms * MS))
    patterns = [{"name": "indexed_run", "line": "XLA Modules",
                 "regex": re.compile(r"^jit_run(\(|$)")}]
    summary = trace.summarize_events(events, anchor_mono, t_open, t_open + 100 * MS, patterns)
    assert summary.busy_s_by_device == {
        f"/device:TPU:{d}": pytest.approx(ms / 1e3) for d, ms in enumerate((10, 10, 10, 7))}
    w = window(1, MESH_BLOCK)
    w.trace = summary
    assert read("device_busy_skew", w) == pytest.approx(30.0)
    assert summary.kernel_seconds == {"indexed_run": pytest.approx(0.037)}  # summed over the chips
    # no device did anything: nothing to compare
    assert read("device_busy_skew", window(1, [], dict.fromkeys(BUSY, 0.0))) is None


def test_put_time_is_read_only_from_the_dispatches_that_put():
    """`put_ms` is on every mesh dispatch and every chunk; a batch routed to
    one device of the mesh puts nothing by hand and carries no such field."""
    routed = dispatch(3, 166, 512, shard_n=[166], device=0)
    assert read("engine_put_ms_per_block", window(2, MESH_BLOCK + [routed])) == 3.0
    assert read("engine_put_ms_per_block", window(2, [routed])) is None
    flat = dispatch(4, 4096, 4096, path="device", shard_n=[1024] * 4, put=9.0)
    assert read("engine_put_ms_per_block", window(1, [flat])) is None  # not a table path
