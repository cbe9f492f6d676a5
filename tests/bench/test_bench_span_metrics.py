"""The per-layer metrics that read the program's own spans (PR 24): each
has its file and its BENCHMARK.json entry, reads a hand-built window and
ring to the number worked out by hand, and reads nothing (never 0) where
there is nothing to read: a program without the spans, a ring that holds
too little of the window, no recorder or several.
"""

import importlib
import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness  # noqa: E402
from benchmarks.reducers import ring_sum_per_block  # noqa: E402
from tendermint_tpu.libs import tracing  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

ACCEPTED = [  # per-layer metrics of PR 23, which stay as they are and come first
    "deliver_ms_per_block", "engine_wait_ms_per_block", "table_hit_share",
    "useful_rows_share", "dispatches_per_block", "host_prep_ms_per_block",
    "verify_kernel_ms_per_block", "verify_kernel_roofline", "device_idle_share",
    "block_interval_p95_ms.replay", "block_interval_p50_ms", "replay_queue_blocks_mean",
]
T_OPEN, T_CLOSE = 10**9, 41 * 10**9


class Ring:
    """A recorder as the readers see it: `events()`, oldest first."""

    enabled = True

    def __init__(self, events, first_seq=0):
        self._events = [dict(ev, seq=first_seq + i) for i, ev in enumerate(events)]

    def events(self, since=0, kinds=None):
        return list(self._events)


def window(blocks, events=()):
    cell = harness.Cell("toy.replay", 1, {}, {}, 0, end_to_end=[], per_layer=[])
    return harness.Window(
        cell=cell, seconds=(T_CLOSE - T_OPEN) / 1e9, t_open_ns=T_OPEN, t_close_ns=T_CLOSE,
        block_times=[T_OPEN / 1e9 + i for i in range(1, blocks + 1)],
        block_heights=list(range(1, blocks + 1)), events=list(events), deliver_spans=[],
        buffered=[],
    )


def dispatch(t_s, path, host_prep, pack=None, launch=None, fetch=None):
    ev = {"kind": "verify.dispatch", "t_ns": int(t_s * 1e9), "n": 9500, "bucket": 2048,
          "path": path, "shards": 1, "host_prep_ms": host_prep, "device_ms": 30.0}
    if pack is not None:
        ev.update(pack_ms=pack, launch_ms=launch, fetch_ms=fetch)
    return ev


# what the harness's poll hands the readers: two blocks' verify.* events, the
# second block's dispatches from a stand-in that knows no launch or fetch
POLLED = [
    {"kind": "verify.commit", "t_ns": 2 * 10**9, "id": 5, "n": 9500, "sign_bytes_ms": 40.0,
     "engine_ms": 35.0, "tally_ms": 2.0},
    {"kind": "verify.commit", "t_ns": 3 * 10**9, "id": 5, "n": 9500, "sign_bytes_ms": 44.0,
     "engine_ms": 35.0, "tally_ms": 2.0},
    dispatch(2, "chunked", 12.0, pack=1.0, launch=3.0, fetch=14.0),
    dispatch(3, "chunked", 14.0, pack=1.5, launch=3.5, fetch=16.0),
    dispatch(4, "indexed", 0.5),
    dispatch(5, "host", 0.0, pack=99.0, launch=99.0, fetch=99.0),  # not a table path
]


def block(t_s, height, dur_ms, **fields):
    return dict({"kind": "fastsync.block", "t_ns": int(t_s * 1e9), "id": height,
                 "parent": None, "dur_ns": int(dur_ms * 1e6)}, **fields)


# what the node's ring holds: one block before the window, two inside it, one
# after; two loop.busy events inside it (1000 ms of the loop's time)
RING = [
    block(0.5, 4, 500.0, parts_ms=9.0, store_ms=9.0, save_state_ms=9.0, deliver_ms=9.0,
          decode_ms=9.0, download_ms=9.0),
    block(2.0, 5, 800.0, parts_ms=100.0, store_ms=150.0, save_state_ms=50.0, deliver_ms=26.0,
          decode_ms=60.0, download_ms=4000.0),
    {"kind": "loop.busy", "t_ns": 2 * 10**9 + 1, "interval_ms": 600.0, "fastsync_ms": 300.0,
     "p2p-conn_ms": 120.0, "other_ms": 30.0},
    block(3.0, 6, 900.0, parts_ms=110.0, store_ms=170.0, save_state_ms=60.0, deliver_ms=28.0,
          decode_ms=70.0, download_ms=5000.0),
    {"kind": "loop.busy", "t_ns": 3 * 10**9 + 1, "interval_ms": 400.0, "fastsync_ms": 250.0,
     "p2p-conn_ms": 80.0},
    {"kind": "loop.lag", "t_ns": 3 * 10**9 + 2, "lag_ms": 700.0},
    block(41.5, 7, 100.0, parts_ms=1.0, store_ms=1.0, save_state_ms=1.0, deliver_ms=1.0,
          decode_ms=1.0, download_ms=1.0),
]

BY_HAND = {
    "sign_bytes_ms_per_block": (40.0 + 44.0) / 2,
    "engine_launch_ms_per_block": (1.0 + 3.0 + 1.5 + 3.5) / 2,
    "engine_fetch_ms_per_block": (14.0 + 16.0) / 2,
    "host_prep_ms_per_block.chunked": (12.0 + 14.0) / 2,
    "replay_loop_ms_per_block": (800.0 + 900.0) / 2,
    "replay_store_ms_per_block": (100.0 + 150.0 + 50.0 + 110.0 + 170.0 + 60.0) / 2,
    "deliver_ms_per_block.program": (26.0 + 28.0) / 2,
    "block_decode_ms_per_block": (60.0 + 70.0) / 2,
    "block_download_ms": (4000.0 + 5000.0) / 2,
    "p2p_loop_share": 100.0 * (120.0 + 80.0) / 1000.0,
    "loop_idle_share": 100.0 * (1 - (300.0 + 120.0 + 30.0 + 250.0 + 80.0) / 1000.0),
}
RING_READERS = ("ring_sum_per_block", "ring_share")


def load(metric):
    with open(os.path.join(REPO, "benchmarks", "metrics", metric + ".json")) as f:
        spec = json.load(f)
    return spec, importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")


@pytest.fixture
def ring(monkeypatch):
    """The node's recorder is the hand-built ring, and the only one alive."""

    def install(*rings):
        monkeypatch.setattr(tracing, "live_recorders", lambda: list(rings))

    install(Ring(RING))
    return install


def test_the_benchmark_gains_the_eleven_entries_and_nothing_else():
    """PR 24's eleven are there, after PR 23's twelve and well-formed; what
    later PRs add after them is theirs to hold."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:len(ACCEPTED)] == ACCEPTED
    assert sorted(names[len(ACCEPTED):len(ACCEPTED) + 11]) == sorted(BY_HAND)
    assert len(set(names)) == len(names)
    layers = {m["layer"] for m in BENCH["per_layer"][:len(ACCEPTED)]}
    for m in BENCH["per_layer"][len(ACCEPTED):len(ACCEPTED) + 11]:
        assert m["moves"] == "replay_blocks_per_s" and m["better"] == "lower"
        assert m["unit"] == ("%" if m["name"].endswith("_share") else "ms")
        assert m["layer"] in layers  # a layer the benchmark already names
        assert "bound" not in m
        assert m.get("workloads") == (
            ["committee-10k.replay"] if m["name"] == "host_prep_ms_per_block.chunked" else None)


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_each_new_metric_reads_the_hand_built_run_to_the_number_worked_out_by_hand(metric, ring):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    spec, reader = load(metric)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert reader.read(window(2, POLLED), spec["params"]) == pytest.approx(BY_HAND[metric])


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_program_without_the_spans_reads_nothing(metric, ring, monkeypatch):
    """The parent commit: no `live_recorders`, no verify.commit event, no
    launch or fetch on a dispatch.  No new reader raises, none makes a 0 up
    (host prep on the chunked path was the constant 0.0 there, and read so)."""
    spec, reader = load(metric)
    monkeypatch.delattr(tracing, "live_recorders")
    old = [dispatch(2, "chunked", 0.0), dispatch(3, "chunked", 0.0)]
    got = reader.read(window(2, old), spec["params"])
    assert got == (0.0 if metric == "host_prep_ms_per_block.chunked" else None)


@pytest.mark.parametrize("metric", [m for m in sorted(BY_HAND) if load(m)[0]["reducer"] in RING_READERS])
def test_a_ring_that_holds_too_little_of_the_window_reads_nothing(metric, ring):
    spec, reader = load(metric)
    w = window(2, POLLED)
    # the window's first second has aged out of the ring: two blocks stand for nothing
    ring(Ring(RING[1:], first_seq=3000))
    assert reader.read(w, spec["params"]) is None
    # a hundred blocks of it do, and read as their own mean
    busy = [ev for ev in RING if ev["kind"] == "loop.busy"]
    many = [block(10 + i / 10, 100 + i, 20.0, parts_ms=1.0, store_ms=2.0, save_state_ms=3.0,
                  deliver_ms=4.0, decode_ms=5.0, download_ms=6.0) for i in range(100)]
    ring(Ring([dict(ev, t_ns=ev["t_ns"] + 9 * 10**9) for ev in busy] + many, first_seq=3000))
    want = {"replay_loop_ms_per_block": 20.0, "replay_store_ms_per_block": 6.0,
            "deliver_ms_per_block.program": 4.0, "block_decode_ms_per_block": 5.0,
            "block_download_ms": 6.0}.get(metric, BY_HAND[metric])
    assert reader.read(w, spec["params"]) == pytest.approx(want)
    # whose ring would it be: none alive, or two
    ring()
    assert reader.read(w, spec["params"]) is None
    ring(Ring(RING), Ring(RING))
    assert reader.read(w, spec["params"]) is None
    # a ring that holds other kinds only
    ring(Ring([ev for ev in RING if ev["kind"] == "loop.lag"]))
    assert reader.read(w, spec["params"]) is None


def test_fields_are_summed_over_the_events_that_carry_them_all():
    spec, reader = load("engine_launch_ms_per_block")
    events = [dispatch(2, "indexed", 0.5, pack=0.25, launch=1.0, fetch=2.0),
              dispatch(3, "indexed", 0.5),  # the CPU stand-in's: no such fields
              {**dispatch(4, "indexed", 0.5), "pack_ms": 7.0}]  # one of the two: skipped
    assert reader.read(window(1, events), spec["params"]) == 1.25
    assert reader.read(window(1, events[1:]), spec["params"]) is None
    assert reader.read(window(0, events), spec["params"]) is None


def test_a_real_recorders_spans_read_back_through_the_ring(monkeypatch):
    rec = tracing.FlightRecorder(size=256)
    monkeypatch.setattr(tracing, "live_recorders", lambda: [rec])
    t_open = time.monotonic_ns()
    lengths = []
    for height in (1, 2, 3):
        with rec.span("fastsync.block", id=height, decode_ms=float(height)) as span:
            span.lap("parts_ms")
            tracing.annotate(deliver_ms=2.0 * height)
        lengths.append(rec.events()[-1]["dur_ns"])
    rec.record("loop.busy", interval_ms=50.0, **{"p2p-conn_ms": 10.0, "fastsync_ms": 15.0})
    w = window(3)
    w.t_open_ns, w.t_close_ns = t_open, time.monotonic_ns()
    assert ring_sum_per_block.MIN_BLOCKS == 100  # three blocks count: the ring holds them all

    def read(metric):
        spec, reader = load(metric)
        return reader.read(w, spec["params"])

    assert read("replay_loop_ms_per_block") == pytest.approx(sum(lengths) / 3 / 1e6)
    assert read("block_decode_ms_per_block") == 2.0
    assert read("deliver_ms_per_block.program") == 4.0
    assert read("block_download_ms") is None  # no block carried it
    assert read("p2p_loop_share") == 20.0 and read("loop_idle_share") == 50.0
