"""The reduction from a profiler trace to numbers, on a hand-built trace:
busy/idle union, per-kernel time by pattern file, gap attribution, and the
readers that turn them into per-layer metrics.
"""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, reference, trace  # noqa: E402
from benchmarks.reducers import (  # noqa: E402
    device_idle, kernel_ms_per_block, kernel_roofline, useful_rows,
)

MS = 1_000_000
DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"
HOST = "/host:CPU"
ANCHOR_MONO = 50_000 * MS  # the host's monotonic clock when the anchor was written
ANCHOR_TRACE = 7 * MS  # where the anchor sits on the trace's own clock
SHIFT = ANCHOR_MONO - ANCHOR_TRACE


def at(ms):
    """A time on the trace's clock for a time (ms) after the window opens."""
    return ms * MS + T_OPEN - SHIFT


T_OPEN = ANCHOR_MONO + 100 * MS
T_CLOSE = T_OPEN + 100 * MS

# Device 0: two kernel executions of 10 ms (their ops nested inside), one
# copy of 5 ms, one kernel straddling the window's end (4 ms inside).
# Device 1: one kernel execution of 10 ms.
HAND_TRACE = [
    (HOST, "python", trace.ANCHOR, ANCHOR_TRACE, 1),
    (DEV0, "XLA Modules", "jit_run(1234)", at(10), 10 * MS),
    (DEV0, "XLA Ops", "gather.1", at(10), 2 * MS),
    (DEV0, "XLA Ops", "ladder_kernel", at(12), 8 * MS),
    (DEV0, "XLA Modules", "jit_run(1234)", at(40), 10 * MS),
    (DEV0, "XLA Ops", "gather.1", at(40), 2 * MS),
    (DEV0, "XLA Ops", "ladder_kernel", at(42), 8 * MS),
    (DEV0, "XLA Modules", "jit_copy(7)", at(60), 5 * MS),
    (DEV0, "XLA Ops", "copy.3", at(60), 5 * MS),
    (DEV0, "XLA Modules", "jit_run(1234)", at(96), 10 * MS),
    (DEV0, "XLA Ops", "ladder_kernel", at(96), 10 * MS),
    (DEV0, "XLA Ops", "before_the_window", at(-20), 5 * MS),
    (DEV1, "XLA Modules", "jit_run(1234)", at(10), 10 * MS),
    (DEV1, "XLA Ops", "ladder_kernel", at(10), 10 * MS),
]

PATTERNS = [{"name": "indexed_run", "line": "XLA Modules", "regex": re.compile(r"^jit_run(\(|$)")}]


def summary():
    return trace.summarize_events(HAND_TRACE, ANCHOR_MONO, T_OPEN, T_CLOSE, PATTERNS)


def test_interval_arithmetic():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert trace.clip([(0, 4), (5, 10)], 3, 7) == [(3, 4), (5, 7)]
    assert trace.total([(3, 4), (5, 7)]) == 3
    assert trace.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert trace.gaps([], 0, 10) == [(0, 10)]
    assert trace.overlap((4, 9), [(0, 5), (8, 20)]) == 2


def test_busy_is_the_union_of_device_ops_inside_the_window():
    s = summary()
    assert s.window_s == pytest.approx(0.100)
    # device 0: 10 + 10 + 5 + 4 ms; device 1: 10 ms; the mean is reported
    assert s.busy_s_by_device == {DEV0: pytest.approx(0.029), DEV1: pytest.approx(0.010)}
    assert s.busy_s == pytest.approx(0.0195)
    # the idle gaps are the busiest device's
    assert [(a - T_OPEN, b - T_OPEN) for a, b in s.idle_gaps] == [
        (0, 10 * MS), (20 * MS, 40 * MS), (50 * MS, 60 * MS), (65 * MS, 96 * MS),
    ]


def test_kernel_time_by_pattern_and_op_time_by_name():
    s = summary()
    assert s.kernel_events == 4
    assert s.kernel_seconds == {"indexed_run": pytest.approx(0.034)}  # 10 + 10 + 4 + 10 ms
    assert s.op_seconds["ladder_kernel"] == pytest.approx(0.030)  # 8 + 8 + 4 + 10 ms
    assert s.op_seconds["gather.1"] == pytest.approx(0.004)
    assert "before_the_window" not in s.op_seconds
    assert "jit_run(1234)" not in s.op_seconds  # a module is not an op


def test_a_trace_without_an_anchor_or_a_device_is_no_trace():
    with pytest.raises(ValueError):
        trace.summarize_events(HAND_TRACE[1:], ANCHOR_MONO, T_OPEN, T_CLOSE, PATTERNS)
    assert trace.summarize_events(HAND_TRACE[:1], ANCHOR_MONO, T_OPEN, T_CLOSE, PATTERNS) is None


def window(**kw):
    cell = harness.Cell("toy.replay", 1, {}, {}, 0, [], [])
    base = dict(
        cell=cell, seconds=0.1, t_open_ns=T_OPEN, t_close_ns=T_CLOSE,
        block_times=[T_OPEN / 1e9 + 0.03, T_OPEN / 1e9 + 0.07],
        block_heights=[5, 6], events=[], deliver_spans=[], buffered=[3, 2],
        device_kind="TPU v5 lite",
    )
    base.update(kw)
    return harness.Window(**base)


def dispatch(end_ms, took_ms, n=100, bucket=512, path="indexed"):
    return {
        "kind": "verify.dispatch", "t_ns": T_OPEN + end_ms * MS, "n": n, "bucket": bucket,
        "path": path, "host_prep_ms": 1.0, "device_ms": took_ms - 1.0, "shards": 1,
    }


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    w = window(
        events=[dispatch(22, 14), dispatch(52, 14)],  # engine calls 8..22 and 38..52 ms
        deliver_spans=[(5, T_OPEN + 25 * MS, T_OPEN + 30 * MS)],
    )
    out = summary().breakdown(w)
    assert out["device_ops"][0] == ["ladder_kernel", pytest.approx(0.030)]
    gaps = dict((name, secs) for name, secs in out["idle_gaps"])
    # idle 0..10, 20..40, 50..60, 65..96: the engine calls cover 8..10,
    # 20..22, 38..40 and 50..52 of it, deliver 25..30, the rest is the loop
    assert gaps[trace.ENGINE_CALL] == pytest.approx(0.008)
    assert gaps[trace.DELIVER] == pytest.approx(0.005)
    assert gaps[trace.OTHER] == pytest.approx(0.071 - 0.013)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_readers_of_the_trace():
    w = window(events=[dispatch(22, 14, n=166), dispatch(52, 14, n=166)], trace=summary())
    assert device_idle.read(w, {}) == pytest.approx(71.0)  # the busiest device: 29 of 100 ms
    assert kernel_ms_per_block.read(w, {}) == pytest.approx(17.0)  # 34 ms over 2 blocks
    share = kernel_roofline.read(w, {"paths": ["indexed"], "ops_peak": "int8_ops_per_s"})
    least_s = 332 * reference.verify_ops_per_signature() / 393e12
    assert share == pytest.approx(100.0 * least_s / 0.034)
    assert 0 < share < 100


def test_readers_find_nothing_without_a_trace_or_a_kernel_event():
    assert device_idle.read(window(), {}) is None
    assert kernel_ms_per_block.read(window(), {}) is None
    none_matched = trace.summarize_events(HAND_TRACE, ANCHOR_MONO, T_OPEN, T_CLOSE, [])
    w = window(events=[dispatch(22, 14)], trace=none_matched)
    assert kernel_roofline.read(w, {"paths": ["indexed"], "ops_peak": "int8_ops_per_s"}) is None
    assert kernel_ms_per_block.read(w, {}) is None


def test_an_unknown_device_has_no_peak():
    w = window(events=[dispatch(22, 14)], trace=summary(), device_kind="TPU v99")
    with pytest.raises(KeyError):
        kernel_roofline.read(w, {"paths": ["indexed"], "ops_peak": "int8_ops_per_s"})


def test_useful_rows_counts_a_chunked_dispatch_by_its_chunks():
    w = window(events=[
        dispatch(10, 5, n=166, bucket=512),
        dispatch(20, 5, n=9498, bucket=2048, path="chunked"),  # 5 chunks of 2048
        dispatch(30, 5, n=40, bucket=0, path="host"),
    ])
    paths = {"paths": ["indexed", "chunked"]}
    assert useful_rows.read(w, paths) == pytest.approx(100.0 * (166 + 9498) / (512 + 10240))
    assert useful_rows.read(window(), paths) is None


def test_kernel_pattern_files_name_the_programs_jitted_entry_points():
    """One file per kernel; each pattern matches the module name XLA gives
    the jitted function it stands for, and no other's."""
    patterns = {p["name"]: p for p in trace.load_kernel_patterns()}
    assert set(patterns) == {"indexed_run", "ladder_flat", "straus_flat"}
    names = {
        "indexed_run": "jit_run(5163829213)",
        "ladder_flat": "jit_verify_prepared_pallas(77)",
        "straus_flat": "jit_verify_prepared",
    }
    for kernel, module in names.items():
        hits = [k for k, p in patterns.items() if p["regex"].search(module)]
        assert hits == [kernel], (module, hits)


def test_inventory_lists_the_costliest_names_per_line():
    inv = trace.inventory(HAND_TRACE, top=2)
    ops = inv[f"{DEV0} | XLA Ops"]
    assert ops[0][0] == "ladder_kernel" and ops[0][1] == 3
    assert len(ops) == 2
    json.dumps(inv)
