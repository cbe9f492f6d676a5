"""From a profiler trace to numbers: device busy and idle time, time per
kernel by name pattern, and the idle gaps named by what the host was doing.

The profiler writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`;
`jax.profiler.ProfileData` reads it.  Its timestamps count from the start
of the profile, so `start()` leaves an annotation in the host's plane at a
known `time.monotonic_ns()`; that anchor puts the trace, the flight
recorder's events and the harness's own spans on one clock.

The reduction works on plain tuples (plane, line, name, start_ns, dur_ns),
so the tests feed it a hand-built trace.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

ANCHOR = "bench.anchor"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"  # one event per executed HLO op or custom call
KERNEL_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kernels")

Event = Tuple[str, str, str, int, int]  # plane, line, name, start_ns, dur_ns
Interval = Tuple[int, int]

def start(trace_dir: str) -> int:
    """Start the profiler (device and annotation tracing; no Python call
    tracer, which would slow the replay loop and swell the file).  Returns
    the host's `time.monotonic_ns()` at the anchor annotation, which
    `summarize` needs to place the trace in time."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    anchor_mono_ns = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(ANCHOR):
        pass
    return anchor_mono_ns


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load_xplane(trace_dir: str) -> List[Event]:
    import jax

    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events: List[Event] = []
    for path in files:
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    events.append(
                        (plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns))
                    )
    return events


def load_kernel_patterns(kernel_dir: str = KERNEL_DIR) -> List[dict]:
    """One file per kernel: {"name", "line", "pattern"}; an event on a device
    plane counts for the kernel when its line is `line` and its name matches
    `pattern`."""
    out = []
    for path in sorted(glob.glob(os.path.join(kernel_dir, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        spec["regex"] = re.compile(spec["pattern"])
        out.append(spec)
    return out


def op_name(event_name: str) -> str:
    """The HLO instruction's own name: the trace gives the whole instruction
    (`%fusion.3 = s32[512,4,20]{...} fusion(...)`)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of disjoint sorted `busy` inside [lo, hi]."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(gap: Interval, spans: Sequence[Interval]) -> int:
    """Length of `gap` covered by disjoint sorted `spans`."""
    return total(clip(spans, gap[0], gap[1]))


@dataclass
class TraceSummary:
    """One traced window, on the host's monotonic clock (ns)."""

    window_s: float
    busy_s: float  # union of device-op intervals, averaged over the devices
    busy_s_by_device: Dict[str, float]
    idle_gaps: List[Interval]  # of the busiest device
    op_seconds: Dict[str, float]  # device-op name -> seconds, summed over devices
    kernel_seconds: Dict[str, float]  # kernel (pattern file) name -> seconds, summed over devices
    kernel_events: int = 0
    kernel_intervals: List[Interval] = field(default_factory=list)  # all devices, merged

    def breakdown(self, window) -> dict:
        """The ten device operations that took most time, and the idle time
        of the busiest device by what the host was doing meanwhile."""
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        return {
            "device_ops": [[name, secs] for name, secs in ops],
            "idle_gaps": attribute_gaps(self.idle_gaps, host_activity(window))[:10],
        }


def summarize_events(
    events: Sequence[Event], anchor_mono_ns: int, t_open_ns: int, t_close_ns: int,
    kernel_patterns: Sequence[dict],
) -> Optional[TraceSummary]:
    """Reduce a trace to the window [t_open_ns, t_close_ns] (monotonic ns).
    None when the trace holds no device plane (a CPU run)."""
    anchors = [ev for ev in events if ev[2] == ANCHOR]
    if not anchors:
        raise ValueError("the trace holds no anchor annotation: it cannot be placed in time")
    shift = anchor_mono_ns - anchors[0][3]
    by_device: Dict[str, List[Event]] = {}
    for ev in events:
        if DEVICE_PLANE.match(ev[0]):
            by_device.setdefault(ev[0], []).append(ev)
    if not by_device:
        return None
    busy_by_device, unions = {}, {}
    op_seconds: Dict[str, float] = {}
    kernel_seconds: Dict[str, float] = {spec["name"]: 0.0 for spec in kernel_patterns}
    kernel_events = 0
    kernel_intervals: List[Interval] = []
    for device, evs in sorted(by_device.items()):
        spans = []
        for _, line, name, start, dur in evs:
            a, b = start + shift, start + shift + dur
            if b <= t_open_ns or a >= t_close_ns:
                continue
            inside = (min(b, t_close_ns) - max(a, t_open_ns)) / 1e9
            if line == OPS_LINE:
                spans.append((a, b))
                op = op_name(name)
                op_seconds[op] = op_seconds.get(op, 0.0) + inside
            for spec in kernel_patterns:
                if spec["line"] == line and spec["regex"].search(name):
                    kernel_seconds[spec["name"]] += inside
                    kernel_events += 1
                    kernel_intervals.append((a, b))
                    break
        unions[device] = union(clip(spans, t_open_ns, t_close_ns))
        busy_by_device[device] = total(unions[device]) / 1e9
    busiest = max(busy_by_device, key=busy_by_device.get)
    return TraceSummary(
        window_s=(t_close_ns - t_open_ns) / 1e9,
        busy_s=sum(busy_by_device.values()) / len(busy_by_device),
        busy_s_by_device=busy_by_device,
        idle_gaps=gaps(unions[busiest], t_open_ns, t_close_ns),
        op_seconds=op_seconds, kernel_seconds=kernel_seconds, kernel_events=kernel_events,
        kernel_intervals=union(clip(kernel_intervals, t_open_ns, t_close_ns)),
    )


def summarize(
    trace_dir: str, anchor_mono_ns: int, t_open_ns: int, t_close_ns: int,
    names_out: Optional[str] = None,
) -> Optional[TraceSummary]:
    events = load_xplane(trace_dir)
    if names_out is not None:
        with open(names_out, "w") as f:
            json.dump(inventory(events), f, indent=1)
    return summarize_events(
        events, anchor_mono_ns, t_open_ns, t_close_ns, load_kernel_patterns()
    )


def inventory(events: Sequence[Event], top: int = 40) -> Dict[str, list]:
    """Per plane and line, the event names that took most time, as
    [name, events, seconds]: what a new kernel pattern file is written
    against."""
    acc: Dict[str, Dict[str, list]] = {}
    for plane, line, name, _, dur in events:
        slot = acc.setdefault(f"{plane} | {line}", {}).setdefault(name, [0, 0])
        slot[0] += 1
        slot[1] += dur
    return {
        key: [[name, n, ns / 1e9] for name, (n, ns) in
              sorted(names.items(), key=lambda kv: -kv[1][1])[:top]]
        for key, names in sorted(acc.items())
    }


def host_activity(window) -> Dict[str, List[Interval]]:
    """What the host was doing, as disjoint intervals by name, from the
    flight recorder's events and the harness's spans: a verify.dispatch
    event is written when the engine call returns and carries how long it
    took; a deliver span runs from begin_block to commit's return."""
    dispatch = []
    for ev in window.events:
        if ev["kind"] == "verify.dispatch":
            took = int((ev["host_prep_ms"] + ev["device_ms"]) * 1e6)
            dispatch.append((ev["t_ns"] - took, ev["t_ns"]))
    return {
        ENGINE_CALL: union(dispatch),
        DELIVER: union((a, b) for _, a, b in window.deliver_spans),
    }


ENGINE_CALL = "engine call (verify.dispatch: prep, transfer, fetch)"
DELIVER = "abci deliver (begin_block..commit)"
OTHER = "replay loop outside engine and deliver"


def attribute_gaps(idle: Sequence[Interval], spans: Dict[str, List[Interval]]) -> List[list]:
    """Seconds of device idle time by what the host was doing, most first.
    What no span covers is the rest of the replay loop: block decode, part
    sets, store, validation, state save, the event loop."""
    seconds = {name: 0 for name in spans}
    seconds[OTHER] = 0
    for gap in idle:
        covered = 0
        for name, ivs in spans.items():
            part = overlap(gap, ivs)
            seconds[name] += part
            covered += part
        seconds[OTHER] += max(0, gap[1] - gap[0] - covered)
    return sorted(([n, s / 1e9] for n, s in seconds.items() if s), key=lambda kv: -kv[1])
