"""Share (%) of the event loop's time, from the node's `loop.busy` events
(libs/loopprof.py: per-category task time since the last such event, and
`interval_ms`, how long that was): the named categories' time over the time
elapsed, or with `idle` what no category accounts for.  Read from the live
recorder's ring like ring_sum_per_block, over the part of the window the
ring still holds."""

from benchmarks.reducers.ring_sum_per_block import held_events


def read(window, params):
    inside = held_events(window)
    if inside is None:
        return None
    total = params["total"]
    events = [ev for ev in inside if ev["kind"] == params["kind"] and ev.get(total)]
    if not events:
        return None
    elapsed = sum(ev[total] for ev in events)
    if params.get("idle"):
        busy = sum(
            v for ev in events for k, v in ev.items()
            if k.endswith("_ms") and k != total and isinstance(v, (int, float))
        )
        return 100.0 * (1.0 - busy / elapsed)
    return 100.0 * sum(ev.get(f, 0.0) for ev in events for f in params["fields"]) / elapsed
