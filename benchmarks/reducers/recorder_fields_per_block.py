"""Sum of the named fields over the window's recorder events of one kind
(optionally only some dispatch paths) that carry every one of them, per
block applied.  Unlike recorder_sum_per_block it skips an event that lacks a
field (an older program's, a stand-in's) and reads nothing when none has
them all."""


def read(window, params):
    paths, fields = params.get("paths"), params["fields"]
    events = [
        ev for ev in window.events
        if ev["kind"] == params["kind"] and (paths is None or ev.get("path") in paths)
        and all(f in ev for f in fields)
    ]
    if not events or not window.blocks:
        return None
    return sum(ev[f] for ev in events for f in fields) / window.blocks
