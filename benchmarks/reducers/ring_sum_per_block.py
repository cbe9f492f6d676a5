"""Mean over the blocks applied of the summed named fields of one kind of
span event (`fastsync.block`), read from the node's own flight recorder.

The harness hands its readers only `verify.*` events, so this one takes the
recorder of the process the rig started (`window.recorder`; where the
window was handed none, the process's one live recorder) and reads its ring
after the window: what it still holds of the window is a suffix (8192
events; about half of a hub window).  A suffix of fewer than MIN_BLOCKS blocks reads nothing, unless the
ring still holds the whole window.  `scale` converts a field's unit
(`dur_ns` to ms: 1e-6).
"""

MIN_BLOCKS = 100
BLOCK_SPAN = "fastsync.block"


def the_one_alive():
    """The process's one live recorder, or None: none or several (whose
    would it be), or a program without `live_recorders`."""
    try:
        from tendermint_tpu.libs import tracing
    except ImportError:
        return None
    live = getattr(tracing, "live_recorders", None)
    recorders = live() if live is not None else []
    return recorders[0] if len(recorders) == 1 else None


def held_events(window):
    """The events inside the window that the ring of the window's recorder
    (else of the_one_alive) still holds, oldest first, or None: no
    recorder, nothing of the window left in the ring, or too little of it
    to stand for the window."""
    recorder = getattr(window, "recorder", None) or the_one_alive()
    if recorder is None:
        return None
    ring = recorder.events()
    inside = [ev for ev in ring if window.t_open_ns < ev["t_ns"] <= window.t_close_ns]
    if not inside:
        return None
    whole = ring[0]["t_ns"] <= window.t_open_ns or ring[0]["seq"] == 0
    blocks = sum(1 for ev in inside if ev["kind"] == BLOCK_SPAN)
    if not whole and blocks < MIN_BLOCKS:
        return None
    return inside


def read(window, params):
    inside = held_events(window)
    if inside is None:
        return None
    fields, scale = params["fields"], params.get("scale", 1.0)
    events = [ev for ev in inside if ev["kind"] == params["kind"]]
    if not any(f in ev for ev in events for f in fields):
        return None
    return sum(ev.get(f, 0) for ev in events for f in fields) * scale / len(events)
