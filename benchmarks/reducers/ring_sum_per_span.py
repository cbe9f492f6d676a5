"""Sum over the window of the summed named fields of one kind of event,
per span of the metric file's `block_span` (a height committed: several
events of the kind may fall to one, as a height's vote_batch frames do),
read from the node's own flight recorder as ring_sum_per_block reads it
(`held_events`: the part of the window the ring still holds).  `scale`
converts a field's unit (`dur_ns` to ms: 1e-6).
"""

from benchmarks.reducers.ring_sum_per_block import held_events


def read(window, params):
    inside = held_events(window, params)
    if inside is None:
        return None
    fields, scale = params["fields"], params.get("scale", 1.0)
    blocks = sum(1 for ev in inside if ev["kind"] == params["block_span"])
    events = [ev for ev in inside if ev["kind"] == params["kind"]]
    if not blocks or not any(f in ev for ev in events for f in fields):
        return None
    return sum(ev.get(f, 0) for ev in events for f in fields) * scale / blocks
