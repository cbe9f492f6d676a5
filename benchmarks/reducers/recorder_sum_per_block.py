"""Sum of the named fields over the window's recorder events of one kind
(optionally only some dispatch paths), per block applied."""


def read(window, params):
    paths = params.get("paths")
    events = [
        ev for ev in window.events
        if ev["kind"] == params["kind"] and (paths is None or ev.get("path") in paths)
    ]
    if not events or not window.blocks:
        return None
    return sum(ev[f] for ev in events for f in params["fields"]) / window.blocks
