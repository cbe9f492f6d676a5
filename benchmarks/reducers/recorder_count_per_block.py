"""Recorder events of one kind (optionally only some dispatch paths) per
block applied."""


def read(window, params):
    paths = params.get("paths")
    count = sum(
        1 for ev in window.events
        if ev["kind"] == params["kind"] and (paths is None or ev.get("path") in paths)
    )
    if not count or not window.blocks:
        return None
    return count / window.blocks
