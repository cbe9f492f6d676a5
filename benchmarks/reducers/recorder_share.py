"""Share (%) of the window's recorder events of one kind whose boolean
field is set."""


def read(window, params):
    events = [ev for ev in window.events if ev["kind"] == params["kind"]]
    if not events:
        return None
    return 100.0 * sum(1 for ev in events if ev[params["field"]]) / len(events)
