"""Signatures verified over rows dispatched (%), device dispatches only.
A chunked dispatch reports its chunk size as `bucket` and runs as many
chunks as its signatures need."""


def read(window, params):
    useful = rows = 0
    for ev in window.events:
        if ev["kind"] != "verify.dispatch" or ev["path"] not in params["paths"]:
            continue
        useful += ev["n"]
        if ev["path"] == "chunked":
            rows += -(-ev["n"] // ev["bucket"]) * ev["bucket"]
        else:
            rows += ev["bucket"]
    return 100.0 * useful / rows if rows else None
