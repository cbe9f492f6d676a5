"""Useful rows over rows dispatched (%) in the least-filled shard of each
device dispatch, summed over the window: `shard_n` is the signatures each
shard held, and a dispatch's rows are split evenly over its shards, so the
padding lands on the last.  A chunked dispatch reports its chunk size as
`bucket` and runs as many chunks as its signatures need (useful_rows.py).
An event without `shard_n` (an older program's, a stand-in's) is skipped,
and nothing is read when none has it."""


def read(window, params):
    useful = rows = 0
    for ev in window.events:
        if ev["kind"] != "verify.dispatch" or ev["path"] not in params["paths"]:
            continue
        shard_n = ev.get("shard_n")
        if not shard_n:
            continue
        chunks = -(-ev["n"] // ev["bucket"]) if ev["path"] == "chunked" else 1
        useful += min(shard_n)
        rows += chunks * ev["bucket"] / len(shard_n)
    return 100.0 * useful / rows if rows else None
