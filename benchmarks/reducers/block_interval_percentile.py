"""A percentile (ms) of the time between consecutive blocks applied, over
every block of the window; heights that arrived together under one stamp
(a light client's answer) are one block here, so at a gap over 1 it reads
the time between answers and not the 0s inside one."""

from benchmarks.harness import block_intervals_ms, percentile


def read(window, params):
    intervals = block_intervals_ms(window)
    return percentile(intervals, params["q"]) if intervals else None
