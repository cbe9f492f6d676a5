"""A percentile (ms) of the time between consecutive blocks applied, over
every block of the window."""

from benchmarks.harness import block_intervals_ms, percentile


def read(window, params):
    if not window.blocks:
        return None
    return percentile(block_intervals_ms(window), params["q"])
