"""Device time (ms) of the verify kernels' trace events, summed over the
kernels' pattern files and the devices, per block applied."""


def read(window, params):
    trace = window.trace
    if trace is None or not trace.kernel_events or not window.blocks:
        return None
    return sum(trace.kernel_seconds.values()) * 1e3 / window.blocks
