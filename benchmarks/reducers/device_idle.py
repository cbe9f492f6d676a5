"""Share (%) of the traced window in which no operation ran on the device;
with several devices, of the busiest one."""


def read(window, params):
    trace = window.trace
    if trace is None or not trace.window_s:
        return None
    return 100.0 * (1.0 - max(trace.busy_s_by_device.values()) / trace.window_s)
