"""How unevenly the devices were busy in the traced window (%): the busiest
device's busy time less the least busy one's, over the busiest's.  Nothing
to read on one device, or where no device did anything."""


def read(window, params):
    trace = window.trace
    if trace is None or len(trace.busy_s_by_device) < 2:
        return None
    busy = trace.busy_s_by_device.values()
    if not max(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
