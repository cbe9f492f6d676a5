"""Share (%) of the chip's roofline the verify kernels reached: the least
time the chip could take for the signatures verified, over the kernels'
device time.  The work is counted from the signatures (a plain
double-scalar verification each, benchmarks/reference.py), not from the
kernel that ran, so padding rows and a costlier algorithm both lower it."""

import json
import os

from benchmarks import reference

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


def peak(device_kind: str) -> dict:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def read(window, params):
    trace = window.trace
    if trace is None or not trace.kernel_events:
        return None
    kernel_s = sum(trace.kernel_seconds.values())
    sigs = sum(
        ev["n"] for ev in window.events
        if ev["kind"] == "verify.dispatch" and ev["path"] in params["paths"]
    )
    if not sigs or not kernel_s:
        return None
    p = peak(window.device_kind)
    least_s = max(
        sigs * reference.verify_ops_per_signature() / p[params["ops_peak"]],
        sigs * reference.verify_bytes_per_signature() / p["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / kernel_s
