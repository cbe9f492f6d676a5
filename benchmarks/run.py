#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to run without a TPU, or with fewer chips than the cell asks for
(exit 2, nothing on stdout).  Outside a checkout of the program, for a
workload BENCHMARK.json does not have, and for a traffic file whose `kind`
has no rig under benchmarks/rigs/, it exits 3.
Every run that got as far as the chip prints a result line and exits 0: one
whose answers were wrong, or that broke on the way, says `"correct": false`
there, with the numbers that say why, where the driver's record keeps them.
The last stdout line is the result object: `correct`, `attempted`,
`failed`, `metrics`, `device`, `breakdown` in a traced run and, last,
`checks`: every number compared for `correct` beside its limit.  The checks
are also the last lines on stderr.  The stdout line before the result is
`{"context": ...}`: what the run decided and saw (the engine's path and
bucket, the start-up probes' readings, compile seconds), for the reader and
never a metric.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
import traceback

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def print_result(result: dict) -> None:
    """The checks as the last lines on stderr, the result as the last line
    on stdout, the context on the line before it."""
    sys.stderr.flush()
    print(json.dumps({"context": result.pop("context", None)}))
    result["checks"] = result.pop("checks")  # comes last on the line
    for name, check in result["checks"].items():
        print(f"[check] {name} = {check['value']} (limit {check['limit']})", file=sys.stderr)
    print(f"[check] correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def failed_result(reason: str, device: dict) -> dict:
    """The line of a run that broke before it could measure."""
    return {
        "correct": False, "attempted": 0, "failed": 1, "metrics": {}, "device": device,
        "context": {"failure": reason},
        "checks": {"run_failed": {"value": 1, "limit": 0}},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", action="append", default=[],
                    help="plant a fault under the timed path (the control; never a benchmark run)")
    args = ap.parse_args(argv)

    try:
        import tendermint_tpu  # noqa: F401
    except ImportError as exc:
        print(f"run.py: run it from a checkout of the program: {exc}", file=sys.stderr)
        return 3
    from benchmarks import harness

    try:
        cell = harness.load_cell(args.workload)
        harness.find_rig(cell.kind)  # an unknown traffic kind is refused here, by name
    except (harness.HarnessFailure, OSError, KeyError, StopIteration) as exc:
        print(f"run.py: {exc!r}", file=sys.stderr)
        return 3

    import jax

    n_dev = jax.device_count()
    if jax.default_backend() != "tpu" or n_dev < cell.chips:
        print(
            f"run.py: {args.workload} needs {cell.chips} TPU chip(s); JAX found backend "
            f"{jax.default_backend()!r} with {n_dev} device(s) — refusing to run",
            file=sys.stderr,
        )
        return 2
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": n_dev,
              "memory_peak_bytes": 0}
    from tendermint_tpu.libs.log import parse_log_level, setup as log_setup

    # the node's own logging, at its default level, as `cli node` sets it up
    from tendermint_tpu.config import BaseConfig

    log_setup(module_levels=parse_log_level(BaseConfig().log_level))
    try:
        result = asyncio.run(harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), T_START, faults=args.fault
        ))
    except Exception as exc:
        if not isinstance(exc, harness.HarnessFailure):
            traceback.print_exc()
        print(f"run.py: FAILED: {exc}", file=sys.stderr, flush=True)
        print_result(failed_result(str(exc), device))
        # a compile thread may be wedged; do not wait on it to report failure
        sys.stdout.flush()
        os._exit(0)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
