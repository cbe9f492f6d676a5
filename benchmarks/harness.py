"""The rig: one process holds the chip and runs the process under test.

A traffic file states its `kind` (absent: `replay`).  `run_cell` finds the
rig by it: `replay` is `run_replay`, below; any other kind is the module
`benchmarks/rigs/<kind>.py`, whose `run_cell` builds its run from the parts
here (the chain, the engine's watch and warm-up, the stamps and the window,
the tampers, the trace, the result's assembly) and returns the same result
object.  A later PR brings a rig as new files and edits nothing.

`replay`: the node under test joins a generated chain by fast sync from
source peers in child processes.

Set-up (all of it counted in `setup_s`): generate or load the chain (a
child process, while this one goes on), start the node with the default
[tpu] config and one persistent peer that knows the chain's length and
holds no block (see Sources.start_tip), warm the verify engine on the
chain's own validator set through `ValidatorSet.verify_commit` until the
flight recorder shows a table path for this committee's size, start the
sources, dial them, let `warm_in_blocks` apply, open the window.

After the window: stop the sources, read the replayed state back over RPC,
and hold it and the engine's verdicts to the plain reference
(benchmarks/reference.py).  Every number compared is exact, so its limit is
0.

A configuration whose `validator_set_changes` declares changes (see
chain.set_changes) is held to the reference's set for each height, reports
the validator sets it replayed, and may build a table where the chain
brings a new membership (window_rules); one without is held as ever.

Copied from chip_smoke.py (the yardstick may not import what later PRs may
change): the rules on engine events, EngineWatch, until_device, the commit
tampers and engine_first_bad.
"""

from __future__ import annotations

import asyncio
import dataclasses
import gc
import glob
import importlib
import json
import os
import random
import re
import shutil
import struct
import subprocess
import sys
import tempfile
import time
from typing import Awaitable, Callable, Dict, List, Optional, Sequence

import numpy as np

from benchmarks import chain as chainlib
from benchmarks import reference

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, "cache")
OUT_DIR = os.path.join(BENCH_DIR, "out")
# Room for one cell's six seeds at the largest chain there is (0.5 GB): a
# chain pruned between two runs of its seed is written again, and the host
# keeps count of what is written.
CACHE_KEEP_BYTES = 6 * 2**30
# The program's scheduler keeps at most this many block requests out per
# peer; a node that close to its sources' tip is no longer replaying at its
# own pace.
REQUESTS_PER_PEER = 20

# verify.dispatch `path` values: through a device-resident PubkeyTable (the
# indexed hook), the flat device path, and the host tier
TABLE_PATHS = ("indexed", "chunked")
DEVICE_PATHS = TABLE_PATHS + ("device",)
HOST_PATHS = ("host", "host-cold")
# engine events that mean a table was built (on a miss, or ahead of one by
# the node's watch on validator-set updates), and that a compile ran too
TABLE_BUILD_EVENTS = ("verify.table_build", "verify.table_rebuild")
BUILD_EVENTS = ("verify.bucket_compile",) + TABLE_BUILD_EVENTS
# What a new membership may cost a window.  Table builds: the program has
# two builders (the miss in TableCache.verify_indexed, the watch's rebuild),
# and whether both fire is a reading, not a failed run.  Dispatches on the
# flat path while the table builds: sound runs read at most 1.0 a membership
# and a table never built two a block from there on (PERF.md section 2).
BUILDS_PER_MEMBERSHIP = 2
FLAT_DISPATCHES_PER_MEMBERSHIP = 10

WARM_DEADLINE_S = 900.0  # the engine's allowance to reach the device, compiles included
CHAIN_DEADLINE_S = 600.0
WARM_IN_DEADLINE_S = 150.0
NODE_STOP_DEADLINE_S = 30.0
# replayed commits the serial reference re-verifies, the last one among them:
# as many as hold this many signatures between them, within these bounds
SAMPLE_SIGNATURES = 40_000
SAMPLE_COMMITS = (4, 64)
SAMPLE_WRITES = 32  # replayed writes read back over RPC
POLL_PERIOD_S = 0.25  # the recorder's ring is read this often through the window
REPLAY = "replay"  # the kind of a traffic file that states none
_KIND_NAME = re.compile(r"^[a-z][a-z0-9_]*$")


class HarnessFailure(Exception):
    """The run could not be made, or broke one of the rig's rules."""


_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# cells, from the data files
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    heights: int
    end_to_end: List[dict]  # the BENCHMARK.json entries this cell reports
    per_layer: List[dict]

    @property
    def rotating(self) -> bool:
        """Whether the configuration's validator set changes along the chain."""
        return bool(chainlib.set_changes(self.config))

    @property
    def kind(self) -> str:
        """The traffic's kind, which names the rig: `replay` where the file
        states none."""
        return self.traffic.get("kind", REPLAY)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def chain_heights(config: dict, traffic: dict) -> int:
    """Chain length for a configuration under a traffic mix: the traffic
    file's entry for the configuration, else the configuration file's entry
    for the traffic (so that a later configuration brings its own)."""
    by_config = traffic.get("heights", {})
    if config["name"] in by_config:
        return int(by_config[config["name"]])
    by_traffic = config.get("traffic_heights", {})
    if traffic["name"] in by_traffic:
        return int(by_traffic[traffic["name"]])
    raise HarnessFailure(
        f"no chain length for {config['name']} under {traffic['name']}: give it in "
        "the traffic file's `heights` or the configuration's `traffic_heights`"
    )


def warm_in_blocks(config: dict, traffic: dict) -> int:
    """Blocks applied per source dialed before the window may open: the
    traffic file's number, or its entry for the configuration, else the
    configuration file's entry for the traffic (as chain_heights)."""
    by_config = traffic["warm_in_blocks"]
    if not isinstance(by_config, dict):
        return int(by_config)
    if config["name"] in by_config:
        return int(by_config[config["name"]])
    by_traffic = config.get("traffic_warm_in_blocks", {})
    if traffic["name"] in by_traffic:
        return int(by_traffic[traffic["name"]])
    raise HarnessFailure(
        f"no warm-in for {config['name']} under {traffic['name']}: give it in the traffic "
        "file's `warm_in_blocks` or the configuration's `traffic_warm_in_blocks`"
    )


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise HarnessFailure(f"BENCHMARK.json has no workload {workload!r}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _load_json(os.path.join(root, conf["file"]))
    traffic = _load_json(
        os.path.join(root, os.path.basename(BENCH_DIR), "traffic", entry["traffic"] + ".json")
    )

    def reported(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if reported(m)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"] if reported(m) and m["moves"] in e2e_names]
    return Cell(
        name=workload, chips=entry["chips"], config=config, traffic=traffic,
        heights=chain_heights(config, traffic), end_to_end=end_to_end, per_layer=per_layer,
    )


def find_rig(kind: str) -> Callable:
    """The `run_cell` of a traffic kind: `run_replay` here, else that of the
    module `benchmarks/rigs/<kind>.py`."""
    if kind == REPLAY:
        return run_replay
    module = f"benchmarks.rigs.{kind}"
    missing = HarnessFailure(
        f"no rig for traffic kind {kind!r}: there is no benchmarks/rigs/{kind}.py"
    )
    if not _KIND_NAME.match(kind):
        raise missing
    try:
        return importlib.import_module(module).run_cell
    except ModuleNotFoundError as exc:
        if exc.name != module:  # the rig is there, and what it imports is not
            raise
        raise missing from None


# ---------------------------------------------------------------------------
# the rules on engine events (chip_smoke.py's)
# ---------------------------------------------------------------------------


def engine_failures(events: Sequence[dict], min_device_batch: int, warm: bool) -> List[str]:
    """What a slice of flight-recorder events holds that a run refuses: any
    engine event reporting ok=False, and — once `warm` — any host-tier
    dispatch of a batch the device should take."""
    problems = []
    for ev in events:
        if ev.get("ok") is False:
            problems.append(f"{ev['kind']} reported ok=False: {ev.get('error') or ev}")
        if (
            warm
            and ev["kind"] == "verify.dispatch"
            and ev["path"] in HOST_PATHS
            and ev["n"] >= min_device_batch
        ):
            problems.append(
                f"batch of {ev['n']} served on path={ev['path']} after the engine was warm"
            )
    return problems


def window_failures(
    events: Sequence[dict], min_device_batch: int, membership_changes: Optional[int] = None,
) -> Dict[str, int]:
    """The counts a measured window must keep at 0: failed engine events,
    engine-sized batches on the host tier, and compiles or table builds that
    ran inside it.  Where the chain's validator set changes
    (`membership_changes` is how many new memberships the window met), the
    table builds the chain asks for are not held against the run: what is
    counted is the builds beyond BUILDS_PER_MEMBERSHIP for each, and every
    compile; and the dispatches on the flat device path beyond
    FLAT_DISPATCHES_PER_MEMBERSHIP for each (a membership whose table never
    comes rides that path for good)."""
    checks = {
        "engine_errors": sum(1 for ev in events if ev.get("ok") is False),
        "host_tier_dispatches": sum(
            1 for ev in events
            if ev["kind"] == "verify.dispatch" and ev["path"] in HOST_PATHS
            and ev["n"] >= min_device_batch
        ),
    }
    builds = sum(1 for ev in events if ev["kind"] in BUILD_EVENTS)
    if membership_changes is None:
        checks["builds_in_window"] = builds
    else:
        tables = sum(1 for ev in events if ev["kind"] in TABLE_BUILD_EVENTS)
        checks["builds_beyond_membership_changes"] = builds - tables + max(
            0, tables - BUILDS_PER_MEMBERSHIP * membership_changes
        )
        flat = sum(
            1 for ev in events if ev["kind"] == "verify.dispatch" and ev["path"] == "device"
        )
        checks["flat_dispatches_beyond_membership_changes"] = max(
            0, flat - FLAT_DISPATCHES_PER_MEMBERSHIP * membership_changes
        )
    return checks


class EngineWatch:
    """Reads a node's flight recorder incrementally (its ring is small) and
    holds every verify.* event to engine_failures.  `cold` is set while the
    engine warms (host-tier dispatches are then by design)."""

    def __init__(self, recorder, min_device_batch: int):
        self.recorder = recorder
        self.min_device_batch = min_device_batch
        self.cold = True
        self.events: List[dict] = []
        self._next_seq = 0

    def poll(self) -> List[dict]:
        new = self.recorder.events(since=self._next_seq, kinds=["verify."])
        if new:
            self._next_seq = new[-1]["seq"] + 1
            self.events.extend(new)
            problems = engine_failures(new, self.min_device_batch, warm=not self.cold)
            if problems:
                raise HarnessFailure("; ".join(problems))
        return new


async def until_device(
    watch: EngineWatch, n: int, call: Callable[[], Awaitable[None]], deadline_s: float,
    paths: Sequence[str] = TABLE_PATHS, warm_calls: int = 2,
) -> dict:
    """Drive `call` (which checks its own answer against the reference and
    raises on a mismatch) until a verify.dispatch for a batch of n shows one
    of `paths`, then `warm_calls` more times with the warm rule on."""

    async def dispatched() -> List[dict]:
        await call()
        return [ev for ev in watch.poll() if ev["kind"] == "verify.dispatch" and ev["n"] == n]

    watch.poll()
    watch.cold = True
    t0 = time.monotonic()
    calls = 0
    while True:
        seen = await dispatched()
        calls += 1
        if seen and seen[-1]["path"] in paths:
            break
        if time.monotonic() - t0 > deadline_s:
            raise HarnessFailure(
                f"no dispatch of the {n}-signature batch on path {'|'.join(paths)} "
                f"within {deadline_s:.0f} s ({calls} calls, all answered correctly)"
            )
        await asyncio.sleep(0.5)
    cold_s = time.monotonic() - t0
    watch.cold = False
    for _ in range(warm_calls):
        seen = await dispatched() or seen
        if seen[-1]["path"] not in paths:
            raise HarnessFailure(f"left path {'|'.join(paths)} for {seen[-1]['path']} once warm")
    last = seen[-1]
    log(f"engine warm after {cold_s:.1f} s / {calls} cold calls: path={last['path']} "
        f"bucket={last['bucket']} shards={last['shards']}")
    return {"path": last["path"], "bucket": last["bucket"], "shards": last["shards"],
            "cold_calls": calls, "cold_s": round(cold_s, 1)}


async def settle(verifier, cache, watch: EngineWatch, deadline_s: float) -> None:
    """Wait for the background compiles of `verifier` (the BatchVerifier) and
    the table builds of `cache` (its TableCache) to land, so that none lands
    in the window."""
    t0 = time.monotonic()
    while verifier._compiling_buckets or cache._building:
        if time.monotonic() - t0 > deadline_s:
            raise HarnessFailure(
                f"background engine work still running after {deadline_s:.0f} s"
            )
        await asyncio.sleep(0.2)
    watch.poll()


# ---------------------------------------------------------------------------
# commits: the program's objects, the reference's view, the tampers
# ---------------------------------------------------------------------------


def commit_view(commit) -> reference.CommitView:
    """A program Commit as plain fields for the reference."""
    return reference.CommitView(
        height=commit.height, round=commit.round, block_hash=commit.block_id.hash,
        parts_total=commit.block_id.parts_header.total,
        parts_hash=commit.block_id.parts_header.hash,
        slots=[
            None if cs.is_absent() else (cs.timestamp_ns, cs.signature)
            for cs in commit.signatures
        ],
    )


def make_commit(chain_id: str, vset, secrets: Sequence[bytes], seed: int, absent_share: float):
    """A commit over a made-up block id at the committee's own size and
    absences, signed slot by slot: what the engine is warmed on."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey
    from tendermint_tpu.types import BlockID, Commit, CommitSig, PartSetHeader

    powers = np.asarray([v.voting_power for v in vset.validators], dtype=np.int64)
    present, stamps = chainlib.draw_votes(seed, 0, powers, absent_share)
    block_id = BlockID(b"\x05" * 32, PartSetHeader(1, b"\x06" * 32))
    head, tail = reference.vote_sign_parts(chain_id, 1, 0, block_id.hash, 1, b"\x06" * 32)
    sigs = []
    for i, v in enumerate(vset.validators):
        if not present[i]:
            sigs.append(CommitSig.absent())
            continue
        ts = int(stamps[i])
        key = Ed25519PrivateKey.from_private_bytes(secrets[i])
        sigs.append(CommitSig.for_block(
            key.sign(head + struct.pack("<Q", ts) + tail), v.address, ts
        ))
    return Commit(1, 0, block_id, sigs)


def tamper(kind: str, chain_id: str, commit, secrets: Sequence[bytes], pos: int):
    """A copy of `commit` whose slot `pos` is bad in the named way."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey
    from tendermint_tpu.types import Commit

    bad = Commit(commit.height, commit.round, commit.block_id, list(commit.signatures))
    cs = bad.signatures[pos]
    if kind == "signature_r":  # one flipped bit in R
        sig = bytes([cs.signature[0] ^ 1]) + cs.signature[1:]
    elif kind == "signature_s":  # one flipped bit in S
        sig = cs.signature[:32] + bytes([cs.signature[32] ^ 1]) + cs.signature[33:]
    elif kind == "message":  # a valid signature, over another timestamp's sign-bytes
        view = commit_view(commit)
        msg = reference.vote_sign_bytes(
            chain_id, view.height, view.round, view.block_hash, view.parts_total,
            view.parts_hash, cs.timestamp_ns + 1,
        )
        sig = Ed25519PrivateKey.from_private_bytes(secrets[pos]).sign(msg)
    elif kind == "noncanonical_s":  # S + L: same point, rejected encoding
        s = int.from_bytes(cs.signature[32:], "little") + reference.ED25519_L
        sig = cs.signature[:32] + s.to_bytes(32, "little")
    else:
        raise ValueError(kind)
    bad.signatures[pos] = dataclasses.replace(cs, signature=sig)
    return bad


TAMPERS = ("signature_r", "message", "noncanonical_s", "signature_s")

_WRONG_SIG = re.compile(r"wrong signature \(#(\d+)\)")


def engine_first_bad(chain_id: str, vset, commit) -> Optional[int]:
    """The system's verdict through ValidatorSet.verify_commit — the call
    fast-sync replay and block validation make: None when the commit is
    accepted, else the validator index it names."""
    try:
        vset.verify_commit(chain_id, commit.block_id, commit.height, commit)
    except ValueError as exc:
        m = _WRONG_SIG.search(str(exc))
        if m is None:
            raise
        return int(m.group(1))
    return None


async def warm_engine(
    watch: EngineWatch, verifier, cache, chain_id: str, vset, pubs: Sequence[bytes],
    powers: Sequence[int], warm_commit, stand_in: Optional[str] = None,
) -> dict:
    """Warm the engine on the chain's own validator set: `warm_commit`
    (make_commit) through `ValidatorSet.verify_commit`, each answer held to
    the reference's, until the recorder shows a table path for a batch of
    this size, then until the background work of `verifier` and `cache`
    (None under a stand-in) has landed.  Returns what until_device saw."""
    n_sigs = sum(not cs.is_absent() for cs in warm_commit.signatures)
    want, _ = reference.commit_verdict(chain_id, pubs, powers, commit_view(warm_commit))

    async def warm_call() -> None:
        got = engine_first_bad(chain_id, vset, warm_commit)
        if got != want:
            raise HarnessFailure(f"warm-up: engine names validator {got}, reference {want}")

    if n_sigs < watch.min_device_batch:
        raise HarnessFailure(
            f"a commit of {n_sigs} signatures rides the host tier "
            f"(min_device_batch {watch.min_device_batch}): no engine to measure"
        )
    if stand_in == "host_tier":
        await warm_call()
        return {"path": "host", "bucket": 0, "shards": 1}
    warm = await until_device(watch, n_sigs, warm_call, WARM_DEADLINE_S)
    if verifier is not None:
        await settle(verifier, cache, watch, WARM_DEADLINE_S)
    return warm


def tamper_mismatches(
    chain_id: str, vset, commit, pubs: Sequence[bytes], powers: Sequence[int],
    secrets: Sequence[bytes], rng: random.Random,
) -> int:
    """How many of five verdicts differ from the reference's: `commit` as
    it stands (accepted), and its four tampered copies, one bad slot in each
    quarter of its signatures, through ValidatorSet.verify_commit on `vset`:
    at the timed size, on the warm table.  `pubs`, `powers` and `secrets` are
    the reference's set for the commit's height."""

    def verdict(of) -> Optional[int]:
        """engine_first_bad; -1 where there is no set for the height, or
        one that refuses the commit before any signature (not its own)."""
        try:
            return -1 if vset is None else engine_first_bad(chain_id, vset, of)
        except ValueError as exc:
            log(f"the set for height {commit.height} refuses the commit: {exc}")
            return -1

    signed = [i for i, cs in enumerate(commit.signatures) if not cs.is_absent()]
    kinds = list(TAMPERS)
    rng.shuffle(kinds)
    mismatches = 0
    if verdict(commit) is not None:
        mismatches += 1
    for q, kind in enumerate(kinds):
        quarter = signed[len(signed) * q // 4: len(signed) * (q + 1) // 4] or signed
        pos = rng.choice(quarter)
        bad = tamper(kind, chain_id, commit, secrets, pos)
        want, _ = reference.commit_verdict(chain_id, pubs, powers, commit_view(bad))
        got = verdict(bad)
        if want != pos:
            raise HarnessFailure(f"the reference misses the {kind} tamper at #{pos} (says {want})")
        if got != want:
            log(f"tamper {kind} at #{pos}: engine says {got}, reference {want}")
            mismatches += 1
    return mismatches


def sample_size(set_size: int) -> int:
    """How many accepted commits the serial reference re-verifies: as many
    as hold SAMPLE_SIGNATURES between them, within SAMPLE_COMMITS."""
    return max(SAMPLE_COMMITS[0], min(SAMPLE_COMMITS[1], SAMPLE_SIGNATURES // set_size))


# ---------------------------------------------------------------------------
# what the window records
# ---------------------------------------------------------------------------


class Stamps:
    """When each height became the process under test's own, by the host
    clock, whoever stamps it: what cut_window reads.  `buffered` is for a rig
    whose process queues work behind the height stamped; one without leaves
    it empty."""

    def __init__(self):
        self.times_ns: List[int] = []  # time.monotonic_ns() at each arrival
        self.heights: List[int] = []
        self.buffered: List[int] = []

    def note(self, heights: Sequence[int]) -> None:
        """These heights arrived now, together."""
        now = time.monotonic_ns()
        self.times_ns.extend([now] * len(heights))
        self.heights.extend(heights)


class BlockStamps(Stamps):
    """Stands in for a NewBlock subscription's queue: notes when each block
    reached the subscriber, by the host clock, and keeps nothing else."""

    maxsize = 1 << 30  # never "out of capacity": nothing is kept

    def __init__(self, queue_depth: Callable[[], int]):
        super().__init__()
        # downloaded blocks waiting behind the one just applied; read here,
        # not on a timer, because the replay loop yields the event loop only
        # when that queue runs dry, and a timer would see nothing else
        self._queue_depth = queue_depth

    def qsize(self) -> int:
        return 0

    def empty(self) -> bool:
        return True

    def put_nowait(self, msg) -> None:
        data = getattr(getattr(msg, "data", None), "data", None)
        if isinstance(data, dict) and "block" in data:
            self.times_ns.append(time.monotonic_ns())
            self.heights.append(data["block"].height)
            self.buffered.append(self._queue_depth())


class CompileClock:
    """Counts JAX backend compiles (persistent-cache reads included) and
    their seconds, from jax.monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class DeliverSpans:
    """Spans around the node's ABCI consensus connection, taken from here
    (fast sync records no deliver.* event): begin_block's call to commit's
    return, one per height.  Only a traced run installs them."""

    def __init__(self, client):
        self.spans: List[tuple] = []  # (height, t0_ns, t1_ns) by time.monotonic_ns
        self._t0 = 0
        self._height = 0
        begin, commit = client.begin_block, client.commit

        async def begin_block(req):
            self._t0 = time.monotonic_ns()
            self._height = req.header.get("height", 0) if isinstance(req.header, dict) else 0
            return await begin(req)

        async def commit_(*args, **kw):
            res = await commit(*args, **kw)
            self.spans.append((self._height, self._t0, time.monotonic_ns()))
            return res

        client.begin_block = begin_block
        client.commit = commit_


@dataclasses.dataclass
class Window:
    """Everything the metric readers may read about one measured window."""

    cell: Cell
    seconds: float  # from the block that opens the window to the one that closes it
    t_open_ns: int  # time.monotonic_ns of those two arrivals
    t_close_ns: int
    block_times: List[float]  # arrival of each block applied after the opening one, seconds
    block_heights: List[int]
    events: List[dict]  # the flight recorder's verify.* events inside the window
    deliver_spans: List[tuple]
    buffered: List[int]  # blocks waiting in the replay queue, read as each block applied
    trace: Optional[object] = None  # benchmarks.trace.TraceSummary of a traced run
    device_kind: str = ""
    # heights at which a new membership signs first and whose table build
    # could land inside the window (cut_window); empty for a static set
    membership_changes: List[int] = dataclasses.field(default_factory=list)
    # the flight recorder of the process the rig started: the readers of its
    # ring (reducers/ring_*.py) take it from here, and look for the one alive
    # in the process only where none was handed
    recorder: Optional[object] = None

    @property
    def blocks(self) -> int:
        return len(self.block_times)


# ---------------------------------------------------------------------------
# faults a test or the control plants under the timed path
# ---------------------------------------------------------------------------


# the faults that sit in the engine's place or on its hooks: they need the
# flight recorder of the process under test and nothing else of it
ENGINE_FAULTS = (
    "stub_device", "host_tier", "accept_all", "half_batch", "build_always",
    "table_never_built", "one_chip",
)


def plant_fault(name: str, node, shards: int = 1) -> None:
    """Break the timed path underneath the harness, to show `correct` come
    out false.  Never used by a benchmark run.  The faults of the engine are
    plant_engine_fault's; the others need the node."""
    if name in ENGINE_FAULTS:
        plant_engine_fault(name, node.flight_recorder, shards)
    elif name == "stale_validator_sets":
        # the state store answers every height with the genesis set, as one
        # whose records all point back at their first would: the chain goes
        # on (the live path holds its sets in hand) and no val: write is read back
        load = node.state_store.load_validators
        node.state_store.load_validators = lambda height: load(1)
    elif name == "state_unchanged":
        # the app acknowledges every transaction and applies none
        from tendermint_tpu.abci import types as abci

        client = node.blockchain_reactor.block_exec.proxy_app

        async def deliver_tx(req):
            return abci.ResponseDeliverTx(code=abci.CODE_TYPE_OK)

        client.deliver_tx = deliver_tx
    else:
        raise ValueError(f"unknown fault {name!r}")


def plant_engine_fault(name: str, recorder, shards: int = 1) -> None:
    """One of ENGINE_FAULTS, reporting to `recorder` as the engine would.
    `stub_device` is the exception that breaks nothing: it stands in for the
    chip in a CPU test (serial host verification that reports itself as an
    indexed device dispatch over `shards` chips), so that the rest of a run
    can be driven without one.  Like the engine it keeps a table per
    membership: the first commit of one it has not met is a miss that builds
    that membership's table and is declined to the flat device path, which
    it stands in for too."""
    from tendermint_tpu.crypto import batch as crypto_batch

    def dispatched(n: int, path: str, shards: int = shards) -> None:
        recorder.record(
            "verify.dispatch", n=n, bucket=n, path=path, host_prep_ms=0.0, device_ms=0.0,
            shards=shards,
        )

    def table_built(set_key: bytes, rows: int, ms: float) -> None:
        recorder.record(
            "verify.table_build", set_key=set_key.hex()[:16], validators=rows, ms=ms, ok=True,
            error=None, shards=shards,
        )

    if name == "stub_device":
        tables = set()

        def stub(set_key, pubkeys, idxs, msgs, sigs):
            rows = pubkeys() if callable(pubkeys) else pubkeys  # verify_commit passes them lazily
            hit = set_key in tables
            recorder.record("verify.table", hit=hit, n=len(sigs))
            if not hit:  # build the table and decline this commit, as the engine does
                t0 = time.perf_counter()
                tables.add(bytes(set_key))
                table_built(set_key, len(rows), (time.perf_counter() - t0) * 1e3)
                return None
            dispatched(len(sigs), "indexed", stub.shards)
            return crypto_batch.host_batch_verify([rows[i] for i in idxs], msgs, sigs)

        def flat(pubkeys, msgs, sigs):  # where a declined commit goes: pubkeys shipped
            dispatched(len(sigs), "device", stub.shards)
            return crypto_batch.host_batch_verify(pubkeys, msgs, sigs)

        stub.shards = shards  # `one_chip` takes the mesh away under a run
        crypto_batch.set_indexed_verifier(stub)
        crypto_batch.set_verifier(flat)
    elif name == "host_tier":
        # the engine sends every batch to the serial host path
        def on_host(pubkeys, msgs, sigs):
            dispatched(len(sigs), "host")
            return crypto_batch.host_batch_verify(pubkeys, msgs, sigs)

        crypto_batch.set_verifier(on_host)
        crypto_batch.set_indexed_verifier(None)
    elif name in ("accept_all", "half_batch"):
        flat, indexed = crypto_batch.get_verifier(), crypto_batch.get_indexed_verifier()

        def keep(n: int) -> int:
            return 0 if name == "accept_all" else n // 2

        def flat_fault(pubkeys, msgs, sigs):
            k = keep(len(sigs))
            return list(flat(pubkeys[:k], msgs[:k], sigs[:k])) + [True] * (len(sigs) - k)

        def indexed_fault(set_key, pubkeys, idxs, msgs, sigs):
            # the device still runs the whole batch; the answer is altered
            # where it is produced
            out = indexed(set_key, pubkeys, idxs, msgs, sigs)
            if out is None:
                return None
            k = keep(len(sigs))
            return list(out[:k]) + [True] * (len(sigs) - k)

        crypto_batch.set_verifier(flat_fault)
        if indexed is not None:
            crypto_batch.set_indexed_verifier(indexed_fault)
    elif name == "build_always":
        # the engine builds a table for every commit it is handed, as one
        # keyed by the set's powers would where they change block by block
        indexed = crypto_batch.get_indexed_verifier()

        def rebuilding(set_key, pubkeys, idxs, msgs, sigs):
            table_built(set_key, len(idxs), 0.0)
            return indexed(set_key, pubkeys, idxs, msgs, sigs)

        crypto_batch.set_indexed_verifier(rebuilding)
    elif name == "table_never_built":
        # the engine serves the membership it meets first from its table and
        # declines every other without building one: a new membership's
        # commits ride the flat path for good
        indexed = crypto_batch.get_indexed_verifier()
        served = []

        def declining(set_key, pubkeys, idxs, msgs, sigs):
            if not served:
                served.append(bytes(set_key))
            if bytes(set_key) != served[0]:
                return None
            return indexed(set_key, pubkeys, idxs, msgs, sigs)

        crypto_batch.set_indexed_verifier(declining)
    elif name == "one_chip":
        # the stand-in (planted before this) with the mesh left out: every
        # dispatch answers correctly and reports itself on one chip
        crypto_batch.get_indexed_verifier().shards = 1
    else:
        raise ValueError(f"unknown fault {name!r}")


def unplant_faults() -> None:
    """What plant_fault hooked into the process goes with the run."""
    from tendermint_tpu.crypto import batch as crypto_batch

    crypto_batch.set_verifier(None)
    crypto_batch.set_indexed_verifier(None)


STAND_INS = ("stub_device", "host_tier")  # planted before warm-up: they replace the engine


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def _child_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # a child never touches the chip
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def start_chain(cell: Cell, seed: int):
    """The chain's directory, and the generator process if it has to be
    made (None when the cache holds it)."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    key = chainlib.cache_key(cell.config, cell.traffic, seed, cell.heights)
    chain_dir = os.path.join(CACHE_DIR, key)
    if chainlib.load_meta(chain_dir) is not None:
        os.utime(chain_dir)
        log(f"chain {key}: cached")
        return chain_dir, None
    shutil.rmtree(chain_dir, ignore_errors=True)
    shutil.rmtree(chain_dir + ".partial", ignore_errors=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    spec = os.path.join(OUT_DIR, f"{key}.spec")
    for suffix, body in ((".config.json", cell.config), (".traffic.json", cell.traffic)):
        with open(spec + suffix, "w") as f:
            json.dump(body, f)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "chain.py"),
         "--config", spec + ".config.json", "--traffic", spec + ".traffic.json",
         "--seed", str(seed), "--heights", str(cell.heights), "--out", chain_dir],
        env=_child_env(), stdin=subprocess.DEVNULL, stdout=sys.stderr,
    )
    return chain_dir, proc


async def wait_chain(chain_dir: str, proc, deadline_s: float) -> dict:
    if proc is not None:
        t0 = time.monotonic()
        while proc.poll() is None:
            if time.monotonic() - t0 > deadline_s:
                proc.kill()
                proc.wait()
                raise HarnessFailure(f"chain generator still running after {deadline_s:.0f} s")
            await asyncio.sleep(0.1)
        if proc.returncode != 0:
            raise HarnessFailure(f"chain generator exited with code {proc.returncode}")
    meta = chainlib.load_meta(chain_dir)
    if meta is None:
        raise HarnessFailure(f"no finished chain in {chain_dir}")
    return meta


class Sources:
    """The peers of the node under test: child processes, the source peers
    over the generated chain and one peer that only knows its length."""

    def __init__(self):
        self.procs: List[subprocess.Popen] = []
        self.addrs: List[str] = []  # the source peers', for the harness to dial

    def _spawn(self, *args: str) -> subprocess.Popen:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "source.py"), *args],
            env=_child_env(), stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        )
        self.procs.append(proc)
        return proc

    @staticmethod
    async def _addr(proc: subprocess.Popen) -> str:
        loop = asyncio.get_running_loop()
        line = await asyncio.wait_for(loop.run_in_executor(None, proc.stdout.readline), 60)
        if not line:
            raise HarnessFailure("a peer process exited before it listened")
        return json.loads(line)["addr"]

    def start_tip(self, chain_id: str, height: int) -> Awaitable[str]:
        """Start the peer that reports the chain's height and holds no block;
        await the result for its address.

        The node under test is given it as its persistent peer, so it dials
        it as it starts, as a deployment's node dials its configured peers,
        and keeps it throughout.  It is never asked for a block.  It is
        there because the program's `Scheduler.only_tip_outstanding()` takes
        a peer that has not reported its height yet (`add_peer` files it at
        height 0) for proof that the node has caught up: where that peer is
        the only one, and the pool routine's once-a-second look falls
        between the peer's arrival and its status message, the node leaves
        fast sync for good, a few blocks into a chain of thousands.  Inside the
        reactor's first second that look is not made; later a peer of known
        height keeps it from firing, at the first source's dial and at the
        redial of a source the node dropped (one a window at 10k
        validators).  PERF.md, Open questions, has the fault."""
        return self._addr(self._spawn("--tip", str(height), "--chain-id", chain_id))

    async def start(self, chain_dir: str, count: int, sink_channels: Sequence[int]) -> None:
        procs = [
            self._spawn("--chain", chain_dir, "--sink", ",".join(str(c) for c in sink_channels))
            for _ in range(count)
        ]
        for proc in procs:
            self.addrs.append(await self._addr(proc))

    def stop(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                proc.stdin.close()
        for proc in self.procs:
            try:
                proc.wait(10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            proc.stdout.close()
        self.procs = []


def node_config(cell: Cell, home: str, configure=None, tip_addr: str = ""):
    """The node under test's config: the program's defaults, fast sync on,
    kvstore, stores in memory, RPC and p2p on free local ports, the peer that
    knows the chain's length as its persistent peer."""
    from tendermint_tpu.config import Config

    cfg = Config(home=home)
    cfg.base.fast_sync = True
    cfg.base.proxy_app = cell.config["app"]
    cfg.base.db_backend = cell.config["node"]["db_backend"]
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.allow_duplicate_ip = True  # every peer is at 127.0.0.1
    cfg.p2p.persistent_peers = tip_addr
    if configure is not None:
        configure(cfg)
    cfg.validate_basic()
    cfg.ensure_dirs()
    return cfg


def genesis(cell: Cell, seed: int, pubs: Sequence[bytes], powers: Sequence[int]):
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.types import GenesisDoc, GenesisValidator

    vals = []
    for pub, power in zip(pubs, powers):
        key = Ed25519PubKey(pub)
        vals.append(GenesisValidator(key.address(), key, power))
    return GenesisDoc(
        chain_id=chainlib.chain_id(cell.config["name"], seed),
        genesis_time_ns=chainlib.GENESIS_TIME_NS, validators=vals,
    )


def connections(node) -> tuple:
    """The node's peer connections, by identity: a redialed peer is another."""
    return tuple(sorted(id(p) for p in node.switch.peer_list()))


async def dial_and_warm_in(node, addrs: Sequence[str], stamps: "BlockStamps", cell: Cell):
    """Dial the sources one at a time, `warm_in_blocks` applied blocks apart;
    returns (the connections held at the end, how often the count started
    again).

    A peer newly connected is asked for 20 blocks at once.  Where blocks are
    large (1.5 MB at 10k validators) two such waves together outlast the
    scheduler's 15 s request timeout as often as not; the program then takes
    a late block for an unsolicited one, drops both peers, redials them, and
    the same wave goes out again (my chip runs, PR 23: every ~17 blocks).
    That is the program's start-up and redial behaviour, not replay; one
    wave at a time lands.  Should the peers drop all the same, the count of
    warm-in blocks starts again when they are back."""
    warm_in = warm_in_blocks(cell.config, cell.traffic)
    t0 = time.monotonic()
    held, redials = connections(node), 0
    before = len(held)  # the peer at the tip
    for dialed, addr in enumerate(addrs, start=before + 1):
        # persistent, as a deployment's configured peers are: should the node
        # drop one, its redial does not hang on the address book's trust
        # score alone (which ranks a dropped source below the peer that only
        # knows the chain's length)
        if await node.switch.dial_peer(addr, persistent=True) is None:
            raise HarnessFailure(f"could not dial source {addr}")
        held, base = connections(node), len(stamps.times_ns)
        while len(held) < dialed or len(stamps.times_ns) - base < warm_in:
            if not node.blockchain_reactor.fast_sync:  # no further block will come
                raise HarnessFailure(
                    f"the node left fast sync during warm-in, at height {stamps.heights[-1:]}: "
                    "the chain is too short for this cell"
                )
            if time.monotonic() - t0 > WARM_IN_DEADLINE_S:
                raise HarnessFailure(
                    f"only {len(stamps.times_ns) - base} blocks applied over {len(held) - before} "
                    f"steady connection(s) to sources {WARM_IN_DEADLINE_S:.0f} s after the first "
                    f"was dialed (still in fast sync: {node.blockchain_reactor.fast_sync})"
                )
            await asyncio.sleep(0.02)
            now = connections(node)
            if now != held:
                held, base, redials = now, len(stamps.times_ns), redials + 1
                log(f"warm-in starts again at height {base}: {len(now) - before} source(s) held")
    return held, redials


def engine_rules(
    cell: Cell, window: "Window", min_device_batch: int, compiles: int, dispatches_due: int,
) -> Dict[str, int]:
    """What every rig holds a window's engine to (see window_failures for the
    first three): no compile inside it, and `dispatches_due` dispatches on a
    table path, the rig saying how many its traffic owes (one a block
    applied, one a header stored)."""
    rotating = cell.rotating
    checks = window_failures(
        window.events, min_device_batch, len(window.membership_changes) if rotating else None
    )
    checks["compiles_in_window"] = compiles
    # a new membership's commits ride the flat device path while its table builds
    served = table_dispatches(window) if not rotating else [
        ev for ev in window.events
        if ev["kind"] == "verify.dispatch" and ev["path"] in DEVICE_PATHS
    ]
    checks["blocks_without_device_dispatch"] = max(0, dispatches_due - len(served))
    return checks


def table_dispatches(window: "Window") -> List[dict]:
    return [
        ev for ev in window.events
        if ev["kind"] == "verify.dispatch" and ev["path"] in TABLE_PATHS
    ]


def unsharded_dispatches(cell: Cell, window: "Window") -> int:
    """What exists only across chips: every table dispatch on all of them."""
    return sum(1 for ev in table_dispatches(window) if ev["shards"] != cell.chips)


def window_rules(
    cell: Cell, window: "Window", min_device_batch: int, compiles: int, still_syncing: bool,
    last_height: int, chain_heights_: int,
) -> Dict[str, int]:
    """The counts a replay run must keep at 0 for its window to be the one
    the cell describes: engine_rules with a dispatch due for every block, and
    the node still in fast sync and short of its sources' tip."""
    checks = engine_rules(cell, window, min_device_batch, compiles, window.blocks)
    tip_margin = 2 * REQUESTS_PER_PEER * cell.config["source_peers"]
    checks["left_fast_sync"] = int(not still_syncing)
    checks["chain_exhausted"] = int(last_height > chain_heights_ - tip_margin)
    checks["window_empty"] = int(window.blocks == 0)
    if cell.chips > 1:
        checks["unsharded_dispatches"] = unsharded_dispatches(cell, window)
    return checks


def cut_window(
    cell: Cell, stamps: "Stamps", first: int, seconds: float, events: Sequence[dict],
    deliver_spans: Sequence[tuple], membership_heights: Sequence[int] = (), recorder=None,
) -> "Window":
    """The window, read off the stamps (heights and the times they arrived,
    whoever made them): from arrival `first` to the last
    arrival within `seconds` of it, with the events and spans inside, and
    those of the chain's `membership_heights` that it met: the new
    memberships whose table build could land inside it.  A membership that
    signs first at height h is known once h - 2 is applied (the watch's build
    starts there, the miss's as h is verified); the watch's builds take three
    to four of the hub's blocks (36-48 ms, PERF.md section 6).  So: from two
    heights before the first block inside to one past the last.  The count
    caps builds and flat dispatches, so one too many loosens a cap that a
    fault passes a hundred times over, and one too few fails a sound run."""
    import jax

    t_open_ns = stamps.times_ns[first]
    inside = [
        i for i in range(first + 1, len(stamps.times_ns))
        if stamps.times_ns[i] - t_open_ns <= seconds * 1e9
    ]
    t_close_ns = stamps.times_ns[inside[-1]] if inside else t_open_ns
    return Window(
        cell=cell, seconds=(t_close_ns - t_open_ns) / 1e9,
        t_open_ns=t_open_ns, t_close_ns=t_close_ns,
        block_times=[stamps.times_ns[i] / 1e9 for i in inside],
        block_heights=[stamps.heights[i] for i in inside],
        events=[ev for ev in events if t_open_ns < ev["t_ns"] <= t_close_ns],
        deliver_spans=[s for s in deliver_spans if t_open_ns <= s[1] and s[2] <= t_close_ns],
        buffered=[stamps.buffered[i] for i in inside] if stamps.buffered else [],
        device_kind=jax.devices()[0].device_kind, recorder=recorder,
        membership_changes=[
            h for h in membership_heights
            if inside and stamps.heights[inside[0]] - 2 <= h <= stamps.heights[inside[-1]] + 1
        ],
    )


async def run_cell(
    cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
    faults: Sequence[str] = (), **for_tests,
) -> dict:
    """One run of one cell, by the rig its traffic's kind names; returns the
    result object (see run.py).  `faults` and what a rig takes besides
    (run_replay: `configure`) are for the tests and the control."""
    return await find_rig(cell.kind)(cell, seed, seconds, trace, t_start, faults, **for_tests)


def open_trace(cell: Cell, seed: int) -> tuple:
    """Start the profiler; (the trace's directory, the anchor close_trace
    needs).  `benchmarks.trace.stop()` ends it."""
    from benchmarks import trace as tracelib

    trace_dir = os.path.join(OUT_DIR, f"trace.{cell.name}.s{seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return trace_dir, tracelib.start(trace_dir)


def close_trace(cell: Cell, trace_dir: str, anchor_ns: int, window: Window):
    """Reduce a stopped trace to the window (None on a CPU), hand it to the
    window's readers, and delete the trace."""
    from benchmarks import trace as tracelib

    summary = tracelib.summarize(
        trace_dir, anchor_ns, window.t_open_ns, window.t_close_ns,
        names_out=os.path.join(OUT_DIR, f"trace_names.{cell.name}.json"),
    )
    window.trace = summary
    shutil.rmtree(trace_dir, ignore_errors=True)
    return summary


def memory_peak() -> int:
    """Peak bytes in use on the fullest chip, as JAX reports it."""
    import jax

    return max(
        ((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()),
        default=0,
    )


def result_object(
    cell: Cell, window: Window, checks: Dict[str, int], setup_s: float, trace: bool,
    summary, peak: int, context: dict,
) -> dict:
    """The result of a run that reached its window's end, with the keys in
    the order run.py prints them; `context` is the rig's own, to which what
    every rig can say of a traced window is added."""
    import jax

    correct = all(v == 0 for v in checks.values())
    log(f"compared with the reference: correct={correct}")
    result = {
        "correct": correct,
        "attempted": window.blocks,
        "failed": 0 if correct else max(1, sum(checks.values())),
        "metrics": read_metrics(cell, window, setup_s, trace),
        "device": {
            "platform": jax.devices()[0].platform, "kind": window.device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak,
        },
    }
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown(window)
    result["context"] = context
    if summary is not None and summary.kernel_intervals:
        from benchmarks import trace as tracelib

        # the two clocks agree where the kernels ran inside the engine's calls
        calls = tracelib.host_activity(window)[tracelib.ENGINE_CALL]
        inside = sum(tracelib.overlap(iv, calls) for iv in summary.kernel_intervals)
        context["kernel_time_inside_engine_calls_share"] = (
            inside / tracelib.total(summary.kernel_intervals)
        )
        context["busy_s_by_device"] = summary.busy_s_by_device
        context["kernel_seconds"] = summary.kernel_seconds
    result["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    return result


def release_run(faults: Sequence[str], *chain_procs) -> None:
    """What every rig's run leaves as it ends, whatever became of it: no
    generator running, no fault hooked into the process, no spec file, a
    chain cache within its size."""
    for proc in chain_procs:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait()
    if faults:
        unplant_faults()
    for path in glob.glob(os.path.join(OUT_DIR, "*.spec.*.json")):
        os.remove(path)
    if os.path.isdir(CACHE_DIR):
        chainlib.prune_cache(CACHE_DIR, CACHE_KEEP_BYTES)


async def run_replay(
    cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
    faults: Sequence[str] = (), configure=None,
) -> dict:
    """The rig of kind `replay`: a node joins the chain by fast sync.
    `faults` (see plant_fault) and `configure` are for the tests and the
    control."""
    from tendermint_tpu import ops  # noqa: F401 — places the compile cache
    from tendermint_tpu.node import Node
    from tendermint_tpu.rpc.client import HTTPClient
    from tendermint_tpu.types.events import EVENT_NEW_BLOCK, query_for_event

    gc.set_threshold(50_000, 50, 25)  # as `cli node` sets it
    clock = CompileClock()
    chain_dir, chain_proc = start_chain(cell, seed)
    sources = Sources()
    home = tempfile.mkdtemp(prefix="bench-node-")
    node = None
    tracing = False
    try:
        tip_addr = sources.start_tip(chainlib.chain_id(cell.config["name"], seed), cell.heights)
        secrets, pubs, powers = chainlib.committee(seed, cell.config)
        cfg = node_config(cell, home, configure, tip_addr=await tip_addr)
        node = Node(cfg, genesis(cell, seed, pubs, powers), priv_validator=None)
        await node.start()
        stand_in = next((f for f in faults if f in STAND_INS), None)
        if not node.blockchain_reactor.fast_sync:
            raise HarnessFailure("the node started without fast sync")
        t0 = time.monotonic()
        while not node.switch.peer_list():  # dialed as the node started
            if time.monotonic() - t0 > 30:
                raise HarnessFailure("the node did not reach its persistent peer in 30 s")
            await asyncio.sleep(0.02)
        if node.batch_verifier is None and stand_in is None:
            raise HarnessFailure("the node started without its verify engine")
        min_device_batch = (
            node.batch_verifier.min_device_batch if node.batch_verifier is not None
            else cfg.tpu.min_device_batch
        )
        stamps = BlockStamps(lambda: node.blockchain_reactor.processor.pending_range())
        sub = await node.event_bus.subscribe("bench", query_for_event(EVENT_NEW_BLOCK))
        sub.queue = stamps
        watch = EngineWatch(node.flight_recorder, min_device_batch)
        if stand_in is not None:
            plant_fault(stand_in, node, shards=cell.chips)

        # -- warm the engine on this chain's validator set -------------------
        chain_id = node.genesis_doc.chain_id
        vset = node.blockchain_reactor.state.validators
        if [v.pub_key.bytes() for v in vset.validators] != pubs:
            raise HarnessFailure("the node's validator set is not the generated committee")
        warm_commit = make_commit(chain_id, vset, secrets, seed, cell.config["absent_share"])
        warm = await warm_engine(
            watch, node.batch_verifier, node.table_cache, chain_id, vset, pubs, powers,
            warm_commit, stand_in,
        )
        deliver = DeliverSpans(node.blockchain_reactor.block_exec.proxy_app) if trace else None

        # -- sources, warm-in -----------------------------------------------
        meta = await wait_chain(chain_dir, chain_proc, CHAIN_DEADLINE_S)
        chain_proc = None
        history = chainlib.set_history(zip(pubs, powers), meta)
        sink = sorted(
            d.id for d in node.switch.channel_descs
            if d.id not in {c.id for c in node.blockchain_reactor.get_channels()}
        )
        await sources.start(chain_dir, cell.config["source_peers"], sink)
        if not node.blockchain_reactor.fast_sync or not node.switch.peer_list():
            raise HarnessFailure(
                "before a source was dialed the node had left fast sync or lost the peer "
                f"that knows the chain's length (fast sync: {node.blockchain_reactor.fast_sync}, "
                f"peers: {len(node.switch.peer_list())})"
            )
        held, redials = await dial_and_warm_in(node, sources.addrs, stamps, cell)
        watch.poll()

        # -- the window -----------------------------------------------------
        if trace:
            from benchmarks import trace as tracelib

            trace_dir, trace_anchor_ns = open_trace(cell, seed)
            tracing = True
        for fault in faults:
            if fault not in STAND_INS:
                plant_fault(fault, node)
        compiles_before = clock.count
        n_events_before = len(watch.events)
        # The window runs from block to block: it opens with the first block
        # applied from now on and closes with the last one applied within
        # `seconds` of that.  The arrivals are stamped as the node publishes
        # them, whatever this coroutine's turn on the event loop (the replay
        # loop holds it for many blocks at a stretch), so the window's ends
        # are read off the stamps afterwards.
        t_decided_ns = time.monotonic_ns()
        n_blocks_before = len(stamps.times_ns)
        log(f"window opens with the next block, after height {stamps.heights[-1]}")

        def window_over() -> bool:
            if len(stamps.times_ns) == n_blocks_before:  # not open yet
                return (
                    not node.blockchain_reactor.fast_sync  # and never will: it has caught up
                    or time.monotonic_ns() - t_decided_ns >= WARM_IN_DEADLINE_S * 1e9
                )
            return time.monotonic_ns() - stamps.times_ns[n_blocks_before] >= seconds * 1e9

        while not window_over():
            await asyncio.sleep(POLL_PERIOD_S)
            watch.poll()
        summary = None
        if trace:
            tracelib.stop()
            tracing = False
        peak = memory_peak()
        still_syncing = node.blockchain_reactor.fast_sync
        peers_lost = connections(node) != held
        sources.stop()
        watch.poll()
        if len(stamps.times_ns) == n_blocks_before:
            raise HarnessFailure(
                "no block applied once warm-in was over: " + (
                    f"the replay stalled for {WARM_IN_DEADLINE_S:.0f} s" if still_syncing
                    else "the node had left fast sync, the chain is too short for this cell"
                )
            )
        window = cut_window(
            cell, stamps, n_blocks_before, seconds, watch.events[n_events_before:],
            deliver.spans if deliver else [], history.membership_heights,
            recorder=node.flight_recorder,
        )
        setup_s = window.t_open_ns / 1e9 - t_start
        log(f"set-up took {setup_s:.1f} s")
        log(f"window closed: {window.blocks} blocks in {window.seconds:.2f} s, "
            f"heights {window.block_heights[:1]}..{window.block_heights[-1:]} of {meta['heights']}")
        if trace:
            summary = close_trace(cell, trace_dir, trace_anchor_ns, window)

        # -- correct? -------------------------------------------------------
        checks = window_rules(
            cell, window, watch.min_device_batch, clock.count - compiles_before,
            still_syncing, stamps.heights[-1], meta["heights"],
        )
        async with HTTPClient(node.rpc_server.listen_addr) as client:
            spare_secrets, spare_pubs = chainlib.standby(seed, cell.config)
            checks.update(await compare_with_reference(
                client, node, window, meta, seed, chain_id, vset,
                dict(zip(pubs + spare_pubs, secrets + spare_secrets)), history,
            ))
        problems = engine_failures(watch.events, watch.min_device_batch, warm=False)
        checks["engine_errors"] = max(checks["engine_errors"], len(problems))
        return result_object(cell, window, checks, setup_s, trace, summary, peak, {
            "seed": seed, "warm": warm, "compile_s": round(clock.seconds, 2),
            "persistent_cache_hits": clock.cache_hits, "chain_generate_s": meta["generate_s"],
            "rtt_probe": getattr(node.batch_verifier, "rtt_probe", None),
            "membership_changes": window.membership_changes,
            "table_builds_in_window": [
                {k: ev.get(k) for k in ("kind", "ms", "ok")}
                for ev in window.events if ev["kind"] in TABLE_BUILD_EVENTS
            ],
            "heights_replayed": [window.block_heights[0], window.block_heights[-1]]
            if window.blocks else [],
            "buffered_blocks_min": min(window.buffered) if window.buffered else None,
            "warm_in_restarts": redials, "peers_lost_in_window": peers_lost,
            "block_interval_ms": {
                f"p{q}": percentile(block_intervals_ms(window), q)
                for q in (50, 75, 90, 95, 97.5, 99, 100)
            } if window.blocks else None,
        })
    finally:
        if tracing:
            from benchmarks import trace as tracelib

            tracelib.stop()
        sources.stop()
        if node is not None and node.is_running:
            # the run has its result or its failure by now: a node that
            # will not stop cleanly may not take either away (the process
            # ends next, and every child has been waited for)
            try:
                await asyncio.wait_for(node.stop(), NODE_STOP_DEADLINE_S)
            except Exception as exc:
                log(f"the node did not stop cleanly: {exc!r}")
        shutil.rmtree(home, ignore_errors=True)
        release_run(faults, chain_proc)


def accepted_wrongly(
    chain_id: str, history: reference.SetHistory, commit, block_hash: str
) -> bool:
    """Whether a commit the node accepted fails the reference: some
    signature rejected by OpenSSL under the reference's set for the commit's
    height, too little of that set's power, or another block's id."""
    pubs, powers = history.at(commit.height)
    first_bad, enough = reference.commit_verdict(chain_id, pubs, powers, commit_view(commit))
    return first_bad is not None or not enough or commit.block_id.hash.hex() != block_hash


async def reported_set(client, height: int) -> Optional[list]:
    """[(pubkey, power)] of the validator set the node reports at `height`
    (`/validators`, every page), or None where it has none."""
    from tendermint_tpu.rpc import RPCError

    members, page = [], 1
    while True:
        try:
            got = await client.validators(height, page=page, per_page=100)
        except RPCError as exc:  # the node's answer is the finding
            log(f"/validators at height {height}: {exc!r}")
            return None
        members += [(v["pub_key"]["value"], v["voting_power"]) for v in got["validators"]]
        if len(members) >= got["total"] or not got["validators"]:
            return members
        page += 1


async def compare_with_reference(
    client, node, window: Window, meta: dict, seed: int, chain_id: str, vset,
    secret_of: Dict[bytes, bytes], history: reference.SetHistory,
) -> Dict[str, int]:
    """The comparisons that decide `correct`, each a count that must be 0.

    From the client's side: the block ids of sampled replayed heights and
    the app hash at the node's tip against the source chain and the
    reference kvstore; sampled replayed writes read back.  The engine's
    verdicts: the commits it accepted in the window, re-verified serially
    by the reference against the reference's set for their height
    (`history`), and tampered copies of the window's last commit sent
    through ValidatorSet.verify_commit on the warm table, which must name
    the validator the reference names.  Where the set changes, the tampers
    go through the set the node's own state store holds for that height
    (`vset` is the genesis set, which a static chain keeps), and the sets
    the node reports at the sampled heights are held to the reference's."""
    if window.blocks == 0:
        return {"reference_not_reached": 1}
    rng = random.Random(seed ^ 0x5EED)
    cell = window.cell
    rotating = cell.rotating
    heights = window.block_heights
    n_commits = sample_size(len(history.at(1)[0]))
    sample = sorted(set(rng.sample(heights, min(n_commits - 1, len(heights))) + [heights[-1]]))

    status = (await client.status())["sync_info"]
    tip = status["latest_block_height"]
    n_txs = meta["txs_per_block"]
    wrong_blocks = 0
    if tip < heights[-1]:
        wrong_blocks += 1
    # the tip's header carries the app hash after the block before it; the
    # kvstore counts key=value transactions only, so `val:` ones do not move it
    if status["latest_app_hash"] != reference.kvstore_app_hash(n_txs * (tip - 1), tip - 1):
        wrong_blocks += 1
    if status["latest_block_hash"].hex() != meta["hashes"][tip - 1]:
        wrong_blocks += 1
    wrong_commits = wrong_sets = 0
    for h in sample:
        got = await client.block(h)
        if got["block_id"] is None or got["block_id"]["hash"].hex() != meta["hashes"][h - 1]:
            wrong_blocks += 1
        # the commit the node verified for height h, as it stored it
        commit = (await client.commit(h))["signed_header"].commit
        if commit.height != h or accepted_wrongly(chain_id, history, commit, meta["hashes"][h - 1]):
            wrong_commits += 1
        if rotating and await reported_set(client, h) != list(zip(*history.at(h))):
            wrong_sets += 1

    wrong_reads = 0
    for _ in range(SAMPLE_WRITES):
        h = rng.choice(heights)
        tx = rng.choice(chainlib.block_txs(seed, h, cell.traffic))
        key, value = chainlib.tx_key_value(tx)
        if (await client.abci_query(data=key))["response"]["value"] != value:
            wrong_reads += 1

    # tampered copies of the window's last commit, one bad slot in each
    # quarter of its signatures, at the timed size on the warm table
    commit = node.block_store.load_block_commit(heights[-1] - 1) or \
        node.block_store.load_seen_commit(heights[-1])
    pubs, powers = history.at(commit.height)
    secrets = [secret_of[p] for p in pubs]
    if rotating:
        vset = node.state_store.load_validators(commit.height)

    verdict_mismatches = tamper_mismatches(chain_id, vset, commit, pubs, powers, secrets, rng)
    checks = {
        "wrong_block_ids": wrong_blocks, "commits_accepted_wrongly": wrong_commits,
        "wrong_reads": wrong_reads, "verdict_mismatches": verdict_mismatches,
    }
    if rotating:
        checks["wrong_validator_sets"] = wrong_sets
    return checks


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks."""
    data = sorted(values)
    if not data:
        raise ValueError("no values")
    k = (len(data) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(data) - 1)
    return data[lo] + (data[hi] - data[lo]) * (k - lo)


def block_intervals_ms(window: Window) -> List[float]:
    """Time between consecutive blocks applied, over every block of the
    window (which opens at a block's arrival)."""
    edges = [window.t_open_ns / 1e9] + list(window.block_times)
    return [(b - a) * 1e3 for a, b in zip(edges, edges[1:])]


def end_to_end_value(name: str, window: Window, setup_s: float) -> Optional[float]:
    if name == "setup_s":
        return setup_s
    if name == "replay_blocks_per_s":
        return window.blocks / window.seconds if window.seconds else None
    if name == "block_interval_p95_ms":
        return percentile(block_intervals_ms(window), 95) if window.blocks else None
    raise HarnessFailure(f"no end-to-end metric {name!r} in this harness")


def read_metrics(cell: Cell, window: Window, setup_s: float, trace: bool) -> dict:
    """`--trace 0`: the cell's end-to-end metrics.  `--trace 1`: its
    per-layer metrics, each by the reader its file under metrics/ names; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    if not trace:
        for m in cell.end_to_end:
            value = end_to_end_value(m["name"], window, setup_s)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out
    for m in cell.per_layer:
        spec = _load_json(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json"))
        reader = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
        value = reader.read(window, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
