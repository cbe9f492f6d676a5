#!/usr/bin/env python
"""Chip smoke: the node's signature-verification path, end to end, on the
TPU this process holds.  The quickest proof that the system still starts
on the chip; it measures nothing a PR may claim as a speed.

One process, three phases, through the entry points a deployment uses:

1. node    — `cli init` a home, `default_new_node(cfg)` with the default
             config (engine on, mesh auto, kvstore, RPC), then
             `broadcast_tx_commit` + `abci_query` read-back over real HTTP.
             Starting the node is what installs the engine's process-wide
             hooks, exactly as `cli node` does.
2. commit  — a committee-width commit (10,000 validators, ~5% absent)
             through `ValidatorSet.verify_commit`, i.e. crypto/batch.py's
             indexed hook -> the node's TableCache -> a device-resident
             PubkeyTable; then tampered copies (corrupted signature, wrong
             message, non-canonical S, invalid pubkey encoding).
3. votes   — one full vote-ingress flush (4,096 precommits, a few bad)
             through `node.async_verifier.verify_many`.

Cold start is part of the path: a node serves the host tier while kernels
compile in the background.  Each phase keeps calling until the flight
recorder shows its batch dispatched on the device, and every answer on the
way — cold or warm — must equal the plain reference
(`crypto.batch.host_batch_verify`, which shares no code with the kernels).
Once a phase is warm, a host-tier dispatch of an engine-sized batch, or any
engine event with ok=False, fails the run.

Refuses to run without a TPU.  Spawns no process that imports JAX (a chip
belongs to one process).  Stdout carries two JSON lines: `{"report": {...}}`
(versions, compile cache and seconds, the path / bucket / kernel each phase
took, the RTT probe, peak HBM), then, last, the verdict with exactly these
keys: `{"ok": true, "device": {"platform", "kind", "count"}}`.  Exit code 0
only if every phase passed.  Nothing is printed to stdout on failure.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import json
import os
import random
import re
import shutil
import sys
import tempfile
import time
import traceback
from typing import Awaitable, Callable, Dict, List, Optional, Sequence

# verify.dispatch `path` values: through a device-resident PubkeyTable (the
# indexed hook), the flat device path, and the host tier
TABLE_PATHS = ("indexed", "chunked")
DEVICE_PATHS = TABLE_PATHS + ("device",)
HOST_PATHS = ("host", "host-cold")

# The real sizes; main() runs no others (tests pass toy sizes to run()).
N_VALIDATORS = 10_000  # committee width of the commit phase (BASELINE config #5)
N_VOTES = 4096  # one full vote-ingress flush ([tpu] max_batch)
N_TXS = 4
PHASE_DEADLINE_S = 420.0  # each phase's allowance to reach the device, compiles included

# ed25519 group order — for forging a non-canonical S (S + L)
_L = 2**252 + 27742317777372353535851937790883648493


class SmokeFailure(Exception):
    """A phase did not meet the smoke's contract."""


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def hbm() -> str:
    """Device-memory counters for a log line (absent on a CPU backend)."""
    import jax

    st = jax.devices()[0].memory_stats() or {}
    return " ".join(
        f"{k}={st[k] / 2**20:.0f}MiB" for k in ("bytes_in_use", "peak_bytes_in_use") if k in st
    )


# ---------------------------------------------------------------------------
# the rules on engine events
# ---------------------------------------------------------------------------


def engine_failures(events: Sequence[dict], min_device_batch: int, warm: bool) -> List[str]:
    """What a slice of flight-recorder events holds that the smoke refuses:
    any engine event reporting ok=False (a failed bucket compile, table
    build/rebuild, RTT probe or mesh probe), and — once
    `warm` — any host-tier dispatch of a batch the device should take."""
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


def fields(ev: dict) -> dict:
    """A recorder event without the recorder's own bookkeeping."""
    return {k: v for k, v in ev.items() if k not in ("seq", "t_ns", "kind")}


class EngineWatch:
    """Reads a node's flight recorder incrementally and holds every
    verify.* event to engine_failures.  `cold` is set by a phase while it
    waits for its kernels (host-tier dispatches are then by design)."""

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
                raise SmokeFailure("; ".join(problems))
        return new


async def until_device(
    watch: EngineWatch,
    what: str,
    n: int,
    call: Callable[[], Awaitable[None]],
    deadline_s: float,
    paths: Sequence[str] = DEVICE_PATHS,
    warm_calls: int = 2,
) -> dict:
    """Drive `call` (which checks its own answer against the reference and
    raises on a mismatch) until a verify.dispatch for a batch of n shows
    one of `paths`, then `warm_calls` more times with the warm rule on.
    Returns the last such dispatch event."""

    async def dispatched() -> List[dict]:
        """One call; the dispatch events it left for the batch of n."""
        await call()
        return [
            ev for ev in watch.poll() if ev["kind"] == "verify.dispatch" and ev["n"] == n
        ]

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
            raise SmokeFailure(
                f"{what}: no dispatch of the {n}-signature batch on path "
                f"{'|'.join(paths)} within {deadline_s:.0f} s ({calls} calls, all "
                "answered correctly)"
            )
        await asyncio.sleep(0.5)
    cold_s = time.monotonic() - t0
    watch.cold = False
    for _ in range(warm_calls):
        seen = await dispatched() or seen
        if seen[-1]["path"] not in paths:
            raise SmokeFailure(
                f"{what}: left path {'|'.join(paths)} for {seen[-1]['path']} once warm"
            )
    last = seen[-1]
    log(f"{what}: warm after {cold_s:.1f} s / {calls} cold calls; path={last['path']} "
        f"bucket={last['bucket']} shards={last['shards']} {hbm()}")
    return {**fields(last), "cold_calls": calls, "cold_s": round(cold_s, 1)}


# ---------------------------------------------------------------------------
# seeded committee, commits and votes
# ---------------------------------------------------------------------------


def make_committee(seed: int, n: int, invalid_key_at: Optional[int] = None):
    """(ValidatorSet, signing keys in set order) for n seeded ed25519
    validators.  With `invalid_key_at`, that member's public key is
    replaced by 32 bytes that decode to no curve point (its signing key is
    None); the set re-sorts by the new address."""
    from tendermint_tpu.crypto import ed25519_math
    from tendermint_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
    from tendermint_tpu.types import Validator, ValidatorSet

    members = []
    for i in range(n):
        key = Ed25519PrivKey.from_secret(b"chip-smoke-%d-%d" % (seed, i))
        members.append((key.pub_key(), key))
    if invalid_key_at is not None:
        rng = random.Random(seed ^ 0x1BAD)
        while True:
            raw = rng.randbytes(32)
            if ed25519_math.decompress(raw) is None:
                break
        members[invalid_key_at] = (Ed25519PubKey(raw), None)
    vset = ValidatorSet([Validator.new(pub, 10) for pub, _ in members])
    by_address = {pub.address(): key for pub, key in members}
    return vset, [by_address[v.address] for v in vset.validators]


def make_commit(chain_id: str, vset, keys, seed: int, absent_frac: float = 0.05):
    """A height-5 commit over a fixed block id, signed slot by slot, with
    ~absent_frac of the slots absent (seeded).  A member without a signing
    key (invalid pubkey) contributes 64 seeded garbage bytes."""
    from tendermint_tpu.types import BlockID, Commit, CommitSig, PartSetHeader

    rng = random.Random(seed)
    block_id = BlockID(b"\x05" * 32, PartSetHeader(1, b"\x06" * 32))
    sigs = [
        CommitSig.absent() if rng.random() < absent_frac
        else CommitSig.for_block(bytes(64), v.address, 1_700_000_000_000_000_000 + i)
        for i, v in enumerate(vset.validators)
    ]
    commit = Commit(5, 0, block_id, sigs)
    for i, (cs, key) in enumerate(zip(list(sigs), keys)):
        if cs.is_absent():
            continue
        sig = key.sign(commit.vote_sign_bytes(chain_id, i)) if key else rng.randbytes(64)
        sigs[i] = dataclasses.replace(cs, signature=sig)
    return commit


def tamper(kind: str, chain_id: str, commit, keys, pos: int):
    """A copy of `commit` whose slot `pos` is bad in the named way."""
    from tendermint_tpu.types import Commit

    bad = Commit(commit.height, commit.round, commit.block_id, list(commit.signatures))
    cs = bad.signatures[pos]
    if kind == "signature":  # one flipped bit in R
        sig = bytes([cs.signature[0] ^ 1]) + cs.signature[1:]
    elif kind == "message":  # a valid signature, over other sign-bytes
        bad.signatures[pos] = dataclasses.replace(cs, timestamp_ns=cs.timestamp_ns + 1)
        sig = keys[pos].sign(bad.vote_sign_bytes(chain_id, pos))
    elif kind == "noncanonical_s":  # S + L: same point, rejected encoding
        s = int.from_bytes(cs.signature[32:], "little") + _L
        sig = cs.signature[:32] + s.to_bytes(32, "little")
    else:
        raise ValueError(kind)
    bad.signatures[pos] = dataclasses.replace(cs, signature=sig)
    return bad


def reference_first_bad(chain_id: str, vset, commit) -> Optional[int]:
    """The plain reference's verdict on a commit: host_batch_verify over
    the same (pubkey, sign-bytes, signature) triples verify_commit forms;
    the validator index of the first rejected one, None when all pass."""
    from tendermint_tpu.crypto.batch import host_batch_verify

    idxs = [i for i, cs in enumerate(commit.signatures) if not cs.is_absent()]
    ok = host_batch_verify(
        [vset.validators[i].pub_key.bytes() for i in idxs],
        [commit.vote_sign_bytes(chain_id, i) for i in idxs],
        [commit.signatures[i].signature for i in idxs],
    )
    return next((i for i, good in zip(idxs, ok) if not good), None)


_WRONG_SIG = re.compile(r"wrong signature \(#(\d+)\)")


def engine_first_bad(chain_id: str, vset, commit) -> Optional[int]:
    """The system's verdict through ValidatorSet.verify_commit — the call
    fast-sync replay, block validation and lite2 make: None when the commit
    is accepted, else the validator index it names."""
    try:
        vset.verify_commit(chain_id, commit.block_id, commit.height, commit)
    except ValueError as exc:
        m = _WRONG_SIG.search(str(exc))
        if m is None:
            raise
        return int(m.group(1))
    return None


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


async def phase_node(home: str, seed: int, n_txs: int, configure=None):
    """`cli init` + `default_new_node` + start, then n_txs seeded key=value
    writes and their read-back over HTTP.  Returns (node, report); the node
    is left running for the engine phases.  `configure(cfg)` lets a test
    shorten timeouts or force the CPU mesh; main() passes none."""
    from tendermint_tpu import cli
    from tendermint_tpu.config import load_config
    from tendermint_tpu.node import default_new_node
    from tendermint_tpu.rpc.client import HTTPClient

    with contextlib.redirect_stdout(sys.stderr):  # stdout carries only the result
        if cli.main(["--home", home, "init", "--chain-id", f"chip-smoke-{seed}"]) != 0:
            raise SmokeFailure("cli init failed")
    cfg = load_config(os.path.join(home, "config", "config.toml"), home=home)
    cfg.rpc.laddr = "tcp://127.0.0.1:0"  # any free local port
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    if configure is not None:
        configure(cfg)
    cfg.validate_basic()
    node = default_new_node(cfg)
    await node.start()
    try:
        rng = random.Random(seed)
        writes = {
            b"smoke-%d-%d" % (seed, i): b"%016x" % rng.getrandbits(64) for i in range(n_txs)
        }
        async with HTTPClient(node.rpc_server.listen_addr) as client:
            h0 = (await client.status())["sync_info"]["latest_block_height"]
            for key, value in writes.items():
                res = await client.broadcast_tx_commit(key + b"=" + value)
                if res["check_tx"]["code"] or res["deliver_tx"]["code"] or res["height"] <= 0:
                    raise SmokeFailure(f"broadcast_tx_commit not acknowledged: {res}")
            for key, value in writes.items():
                got = (await client.abci_query(data=key))["response"]["value"]
                if got != value:
                    raise SmokeFailure(f"acknowledged write {key!r} read back as {got!r}")
            h1 = (await client.status())["sync_info"]["latest_block_height"]
        if h1 <= h0:
            raise SmokeFailure(f"/status height did not advance ({h0} -> {h1})")
    except BaseException:
        await node.stop()
        raise
    log(f"node: {n_txs} writes acknowledged and read back; height {h0} -> {h1}")
    return node, {"txs": n_txs, "read_back": True, "height_from": h0, "height_to": h1}


async def phase_commit(
    node, watch: EngineWatch, seed: int, n_validators: int, deadline_s: float
) -> dict:
    """Committee-width commit verification through the node's engine, then
    the tampered copies.  Accept/reject and the first failing validator
    index must equal the plain reference's on every call."""
    from tendermint_tpu.types import CommitSig

    chain_id = node.genesis_doc.chain_id
    t0 = time.monotonic()
    vset, keys = make_committee(seed, n_validators)
    commit = make_commit(chain_id, vset, keys, seed)
    n_sigs = sum(not cs.is_absent() for cs in commit.signatures)
    log(f"commit: {n_validators} validators, {n_sigs} signatures "
        f"generated in {time.monotonic() - t0:.1f} s")

    def checked(name: str, vs, cm):
        want = reference_first_bad(chain_id, vs, cm)

        async def call() -> None:
            got = engine_first_bad(chain_id, vs, cm)
            if got != want:
                raise SmokeFailure(
                    f"commit/{name}: engine names validator {got}, reference {want}"
                )

        return want, call

    want, call = checked("valid", vset, commit)
    if want is not None:
        raise SmokeFailure(f"reference rejects the untampered commit at #{want}")
    report = await until_device(watch, "commit", n_sigs, call, deadline_s, TABLE_PATHS)
    report.update(validators=n_validators, n=n_sigs, verdicts_equal=True, tampered={})

    rng = random.Random(seed + 1)
    signed = [i for i, cs in enumerate(commit.signatures) if not cs.is_absent()]
    for kind in ("signature", "message", "noncanonical_s"):
        pos = rng.choice(signed)
        want, call = checked(kind, vset, tamper(kind, chain_id, commit, keys, pos))
        if want != pos:
            raise SmokeFailure(f"reference misses the {kind} tamper at #{pos} (says {want})")
        await call()
        watch.poll()  # the table is warm: a host-tier dispatch here fails
        report["tampered"][kind] = {"at": pos, "verdicts_equal": True}

    # An invalid pubkey encoding makes a different validator set: its table
    # is built (and declined meanwhile) like any new set's.
    vset2, keys2 = make_committee(seed, n_validators, invalid_key_at=rng.randrange(n_validators))
    commit2 = make_commit(chain_id, vset2, keys2, seed)
    pos = keys2.index(None)
    if commit2.signatures[pos].is_absent():  # the seeded 5% may have hit it
        commit2.signatures[pos] = CommitSig.for_block(
            rng.randbytes(64), vset2.validators[pos].address, 1_700_000_000_000_000_000
        )
    want, call = checked("invalid_pubkey", vset2, commit2)
    if want != pos:
        raise SmokeFailure(f"reference misses the invalid pubkey at #{pos} (says {want})")
    n2 = sum(not cs.is_absent() for cs in commit2.signatures)
    await until_device(
        watch, "commit/invalid_pubkey", n2, call, deadline_s, TABLE_PATHS, warm_calls=0
    )
    report["tampered"]["invalid_pubkey"] = {"at": pos, "verdicts_equal": True}

    if node.batch_verifier.shards > 1:
        report["placement"] = mesh_placement(node, vset, report)
    return report


def mesh_placement(node, vset, dispatch: dict, platform: Optional[str] = None) -> dict:
    """More than one device: the commit's dispatch events must say
    shards == device count, the pubkey table must sit replicated on every
    device, and the verdict array's shards on that many distinct devices.
    The report names the inner verify the dispatch event names (`kernel`);
    on TPUs (`platform`: the backend's, but for the tests) that must be the
    ladder: XLA Straus on a mesh of chips is the slow path PR 26 took out."""
    import jax
    import numpy as np

    n_dev = len(jax.devices())
    if dispatch["shards"] != n_dev:
        raise SmokeFailure(f"dispatch reports shards={dispatch['shards']} on {n_dev} devices")
    if (platform or jax.default_backend()) == "tpu" and dispatch.get("kernel") != "ladder":
        raise SmokeFailure(
            f"{n_dev} TPU chips dispatched the commit on kernel={dispatch.get('kernel')!r}, "
            "not the Pallas ladder"
        )
    with node.table_cache._lock:
        table = node.table_cache._tables[vset.pubkeys_digest()]
    rows = table.neg_a_rows
    row_devices = {s.device for s in rows.addressable_shards}
    if not rows.sharding.is_fully_replicated or len(row_devices) != n_dev:
        raise SmokeFailure(f"pubkey table not replicated on all {n_dev} devices")
    # one more dispatch of the already-compiled jit, keeping the device array
    b = dispatch["bucket"]
    fn = table._chunked() if dispatch["path"] == "chunked" else table._fused()
    verdicts = fn(
        rows, np.zeros(b, np.int32), np.zeros((b, 32), np.uint8),
        np.zeros((b, 32), np.uint8), np.zeros((b, 20), np.int16), np.zeros(b, np.uint8),
    )
    verdict_devices = {s.device for s in verdicts.addressable_shards}
    if len(verdict_devices) != n_dev:
        raise SmokeFailure(
            f"verdict shards on {len(verdict_devices)} devices, expected {n_dev}"
        )
    return {"table_devices": len(row_devices), "verdict_devices": len(verdict_devices),
            "kernel": dispatch.get("kernel")}


async def phase_votes(
    node, watch: EngineWatch, seed: int, n_votes: int, deadline_s: float
) -> dict:
    """One full vote-ingress flush: n_votes seeded precommits, a few of
    them bad, through the node's AsyncBatchVerifier; the verdict vector
    must equal the plain reference's."""
    from tendermint_tpu.crypto.batch import host_batch_verify
    from tendermint_tpu.types import BlockID, PartSetHeader, Vote
    from tendermint_tpu.types.canonical import PRECOMMIT_TYPE

    chain_id = node.genesis_doc.chain_id
    vset, keys = make_committee(seed, n_votes)
    block_id = BlockID(b"\x07" * 32, PartSetHeader(1, b"\x08" * 32))
    rng = random.Random(seed + 2)
    bad = set(rng.sample(range(n_votes), k=min(5, n_votes)))
    items = []
    for i, (val, key) in enumerate(zip(vset.validators, keys)):
        vote = Vote(
            type=PRECOMMIT_TYPE, height=7, round=0, block_id=block_id,
            timestamp_ns=1_700_000_000_000_000_000 + i,
            validator_address=val.address, validator_index=i,
        )
        msg = vote.sign_bytes(chain_id)
        sig = key.sign(msg)
        if i in bad:
            sig = sig[:40] + bytes([sig[40] ^ 0x10]) + sig[41:]
        items.append((val.pub_key.bytes(), msg, sig))
    want = host_batch_verify(*map(list, zip(*items)))
    if [i for i, good in enumerate(want) if not good] != sorted(bad):
        raise SmokeFailure("reference verdicts do not match the tampered votes")

    async def call() -> None:
        got = await asyncio.gather(*node.async_verifier.verify_many(items))
        if [bool(g) for g in got] != [bool(w) for w in want]:
            raise SmokeFailure("votes: verdict vector differs from the reference")

    report = await until_device(watch, "votes", n_votes, call, deadline_s)
    report.update(n=n_votes, bad=len(bad), verdicts_equal=True)
    return report


async def settle(node, watch: EngineWatch, deadline_s: float) -> None:
    """Wait for background compiles and table builds to land, so a failure
    that arrives late still fails the run."""
    t0 = time.monotonic()
    verifier, cache = node.batch_verifier, node.table_cache
    while verifier._compiling_buckets or cache._building:
        if time.monotonic() - t0 > deadline_s:
            raise SmokeFailure(
                f"background work still running after {deadline_s:.0f} s: buckets "
                f"{sorted(verifier._compiling_buckets)}, {len(cache._building)} table builds"
            )
        await asyncio.sleep(0.2)
    watch.poll()


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included), per jitted function name, from jax.monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.by_name: Dict[str, float] = {}
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            name = str(kw.get("fun_name", "?"))
            self.by_name[name] = self.by_name.get(name, 0.0) + duration

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self) -> dict:
        return {
            "compile_s": round(sum(self.by_name.values()), 2),
            "compile_s_by_fn": {k: round(v, 2) for k, v in sorted(self.by_name.items())},
            "persistent_cache_hits": self.cache_hits,
        }


# the report's names for a dispatch event's `kernel`
KERNEL_NAMES = {"ladder": "pallas-ladder", "straus": "xla-straus"}


async def run(seed: int, n_validators: int, n_votes: int, n_txs: int,
              deadline_s: float, home: str, configure=None) -> dict:
    """All phases against one live node; returns the report body."""
    from tendermint_tpu.crypto import backend, hostprep

    # a missing fused C prep with a toolchain present is an error here, not
    # a quiet drop to the numpy prep
    fast_prep = hostprep.have_fast_prep()
    if not fast_prep and shutil.which("cc"):
        raise SmokeFailure(f"C host-prep extension did not build: {hostprep.lib_error()}")

    node, node_report = await phase_node(home, seed, n_txs, configure)
    try:
        verifier = node.batch_verifier
        watch = EngineWatch(node.flight_recorder, verifier.min_device_batch)
        commit = await phase_commit(node, watch, seed, n_validators, deadline_s)
        votes = await phase_votes(node, watch, seed, n_votes, deadline_s)
        await settle(node, watch, deadline_s)
        for phase in (commit, votes):
            phase["kernel"] = KERNEL_NAMES[phase["kernel"]]
        engine = [e for e in watch.events if e["kind"] == "verify.engine"]
        return {
            "host_tier": backend.active_tier(),
            "fast_prep": fast_prep,
            "shards": verifier.shards,
            "mesh": engine[-1]["mesh"] if engine else None,
            "rtt_probe": verifier.rtt_probe,
            "single_shot": (
                "chunked" if (verifier.rtt_probe or {}).get("chunked_selected") else "monolithic"
            ),
            "bucket_compiles": {
                str(e["bucket"]): e["ms"] for e in watch.events
                if e["kind"] == "verify.bucket_compile"
            },
            "phases": {"node": node_report, "commit": commit, "votes": votes},
        }
    finally:
        await node.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21, help="keys, absences and tampers")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found backend {jax.default_backend()!r} "
            "— refusing to run (no CPU fallback)",
            file=sys.stderr,
        )
        return 2
    import jaxlib

    try:
        from tendermint_tpu import ops  # noqa: F401 — places the compile cache
        from tendermint_tpu.libs.log import setup as log_setup
    except ImportError as exc:
        print(f"chip_smoke: run it from the root of a checkout: {exc}", file=sys.stderr)
        return 3

    try:
        from importlib.metadata import version

        libtpu_version = version("libtpu")
    except Exception:  # metadata absent: the version is a label, not a gate
        libtpu_version = None
    # the node's own log lines ("verify engine" among them) and every
    # error-level line, the engine's failure reports included
    log_setup(module_levels={"*": "error", "node": "info"})
    clock = CompileClock()
    t0 = time.monotonic()
    home = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        body = asyncio.run(
            run(args.seed, N_VALIDATORS, N_VOTES, N_TXS, PHASE_DEADLINE_S, home)
        )
    except Exception as exc:
        if not isinstance(exc, SmokeFailure):
            traceback.print_exc()
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        shutil.rmtree(home, ignore_errors=True)
        # a compile thread may be wedged; do not wait on it to report failure
        os._exit(1)
    shutil.rmtree(home, ignore_errors=True)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print_result({
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu_version},
        "seed": args.seed,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        **clock.report(),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "wall_s": round(time.monotonic() - t0, 1),
        **body,
    })
    return 0


def print_result(report: dict) -> None:
    """The two stdout lines of a passed run: what was observed, then the
    verdict — whose keys are fixed by the chip check's contract, so nothing
    else may ride on it."""
    print(json.dumps({"report": report}))
    print(json.dumps({"ok": True, "device": report["device"]}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
