"""Flight recorder: an always-on ring buffer of hot-path span events.

No reference counterpart — the reference's hot path (serial per-signature
verification) has nothing worth tracing; this framework's batched TPU
verify pipeline (crypto/batch_verifier.py) and the consensus step machine
do, and Prometheus histograms alone cannot answer "what did block 1234
spend its milliseconds on".  The recorder keeps the last N events
(monotonic-clock timestamped, fixed memory) so a bench rig, the
`dump_flight_recorder` RPC route and the `tendermint_tpu trace` CLI all
read the SAME event stream production telemetry comes from.

Event kinds currently emitted:

  consensus (consensus/state.py):
    step              height, round, step      every H/R/S transition
    proposal          height, round, src       proposal accepted; src is the
                                               delivering peer id prefix or
                                               "self" when we proposed
    block.parts_complete  height, round, parts, src   the proposal block
                                               fully assembled on this node
                                               (src delivered the last part)
    commit            height, txs, block       block finalized (hash prefix)
    consensus.height  SPAN, one per height this node enters and commits, id =
                      height: from its entry (update_to_state) to its block
                      applied (NewBlock published), on the pipelined delivery
                      lane too.  Laps in the order they come: votes_ms (to
                      +2/3 precommits for a block: enterCommit), parts_ms (to
                      the proposal block complete), apply_ms (to apply_block's
                      return); votes_added (votes added to this height's
                      sets), wal_ms (the WAL writes of its inputs); and what
                      apply_block hands fastsync.block (validate_ms,
                      deliver_ms, ... and their stamps).  The verify.commit
                      spans of its block's checks open under it
  verify engine (crypto/batch_verifier.py):
    verify.enqueue    pending                  vote entered the batcher
    verify.enqueue_batch  n, pending           whole vote_batch entered as one arrival
    verify.flush      batch, wait_ms, quantum_ms   batcher coalesced a flush
    verify.dispatch   n, bucket, path, shards, host_prep_ms, device_ms,
                      pack_ms, launch_ms, fetch_ms, rows_ms   SPAN, one per engine
                                               call: host_prep_ms (hash/reduce
                                               per signature) + device_ms (the
                                               rest: pack, transfer, launch,
                                               kernel, copy back) = the call's
                                               wall time on every path, less
                                               rows_ms on the table paths (the
                                               row list built from the caller's
                                               indices); pack_ms + launch_ms
                                               (until the jitted call returns) +
                                               fetch_ms (blocked in np.asarray)
                                               split device_ms on device paths.
                                               Device paths also carry kernel
                                               (ladder | straus: the inner
                                               verify), shard_n (useful
                                               rows per shard; shards is how
                                               many devices THIS dispatch was
                                               split over), device (the one a
                                               mesh's small batch ran on) and
                                               put_ms (the host's time in the
                                               explicit argument transfer, a
                                               part of launch_ms: every mesh
                                               dispatch and every chunk)
    verify.bucket_compile  bucket, ms, ok      background XLA compile done
    verify.chunked    selected, rtt_ms, prep_ms    RTT-probe decision
    verify.table      hit, n                   TableCache lookup
  commit verify hook (types/validator.py, inside a caller's span only):
    verify.commit     height, n, sign_bytes_ms, engine_ms, tally_ms   SPAN
                                               around verify_commit /
                                               verify_commit_trusting
  fast sync (fastsync/reactor.py, state/execution.py, state/validation.py):
    fastsync.block    SPAN, one per block applied, id = height: peek_two to
                      block_processed.  In-span stages parts_ms, verify_ms,
                      store_ms, apply_ms (sum <= dur_ns); inside store_ms,
                      from BlockStore.save_block, commit_encodes (how many
                      of C:H-1 and SC:H were encoded for it, 0-2: a commit
                      keeps its sealed record, so fast sync's LastCommit,
                      saved as the seen commit a block before, is not); from
                      apply_block validate_ms, abci_req_ms (BeginBlock's
                      request built), deliver_ms (BeginBlock's call to
                      Commit's return), mempool_ms, save_state_ms, events_ms;
                      inside deliver_ms, as stamps alone, begin_block,
                      deliver_txs (the DeliverTx x n loop), end_block,
                      save_responses, update_state, mempool_lock (the
                      mempool's lock and flush), abci_commit; inside
                      save_responses, responses_generic (how many DeliverTx
                      responses were not of the exact classes and took
                      asdict's walk: 0 from an app in process or behind a
                      socket or gRPC client); with the in-process ABCI
                      client, app_ms (inside the app's handlers over the four
                      ABCI calls) and abci_overhead_ms (the four calls' stamps
                      less app_ms: the client's lock, awaits and request and
                      response objects);
                      inside save_state_ms, from StateStore.save, set_encodes
                      (how many of the state's three validator sets were
                      encoded for it, 0-3: a set keeps its encoding until it
                      changes, so 1 a block on a static set); inside
                      validate_ms, from validate_block, basic_ms (the whole
                      of Block.validate_basic) and commit_hashes (whether
                      the LastCommit's Merkle root was built in it, 0 or 1:
                      a Commit keeps its root), set_hash_ms (the
                      header's two validator-set hashes held against the
                      state's sets) and set_hashes (how many of the two
                      Merkle roots were built for it, a count: 0 on a set
                      that has not changed since its root was taken),
                      median_ms (the LastCommit's weighted median time);
                      carried with the block bytes, decode_ms, download_ms
                      (request to receipt),
                      queued_ms (receipt to peek_two), peer; wait_ms (since the
                      previous block's end), pending (blocks queued).  On
                      on-disk stores (libs/kvstore.py WriteMeter; absent on
                      memdb), what they wrote between the previous block's
                      end and this one's, the indexer's work between spans
                      included: db_ms (inside write transactions, <= the
                      block interval), db_ms.<store> (app, blockstore,
                      state, tx_index, evidence; they add up to db_ms),
                      db_txns, db_rows, db_bytes (counts), db_max_ms (the
                      slowest single transaction: a WAL checkpoint).  With
                      the kv tx indexer on (memdb too): txs (in this block),
                      txs_indexed (taken in by the indexer since the block
                      before closed), index_lag (this height less the last
                      whose txs are all indexed)
  light client (lite2/client.py, on the recorder lite2.proxy.start_proxy
  gives it):
    lite.header       SPAN, one per header made the client's own in
                      sequence or bisection, id = height: fetch_ms (the
                      provider's two calls: a round trip for the commit and
                      one per 100 validators, three at 175, decoding
                      included), decode_ms (0: no provider times it apart),
                      set_hash_ms, verify_ms (the verifier's rules and the
                      commit checks, engine call included), cross_check_ms
                      (the witnesses, on the header asked for; else 0),
                      store_ms, mode; ok=False and error on a header that
                      failed.  The verify.commit spans open under it
    txindex.cut       height, reason, indexed_through
                                               the indexer's subscription was
                                               cancelled under it (a full buffer:
                                               "out of capacity"); nothing after
                                               indexed_through's queued txs is
                                               indexed until a restart
  gossip (consensus/reactor.py, event-driven path):
    gossip.wakeup     peer                     routine woken by an event (not the
                                               fallback sleep cap); HIGH-RATE —
                                               subject to trace_sample_high_rate
    gossip.votes      mode, n, bytes, peer     vote send: mode batch|single
    gossip.vote_batch_recv  n, dup, peer       decoded batch entered the verifier
                                               (n fresh votes, dup already-held)
    consensus.vote_batch  SPAN, one per vote_batch frame received that
                      decodes to votes, id = their height: n (fresh votes
                      sent to the engine), dup, decode_ms (decode,
                      validate_basic, dedup, resolve and sign-bytes),
                      verify_ms (the engine's call, or the keys' own verify),
                      add_ms (the add_vote_input loop); ok=False on a frame
                      refused after it decoded.  Nothing nests in it
    gossip.part_burst n, peer[, catchup]       block parts sent in one burst
    gossip.hop        frame, peer, origin, hop[, h, lat_ms, clamped]
                                               wire-level trace context decoded
                                               off a received frame (gossip
                                               version >= 3): per-kind
                                               propagation latency (sender send
                                               wall ns vs our wall ns) and the
                                               content hop count.  `clamped=1`
                                               marks byzantine/garbled fields
                                               (hop out of range, origin
                                               timestamp outside the ±60 s
                                               sanity window) — those carry no
                                               lat_ms and are excluded from
                                               skew estimation.  HIGH-RATE —
                                               subject to trace_sample_high_rate
                                               (net_budget consumes it)
  scheduler profiler (libs/loopprof.py, [instrumentation] loop_profiler):
    loop.lag          lag_ms                   scheduled-vs-actual probe wakeup
                                               delta, once per probe interval
    loop.busy         interval_ms, <category>_ms...   per-category on-CPU task
                                               time accounted since the last
                                               loop.busy event, interval_ms
                                               being the time elapsed since
                                               then (consensus/gossip/p2p-conn/
                                               fastsync/verify/mempool/rpc/
                                               other)
    loop.gc_pause     n, ms, max_ms            GC pauses accumulated this
                                               interval (gc.callbacks hooks)
    loop.queue        <name>=depth...          sampled queue depths (consensus
                                               receive, verify pending, mconn
                                               send, flush executor)
  statesync (statesync/syncer.py + reactor.py, bootstrap only):
    statesync.offer   height, format, chunks, result   snapshot offered to the app
    statesync.chunk   index, total, peer       chunk hash-verified + applied
    statesync.restore height, ms               app restored + checked vs verified header
    statesync.handover  height                 restored state handed to fastsync
  ingress (rpc/core.py + mempool.py, overload admission control):
    ingress.throttle  reason[, source]         a broadcast request was rejected
                                               with an explicit overload error
                                               (reason rate|inflight|
                                               mempool_full|commit_waiters);
                                               HIGH-RATE — subject to
                                               trace_sample_high_rate
    ingress.evict     n, priority, size        a full mempool evicted n
                                               lower-priority txs to admit one
                                               of the given priority
  evidence (evidence.py, accountability pipeline):
    evidence.add      height, hash             evidence verified into the pool
    evidence.commit   height, hash             evidence committed into a block
  chaos (chaos/ package, fault injection — only when [chaos] enabled):
    chaos.link        peer, drop, delay, ...   a link policy was set
    chaos.heal                                 every link policy cleared
    chaos.skew        skew_s                   consensus wall-clock skew set
    chaos.twin_vote   height, round, type      the twin signed a conflict
    chaos.partition / chaos.kill / chaos.restart ...  scenario events as
                                               executed by the runner

Events are flat dicts: {"seq", "t_ns", "kind", **fields}.  A SPAN is one
such event, written when the span closes: `t_ns` is its end, `dur_ns` its
length, `id` what the spans of one request share (a block's height),
`parent` the kind of the span it was opened in (a contextvars.ContextVar
holds the open span, so it follows a task through its awaits).  A point
event recorded inside an open span carries that span's `parent`/`id` too.
A span's self time is `dur_ns` minus its children's.  Stages of a span are
FIELDS of its event (`Span.lap`), not events of their own: the ring is
always on.  Where they ran is one field more, `stamps`, written on the
kinds of `STAMPED_KINDS` alone (those a reader places in time): [[name,
start_us, end_us], ...], microseconds from the span's start, one a lap in
the order taken (a callee's, handed over by `annotate`, and the inner
stages of a lap that `Laps.stamp` marks, included), so that stages nest by
containment.  While a jax.profiler trace runs, each span is mirrored as a
TraceAnnotation of the same name on the host plane of that trace (only
where jax is already imported).  `t_ns` is
time.monotonic_ns() — deltas are meaningful, wall-clock is not — but the
recorder also carries a monotonic→wall ANCHOR (sampled at construction
and re-sampled on every snapshot) so recorders dumped from DIFFERENT
nodes can be aligned onto one wall timeline: wall(ev) = anchor.wall_ns +
(ev.t_ns - anchor.mono_ns).  libs/tracemerge.py is the consumer.

High-rate kinds (per-wakeup gossip events; ~700 connections can evict
the entire ring between commits) go through `record_sampled`: with
`[instrumentation] trace_sample_high_rate` = N only 1-in-N events is
stored, and the stored event carries `sampled=N` so consumers can
re-scale counts.  N=1 (default) preserves the record-everything behavior
small nets want.

Performance contract: `record` on a disabled recorder (or the module NOP)
is one attribute check; enabled it is one uncontended lock, one
monotonic_ns call, one context lookup, one tuple and one list store — well
under a microsecond; a span costs about three of those and each lap one
more (tests/test_tracing.py tripwires both budgets).  Writers may be
the event loop, the flush executor or warmup threads concurrently; the
lock makes seq order equal timestamp order, which the span-chain
consumers rely on.
"""

from __future__ import annotations

import contextvars
import json
import os
import re
import sys
import threading
import time
import weakref
from typing import Callable, List, Optional, Sequence


#: The innermost open span of the running task (or thread).
_CURRENT: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "tendermint_tpu_span", default=None
)

# every enabled recorder alive in the process, for a reader that was handed
# none (benchmarks/reducers/ring_*.py); weak, so a stopped node's goes with it
_LIVE: "weakref.WeakSet[FlightRecorder]" = weakref.WeakSet()


def live_recorders() -> List["FlightRecorder"]:
    return [r for r in _LIVE if r.enabled]


_monotonic_ns = time.monotonic_ns  # the stopwatch's one clock read, bound once
#: the spans whose event carries its `stamps`: a replay block, a height
#: committed by consensus, a light client's header and their commit checks,
#: which benchmarks/stages.py files device idle time under; every other span
#: keeps its laps as fields alone
STAMPED_KINDS = frozenset(("fastsync.block", "consensus.height", "lite.header", "verify.commit"))
_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax has been imported


def _trace_annotation():
    """jax.profiler.TraceAnnotation where jax is already imported (a node
    with the engine off imports nothing for a span's sake), else None."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        jax = sys.modules.get("jax")
        _TRACE_ANNOTATION = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    return _TRACE_ANNOTATION


class Laps:
    """Stage stopwatch: each `lap(name)` is one clock read and adds the
    milliseconds since the previous one (or the start) to `fields[name]`,
    so a stage that runs in several pieces sums.  Returns the lap, for a
    second sink (a Prometheus histogram) to take the same reading.

    The same read also appends `(name, start_ns, end_ns)` to `stamps`, so
    each stage has its place in time as well as its length.  `stamp(name)`
    marks an inner stage of the lap that is running (from the previous
    stamp or the lap's start): it appends to `stamps` alone and neither
    writes a field nor cuts the lap."""

    __slots__ = ("fields", "stamps", "_t", "_inner")

    def __init__(self):
        self.fields: dict = {}
        self.stamps: list = []
        self._t = self._inner = _monotonic_ns()

    def lap(self, name: str) -> float:
        now = _monotonic_ns()
        t = self._t
        self.stamps.append((name, t, now))
        self._t = self._inner = now
        ms = (now - t) / 1e6
        fields = self.fields
        fields[name] = fields.get(name, 0.0) + ms
        return ms

    def stamp(self, name: str) -> float:
        now = _monotonic_ns()
        t = self._inner
        self.stamps.append((name, t, now))
        self._inner = now
        return (now - t) / 1e6


def current_span() -> Optional["Span"]:
    """The innermost span still open in this task, if any."""
    span = _CURRENT.get()
    while span is not None and span.recorder is None:  # closed in another task's copy
        span = span._outer
    return span


class Span(Laps):
    """One open span (FlightRecorder.span).  `end()` writes its event;
    `drop()` closes it without one.  Detached (no recorder: the NOP, a
    disabled recorder, `child_span` outside any span) it still times its
    laps for their other sinks, writes nothing and is no one's parent."""

    __slots__ = ("kind", "id", "parent", "t0_ns", "recorder", "_outer", "_hist", "_mirror")

    def __init__(self, rec: Optional["FlightRecorder"], kind: str, id, hist, fields: dict):
        self.fields = fields
        self.stamps = []
        self.kind = kind
        self.t0_ns = self._t = self._inner = time.monotonic_ns()
        self.recorder = rec  # None once closed, or detached
        self._hist = hist
        self._mirror = None
        self._outer = outer = current_span()
        # the spans of one request share its id: the root's
        self.id = id if outer is None or outer.id is None else outer.id
        self.parent = None if outer is None else outer.kind
        if rec is None:
            return
        _CURRENT.set(self)
        annotation = _trace_annotation()
        if annotation is not None and annotation.is_enabled():
            self._mirror = annotation(kind) if self.id is None else annotation(kind, id=self.id)
            self._mirror.__enter__()

    def set(self, **fields) -> None:
        self.fields.update(fields)

    def _close(self) -> Optional["FlightRecorder"]:
        rec, self.recorder = self.recorder, None
        if rec is not None:
            if self._mirror is not None:
                self._mirror.__exit__(None, None, None)
            if _CURRENT.get() is self:
                _CURRENT.set(self._outer)
        return rec

    def end(self) -> int:
        """Close the span; returns its length in ns (`t0_ns` + that is its
        event's `t_ns`)."""
        rec = self._close()
        if rec is not None:
            dur_ns = rec._store_span(self)
        else:
            dur_ns = time.monotonic_ns() - self.t0_ns
        if self._hist is not None:
            self._hist.observe(dur_ns / 1e9)
        return dur_ns

    def drop(self) -> None:
        self._close()

    def leave(self) -> None:
        """This task stops being inside the span, which stays open for the
        task it was handed to (one that copied this task's context while the
        span was current): a span opened here next is no child of it."""
        if _CURRENT.get() is self:
            _CURRENT.set(self._outer)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


def child_span(kind: str, **fields) -> Span:
    """A span on the recorder of the span this task has open: how code with
    no recorder of its own (types/validator.py) joins its caller's chain.
    Outside any span it is detached."""
    outer = current_span()
    return Span(None if outer is None else outer.recorder, kind, None, None, fields)


def annotate(stamps: Sequence[tuple] = (), **fields) -> None:
    """Set fields on the span this task has open, if any, and add `stamps`
    (a standalone `Laps`'s) to its own: how a callee
    (BlockExecutor.apply_block) hands its stage times to whichever caller
    opened one."""
    outer = current_span()
    if outer is not None:
        outer.fields.update(fields)
        outer.stamps.extend(stamps)


class NopRecorder:
    """Disabled-path recorder: accepts events and drops them."""

    enabled = False
    size = 0
    sample_high_rate = 1

    def record(self, kind: str, **fields) -> None:
        pass

    def record_sampled(self, kind: str, **fields) -> None:
        pass

    def span(self, kind: str, id=None, hist=None, **fields) -> Span:
        return Span(None, kind, id, hist, fields)

    begin = span

    def events(self, since: int = 0, kinds=None) -> List[dict]:
        return []

    def snapshot(self, since: int = 0, kinds=None) -> dict:
        return {"enabled": False, "size": 0, "next_seq": 0, "events": []}


NOP = NopRecorder()


class FlightRecorder:
    """Fixed-size ring of span events; `enabled=False` degrades to the nop
    fast path while keeping one object type at every call site."""

    __slots__ = (
        "size", "enabled", "sample_high_rate", "_buf", "_seq", "_lock",
        "_sample_counts", "_wall_ns_fn", "anchor_mono_ns", "anchor_wall_ns",
        "__weakref__",
    )

    def __init__(
        self,
        size: int = 8192,
        enabled: bool = True,
        sample_high_rate: int = 1,
        wall_ns_fn: Callable[[], int] = time.time_ns,
    ):
        if size < 1:
            raise ValueError("flight recorder size must be >= 1")
        if sample_high_rate < 1:
            raise ValueError("trace_sample_high_rate must be >= 1")
        self.size = size
        self.enabled = enabled
        self.sample_high_rate = sample_high_rate
        self._buf: List[Optional[tuple]] = [None] * size
        self._seq = 0  # next sequence number; monotonic, never wraps
        # an uncontended Lock costs ~0.1 µs and guarantees seq order ==
        # timestamp order across writer threads (the monotonicity the
        # span-chain consumers rely on)
        self._lock = threading.Lock()
        self._sample_counts: dict = {}
        # monotonic→wall anchor: lets tracemerge place this recorder's
        # t_ns events on a wall timeline shared with OTHER nodes' dumps.
        # wall_ns_fn is pluggable so a chaos SkewedClock (and its tests)
        # can skew what this node believes wall time is.
        self._wall_ns_fn = wall_ns_fn
        self.anchor_mono_ns = time.monotonic_ns()
        self.anchor_wall_ns = wall_ns_fn()
        if enabled:
            _LIVE.add(self)

    def record(self, kind: str, **fields) -> None:
        if not self.enabled:
            return
        span = _CURRENT.get()
        if span is not None and span.recorder is not None:  # open, in this task
            fields.setdefault("parent", span.kind)
            fields.setdefault("id", span.id)
        with self._lock:
            i = self._seq
            self._seq = i + 1
            self._buf[i % self.size] = (i, time.monotonic_ns(), kind, fields)

    def span(self, kind: str, id=None, hist=None, **fields) -> Span:
        """Open a span: a context manager, or (`begin`) an object to `end()`
        by hand where a `with` cannot nest.  ONE event is written when it
        closes: {"kind", "t_ns" (the end), "dur_ns", "id", "parent",
        **fields}.  `hist` (a Prometheus histogram in seconds) observes the
        same reading.  Opened inside another span it takes that span's `id`;
        `id` names the root of a chain."""
        return Span(self if self.enabled else None, kind, id, hist, fields)

    begin = span

    def _store_span(self, span: Span) -> int:
        fields = span.fields
        for name, value in fields.items():
            if type(value) is float:
                fields[name] = round(value, 3)
        if span.stamps and span.kind in STAMPED_KINDS:
            t0 = span.t0_ns
            fields["stamps"] = [
                [name, (a - t0) // 1000, (b - t0) // 1000] for name, a, b in span.stamps
            ]
        fields["id"] = span.id
        fields["parent"] = span.parent
        with self._lock:
            i = self._seq
            self._seq = i + 1
            t_ns = time.monotonic_ns()
            fields["dur_ns"] = dur_ns = t_ns - span.t0_ns
            self._buf[i % self.size] = (i, t_ns, span.kind, fields)
        return dur_ns

    def record_sampled(self, kind: str, **fields) -> None:
        """1-in-N recording for high-rate kinds (gossip.wakeup fires per
        wakeup — at committee scale it can evict the whole ring between
        commits).  The stored event carries `sampled=N` so consumers can
        re-scale counts; N=1 is a plain record (small-net default)."""
        if not self.enabled:
            return
        n = self.sample_high_rate
        if n <= 1:
            self.record(kind, **fields)
            return
        with self._lock:
            c = self._sample_counts.get(kind, 0) + 1
            self._sample_counts[kind] = 0 if c >= n else c
            if c != 1:  # store the 1st of every N
                return
            fields["sampled"] = n
            i = self._seq
            self._seq = i + 1
            self._buf[i % self.size] = (i, time.monotonic_ns(), kind, fields)

    def events(self, since: int = 0, kinds: Optional[Sequence[str]] = None) -> List[dict]:
        """Events still in the ring with seq >= since, oldest first.
        `kinds` filters by prefix match (["gossip.", "step"] keeps every
        gossip event and the step transitions).  Costs what it returns: a
        poller that passes its watermark copies only what is new."""
        size = self.size
        with self._lock:
            end = self._seq
            start = max(since, end - size, 0)
            if start >= end:
                return []
            lo, hi = start % size, end % size
            raw = self._buf[lo:hi] if lo < hi else self._buf[lo:] + self._buf[:hi]
        pref = tuple(kinds) if kinds else None
        return [
            {"seq": seq, "t_ns": t_ns, "kind": kind, **fields}
            for seq, t_ns, kind, fields in raw
            if pref is None or kind.startswith(pref)
        ]

    def snapshot(self, since: int = 0, kinds: Optional[Sequence[str]] = None) -> dict:
        """The dump_flight_recorder RPC payload.  `next_seq` lets a poller
        pass it back as `since` to stream only fresh events; dropped =
        events that aged out of the ring before this snapshot.  `anchor`
        is RE-SAMPLED here (monotonic and wall read back-to-back) so a
        long-lived node's dump carries a fresh mapping — NTP slew between
        start and dump would otherwise skew cross-node alignment."""
        events = self.events(since, kinds)
        mono = time.monotonic_ns()
        wall = self._wall_ns_fn()
        return {
            "enabled": self.enabled,
            "size": self.size,
            "next_seq": self._seq,
            "since": since,
            "dropped": max(0, self._seq - self.size),
            "anchor": {"mono_ns": mono, "wall_ns": wall},
            "events": events,
        }

    @property
    def dropped(self) -> int:
        """Events that have aged out of the ring since start — the silent-
        span-loss number `tendermint_recorder_dropped_total` exports and
        `trace --check` warns about."""
        return max(0, self._seq - self.size)


def _pctl(xs: List[float], q: float) -> float:
    """Nearest-rank percentile (sorted copy; 0 on empty)."""
    xs = sorted(xs)
    if not xs:
        return 0.0
    return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]


def step_chains(events: List[dict]) -> dict:
    """Group `step` events into per-height chains: {height: {step_name:
    first_t_ns}}.  The shared consumer for the bench breakdown, the
    trace-smoke check and the CLI — one definition of "a block's span
    chain" everywhere."""
    chains: dict = {}
    for ev in events:
        if ev.get("kind") != "step":
            continue
        chains.setdefault(ev["height"], {}).setdefault(ev["step"], ev["t_ns"])
    return chains


#: The steps every committed height must pass through, in order.  Wait
#: steps (PrevoteWait/PrecommitWait) and extra rounds are optional.
REQUIRED_STEPS = ("Propose", "Prevote", "Precommit", "Commit")


def complete_heights(chains: dict) -> List[int]:
    """Heights with a full propose→commit chain, ascending."""
    return sorted(
        h for h, steps in chains.items() if all(s in steps for s in REQUIRED_STEPS)
    )


def span_report(events: List[dict], dropped: int = 0, since: int = 0) -> dict:
    """Classify every interior recorded height's span chain:

      complete   — full propose→commit chain present
      truncated  — missing steps are exactly a PREFIX of the required
                   chain while the ring wrapped (dropped > 0) or the dump
                   was watermarked (since > 0): eviction is strictly
                   oldest-first, so a busy ring legitimately ages out the
                   EARLY steps of a height whose commit is still fresh.
                   Not a failure — `trace --check` used to hard-fail here,
                   which made it useless exactly on the busy nets it is
                   for.
      bad        — {height: missing_steps} with a mid-chain or suffix
                   hole: a LATER step present while an earlier one is
                   missing cannot be eviction (later events are newer) and
                   is a real instrumentation/consensus bug.

    Edge heights (first/last recorded) are excluded as before — startup
    and the dump instant truncate them trivially."""
    chains = step_chains(events)
    heights = sorted(chains)
    interior = heights[1:-1]
    wrapped = (dropped or 0) > 0 or (since or 0) > 0
    complete: List[int] = []
    truncated: List[int] = []
    bad: dict = {}
    for h in interior:
        steps = chains[h]
        missing = [s for s in REQUIRED_STEPS if s not in steps]
        if not missing:
            complete.append(h)
        elif wrapped and tuple(missing) == REQUIRED_STEPS[: len(missing)]:
            truncated.append(h)
        else:
            bad[h] = missing
    return {"complete": complete, "truncated": truncated, "bad": bad,
            "interior": len(interior)}


def block_breakdown(events: List[dict]) -> Optional[dict]:
    """Median per-step milliseconds across every complete span chain in
    the event stream: how long each height sat in Propose / Prevote /
    Precommit, commit→next-height turnaround, and total block time
    (propose(h) → propose(h+1)).  None when fewer than 2 complete,
    consecutive chains exist."""
    chains = step_chains(events)
    heights = complete_heights(chains)
    propose_ms, prevote_ms, precommit_ms, commit_ms, block_ms = [], [], [], [], []
    for h in heights:
        steps = chains[h]
        propose_ms.append((steps["Prevote"] - steps["Propose"]) / 1e6)
        prevote_ms.append((steps["Precommit"] - steps["Prevote"]) / 1e6)
        precommit_ms.append((steps["Commit"] - steps["Precommit"]) / 1e6)
        nxt = chains.get(h + 1)
        if nxt and "Propose" in nxt:
            commit_ms.append((nxt["Propose"] - steps["Commit"]) / 1e6)
            block_ms.append((nxt["Propose"] - steps["Propose"]) / 1e6)
    if not block_ms:
        return None

    def med(xs: List[float]) -> float:
        xs = sorted(xs)
        return xs[len(xs) // 2]

    return {
        "source": "flight_recorder",
        "blocks": len(block_ms),
        "propose_ms": round(med(propose_ms), 3),
        "prevote_ms": round(med(prevote_ms), 3),
        "precommit_ms": round(med(precommit_ms), 3),
        "commit_ms": round(med(commit_ms), 3),
        "block_ms": round(med(block_ms), 3),
    }


#: Stage names of the consensus latency budget, in pipeline order.
#: commit_persist = enter Commit → delivery handoff (block save + WAL
#: ENDHEIGHT); finalize = the ABCI delivery span (begin/deliver_tx/end/
#: commit + events), which overlaps the next height when the pipeline is
#: on; next_propose = Commit(H) → Propose(H+1), the height turnaround.
BUDGET_STAGES = (
    "propose", "prevote", "precommit", "commit_persist", "finalize", "next_propose",
)


def stage_budget(events: List[dict]) -> Optional[dict]:
    """Decompose committed heights into a per-stage latency budget from
    flight-recorder spans: propose→prevote→precommit→commit(persist)→
    finalize(deliver)→next-propose, plus commit-to-commit percentiles —
    the `trace budget` report (per-stage methodology after
    arXiv:2302.00418 §5).  Uses the `step` chains for the vote stages and
    the `deliver.start`/`deliver.end` span for ABCI delivery, so the same
    report attributes time in both serial and pipelined modes.  None when
    fewer than 2 complete consecutive chains exist."""
    chains = step_chains(events)
    heights = complete_heights(chains)
    deliver_start: dict = {}
    deliver_end: dict = {}
    for ev in events:
        k = ev.get("kind")
        if k == "deliver.start":
            deliver_start.setdefault(ev["height"], ev["t_ns"])
        elif k == "deliver.end":
            deliver_end[ev["height"]] = ev["t_ns"]
    stages: dict = {name: [] for name in BUDGET_STAGES}
    c2c: List[float] = []
    for h in heights:
        steps = chains[h]
        stages["propose"].append((steps["Prevote"] - steps["Propose"]) / 1e6)
        stages["prevote"].append((steps["Precommit"] - steps["Prevote"]) / 1e6)
        stages["precommit"].append((steps["Commit"] - steps["Precommit"]) / 1e6)
        ds, de = deliver_start.get(h), deliver_end.get(h)
        if ds is not None:
            stages["commit_persist"].append((ds - steps["Commit"]) / 1e6)
            if de is not None:
                stages["finalize"].append((de - ds) / 1e6)
        nxt = chains.get(h + 1)
        if nxt and "Propose" in nxt:
            stages["next_propose"].append((nxt["Propose"] - steps["Commit"]) / 1e6)
        if nxt and "Commit" in nxt:
            c2c.append((nxt["Commit"] - steps["Commit"]) / 1e6)
    if not c2c:
        return None
    out: dict = {"source": "flight_recorder", "blocks": len(c2c), "stages": {}}
    for name in BUDGET_STAGES:
        xs = stages[name]
        if xs:
            out["stages"][name] = {
                "n": len(xs),
                "p50_ms": round(_pctl(xs, 0.5), 3),
                "p90_ms": round(_pctl(xs, 0.9), 3),
                "max_ms": round(max(xs), 3),
            }
    out["commit_to_commit_p50_ms"] = round(_pctl(c2c, 0.5), 3)
    out["commit_to_commit_p90_ms"] = round(_pctl(c2c, 0.9), 3)
    return out


def format_budget(budget: Optional[dict]) -> str:
    """Aligned table rendering of a stage_budget dict (`trace --budget`)."""
    if budget is None:
        return "no complete consecutive span chains — nothing to budget"
    lines = [
        f"latency budget over {budget['blocks']} blocks  "
        f"(commit-to-commit p50 {budget['commit_to_commit_p50_ms']} ms, "
        f"p90 {budget['commit_to_commit_p90_ms']} ms)",
        f"  {'stage':<15}{'n':>5}{'p50 ms':>10}{'p90 ms':>10}{'max ms':>10}",
    ]
    for name in BUDGET_STAGES:
        st = budget["stages"].get(name)
        if st is None:
            continue
        lines.append(
            f"  {name:<15}{st['n']:>5}{st['p50_ms']:>10.3f}"
            f"{st['p90_ms']:>10.3f}{st['max_ms']:>10.3f}"
        )
    return "\n".join(lines)


#: The rows of the replay budget, outermost first: what tiles a block's
#: interval (the wait since the block before, then the in-span stages),
#: apply_block's stages inside apply_ms, the ABCI calls' split into the app's
#: time and the client's inside deliver_ms, validate_block's inside validate_ms
#: (commit_hashes and set_hashes are counts of roots built, not milliseconds),
#: the store's counts of encodings made (commit_encodes inside store_ms,
#: set_encodes inside save_state_ms) and of the responses the save walked
#: with asdict (responses_generic inside deliver_ms), the two commit
#: verifications inside verify_ms and validate_ms, the engine's calls inside
#: those, and what the receive path measured before the block was queued.
REPLAY_ROWS = (
    ("fastsync.block", ("wait_ms", "parts_ms", "verify_ms", "store_ms", "apply_ms")),
    ("fastsync.block", ("validate_ms", "abci_req_ms", "deliver_ms", "mempool_ms",
                        "save_state_ms", "events_ms")),
    ("fastsync.block", ("app_ms", "abci_overhead_ms")),
    ("fastsync.block", ("basic_ms", "commit_hashes", "set_hash_ms", "set_hashes", "median_ms")),
    ("fastsync.block", ("commit_encodes", "set_encodes", "responses_generic")),
    ("verify.commit", ("sign_bytes_ms", "engine_ms", "tally_ms")),
    ("verify.dispatch", ("rows_ms", "host_prep_ms", "pack_ms", "launch_ms", "put_ms",
                         "fetch_ms")),
    ("fastsync.block", ("decode_ms", "download_ms", "queued_ms")),
    ("fastsync.block", ("db_ms", "db_ms.app", "db_ms.blockstore", "db_ms.state",
                        "db_ms.tx_index", "db_max_ms", "db_txns", "txs_indexed", "index_lag")),
)


def replay_budget(events: List[dict]) -> Optional[dict]:
    """Where a joining node's blocks spend their milliseconds, from the
    `fastsync.block` span chain: per block applied, each stage's mean, p50
    and p90, a child span's fields summed over that block's children (two
    commits, two dispatches).  `interval_ms` is the mean of a block's length
    plus its wait: the block interval the replay loop saw.  None without a
    `fastsync.block` event."""
    blocks = [ev for ev in events if ev.get("kind") == "fastsync.block"]
    if not blocks:
        return None
    heights = {ev["id"] for ev in blocks}
    children: dict = {}  # (kind, height) -> its events
    for ev in events:
        if ev.get("kind") in ("verify.commit", "verify.dispatch") and ev.get("id") in heights:
            children.setdefault((ev["kind"], ev["id"]), []).append(ev)
    out: dict = {
        "source": "flight_recorder", "blocks": len(blocks),
        "heights": [blocks[0]["id"], blocks[-1]["id"]],
        "interval_ms": round(
            sum(ev["dur_ns"] / 1e6 + ev.get("wait_ms", 0.0) for ev in blocks) / len(blocks), 3),
        "stages": {},
    }
    commits = [ev for (kind, _), evs in children.items() if kind == "verify.commit" for ev in evs]
    if commits:
        # of the messages the commits verified, how many were built from a
        # per-commit template (Commit.vote_batch): all of them
        out["commit_messages"] = {
            "n": sum(ev.get("n", 0) for ev in commits),
            "templated": sum(ev.get("templated", 0) for ev in commits),
        }
    dispatches = [
        ev for (kind, _), evs in children.items() if kind == "verify.dispatch" for ev in evs
        if "shard_n" in ev
    ]
    if dispatches:
        # where the blocks' device dispatches ran: by inner verify and by how
        # many devices each was split over, and the useful share of the rows
        # of the shard that held fewest (the mesh's padding lands on the
        # last; a chunked dispatch's `bucket` is one chunk)
        placed: dict = {}
        for ev in dispatches:
            where = f"{ev.get('kernel')} x{ev['shards']}"
            if "device" in ev:
                where += f" (device {ev['device']})"
            placed[where] = placed.get(where, 0) + 1
        out["dispatch_placement"] = placed
        out["min_shard_fill"] = round(min(
            min(ev["shard_n"]) * ev["shards"] / (
                ev["bucket"] * (-(-ev["n"] // ev["bucket"]) if ev["path"] == "chunked" else 1))
            for ev in dispatches), 4)
    rows = [("block_ms", [ev["dur_ns"] / 1e6 for ev in blocks])]
    for kind, names in REPLAY_ROWS:
        own = kind == "fastsync.block"
        prefix = "" if own else kind.split(".")[1] + "."
        for name in names:
            rows.append((prefix + name, [
                sum(e.get(name, 0.0) for e in ((ev,) if own else children.get((kind, ev["id"]), ())))
                for ev in blocks
            ]))
    for name, xs in rows:
        if any(xs):
            out["stages"][name] = {
                "mean_ms": round(sum(xs) / len(xs), 3),
                "p50_ms": round(_pctl(xs, 0.5), 3), "p90_ms": round(_pctl(xs, 0.9), 3),
            }
    return out


def format_replay_budget(budget: Optional[dict]) -> str:
    """Aligned rendering of a replay_budget dict (`trace --replay`)."""
    if budget is None:
        return "no fastsync.block span — nothing to budget (the node is not fast-syncing)"
    lines = [
        f"replay budget over {budget['blocks']} blocks (heights {budget['heights'][0]}.."
        f"{budget['heights'][1]}), block interval {budget['interval_ms']} ms",
        f"  {'stage, per block':<26}{'mean ms':>10}{'p50 ms':>10}{'p90 ms':>10}",
    ]
    if "commit_messages" in budget:
        msgs = budget["commit_messages"]
        lines.insert(1, f"  commit messages: {msgs['templated']} of {msgs['n']} from a template")
    if "dispatch_placement" in budget:
        placed = ", ".join(f"{n} on {where}" for where, n in budget["dispatch_placement"].items())
        lines.insert(1, f"  device dispatches: {placed}; useful rows in the least-filled "
                        f"shard {100 * budget['min_shard_fill']:.1f}%")
    for name, st in budget["stages"].items():
        lines.append(
            f"  {name:<26}{st['mean_ms']:>10.3f}{st['p50_ms']:>10.3f}{st['p90_ms']:>10.3f}"
        )
    return "\n".join(lines)


#: Stage names of the cross-node network budget, in dissemination order.
#: proposal_prop = wire propagation latency of received proposal frames
#: (origin send stamp → our receive, measured — needs gossip_version 3
#: peers); part_stream = first sign of the block (proposal accepted or
#: first part hop) → part set complete; vote_fanin = first vote activity
#: for the height (Prevote entry or first vote_batch received) → Commit
#: entry (+2/3 precommits held).
NET_BUDGET_STAGES = ("proposal_prop", "part_stream", "vote_fanin")


def net_budget(events: List[dict]) -> Optional[dict]:
    """The cross-node sibling of stage_budget: from ONE node's flight
    recorder alone, attribute where inter-node time goes per height —
    proposal propagation, part-stream completion, vote fan-in to quorum —
    plus per-frame-kind hop-count and propagation-latency distributions
    from the wire-level trace context (`gossip.hop`, gossip_version >= 3).
    The budget stages work on any net (they only need step/proposal/
    parts_complete events); the hop/latency sections need traced peers.
    Surfaced as `debug trace --net-budget` and folded into the smokes'
    JSON.  None when no height has enough events for any stage."""
    chains = step_chains(events)
    proposal_t: dict = {}        # height -> first proposal-accepted t_ns
    parts_done_t: dict = {}      # height -> parts_complete t_ns
    first_part_t: dict = {}      # height -> first block_part hop t_ns
    first_batch_t: dict = {}     # height -> first vote_batch_recv t_ns
    hops: dict = {}              # frame kind -> [hop counts]
    hop_lat: dict = {}           # frame kind -> [lat_ms]
    prop_lat: dict = {}          # height -> [proposal-frame lat_ms]
    clamped = 0
    for ev in events:
        k = ev.get("kind")
        if k == "proposal":
            proposal_t.setdefault(ev["height"], ev["t_ns"])
        elif k == "block.parts_complete":
            parts_done_t.setdefault(ev["height"], ev["t_ns"])
        elif k == "gossip.vote_batch_recv":
            h = ev.get("h")
            if h is not None:
                first_batch_t.setdefault(h, ev["t_ns"])
        elif k == "gossip.hop":
            frame = ev.get("frame", "?")
            if ev.get("clamped"):
                clamped += 1
            else:
                hops.setdefault(frame, []).append(ev.get("hop", 0))
                lat = ev.get("lat_ms")
                if lat is not None:
                    hop_lat.setdefault(frame, []).append(lat)
                    if frame == "proposal" and ev.get("h") is not None:
                        prop_lat.setdefault(ev["h"], []).append(lat)
            if frame == "block_part" and ev.get("h") is not None:
                first_part_t.setdefault(ev["h"], ev["t_ns"])
    stages: dict = {name: [] for name in NET_BUDGET_STAGES}
    heights: List[int] = []
    for h in sorted(set(chains) | set(parts_done_t) | set(prop_lat)):
        used = False
        for lat in prop_lat.get(h, ()):
            stages["proposal_prop"].append(lat)
            used = True
        done = parts_done_t.get(h)
        if done is not None:
            starts = [t for t in (proposal_t.get(h), first_part_t.get(h)) if t is not None]
            if starts and done >= min(starts):
                stages["part_stream"].append((done - min(starts)) / 1e6)
                used = True
        steps = chains.get(h, {})
        quorum = steps.get("Commit")
        if quorum is not None:
            starts = [t for t in (steps.get("Prevote"), first_batch_t.get(h)) if t is not None]
            if starts and quorum >= min(starts):
                stages["vote_fanin"].append((quorum - min(starts)) / 1e6)
                used = True
        if used:
            heights.append(h)
    if not heights and not hops:
        return None

    def dist(xs: List[float]) -> dict:
        return {
            "n": len(xs),
            "p50": round(_pctl(xs, 0.5), 3),
            "p90": round(_pctl(xs, 0.9), 3),
            "max": round(max(xs), 3) if xs else 0.0,
        }

    out: dict = {
        "source": "flight_recorder",
        "blocks": len(heights),
        "heights": [heights[0], heights[-1]] if heights else [],
        "stages": {},
        "hops": {},
        "hop_lat_ms": {},
        "clamped": clamped,
    }
    for name in NET_BUDGET_STAGES:
        xs = stages[name]
        if xs:
            d = dist(xs)
            out["stages"][name] = {
                "n": d["n"], "p50_ms": d["p50"], "p90_ms": d["p90"], "max_ms": d["max"],
            }
    for frame, xs in sorted(hops.items()):
        out["hops"][frame] = dist([float(x) for x in xs])
    for frame, xs in sorted(hop_lat.items()):
        out["hop_lat_ms"][frame] = dist(xs)
    pooled = [x for xs in hop_lat.values() for x in xs]
    if pooled:
        # frame-agnostic propagation latency: the bench `gossip_hop_p90_ms`
        # number and the telescope's fleet hop-latency line
        out["hop_lat_all_ms"] = dist(pooled)
    return out


def format_net_budget(budget: Optional[dict]) -> str:
    """Aligned rendering of a net_budget dict (`trace --net-budget`)."""
    if budget is None:
        return "no network-plane events — nothing to budget"
    span = (
        f" (heights {budget['heights'][0]}..{budget['heights'][1]})"
        if budget.get("heights") else ""
    )
    lines = [
        f"network budget over {budget['blocks']} blocks{span}",
        f"  {'stage':<15}{'n':>5}{'p50 ms':>10}{'p90 ms':>10}{'max ms':>10}",
    ]
    for name in NET_BUDGET_STAGES:
        st = budget["stages"].get(name)
        if st is None:
            continue
        lines.append(
            f"  {name:<15}{st['n']:>5}{st['p50_ms']:>10.3f}"
            f"{st['p90_ms']:>10.3f}{st['max_ms']:>10.3f}"
        )
    if budget["hops"]:
        lines.append(f"  {'hop counts':<15}{'n':>5}{'p50':>10}{'p90':>10}{'max':>10}")
        for frame, d in budget["hops"].items():
            lines.append(
                f"  {frame:<15}{d['n']:>5}{d['p50']:>10.1f}{d['p90']:>10.1f}{d['max']:>10.1f}"
            )
    if budget["hop_lat_ms"]:
        lines.append(f"  {'hop lat ms':<15}{'n':>5}{'p50':>10}{'p90':>10}{'max':>10}")
        for frame, d in budget["hop_lat_ms"].items():
            lines.append(
                f"  {frame:<15}{d['n']:>5}{d['p50']:>10.3f}{d['p90']:>10.3f}{d['max']:>10.3f}"
            )
        d = budget.get("hop_lat_all_ms")
        if d:
            lines.append(
                f"  {'(all frames)':<15}{d['n']:>5}{d['p50']:>10.3f}"
                f"{d['p90']:>10.3f}{d['max']:>10.3f}"
            )
    if budget.get("clamped"):
        lines.append(f"  clamped trace fields: {budget['clamped']} (excluded above)")
    return "\n".join(lines)


#: The statesync bootstrap chain every snapshot restore must record, in
#: order — the statesync-smoke acceptance gate.
STATESYNC_CHAIN = ("statesync.offer", "statesync.chunk", "statesync.restore", "statesync.handover")


def statesync_bootstrap_ms(events: List[dict]) -> Optional[float]:
    """Wall milliseconds from the (first) snapshot offer to the fastsync
    handover, measured from real recorder spans — the number bench.py
    reports as `statesync_bootstrap_ms`.  None unless the full
    offer→chunk→restore→handover chain is present in order."""
    first: dict = {}
    last: dict = {}
    for ev in events:
        k = ev.get("kind")
        if k in STATESYNC_CHAIN:
            first.setdefault(k, ev["t_ns"])
            last[k] = ev["t_ns"]
    if any(k not in first for k in STATESYNC_CHAIN):
        return None
    o, c, r, h = (first[STATESYNC_CHAIN[0]], first[STATESYNC_CHAIN[1]],
                  last[STATESYNC_CHAIN[2]], last[STATESYNC_CHAIN[3]])
    if not (o <= c <= r <= h):
        return None
    return (h - o) / 1e6


# -- crash-persistent flight spool ------------------------------------------


class FlightSpool:
    """Crash-persistent sink for a FlightRecorder: an append-only rotating
    on-disk journal ([instrumentation] flight_spool) so a SIGKILLed, OOMed
    or wedged node leaves its last seconds of span events on disk.

    Discipline mirrors the mempool tx WAL (libs/autofile.Group): a head
    file plus rotated chunks, total size bounded by `size_limit` (oldest
    chunks deleted first — eviction is oldest-first, exactly like the
    in-memory ring, so `span_report`'s prefix-truncation tolerance applies
    to spool replays too).  Records are JSON lines:

        {"type": "anchor", "mono_ns", "wall_ns", "node", "lost"}   per flush
        {"seq", "t_ns", "kind", ...fields}                         per event

    The anchor line is re-sampled every flush so an offline replay carries
    a fresh monotonic→wall mapping for tracemerge alignment; `lost` counts
    events that aged out of the RING between flushes (the spool's own
    watermark fell behind) — honest about what the disk copy is missing.

    Crucially NOTHING here runs on the recording hot path: `record()` is
    untouched, the spool reads the ring from a flush cadence (the node's
    spool task), from the excepthook/atexit crash hooks, and from close().
    A SIGKILL cannot be caught — for it, the periodic cadence is the
    guarantee: everything up to the last flush (≤ flush_interval old)
    survives.  Flush is threadsafe (task + atexit may race)."""

    def __init__(
        self,
        path: str,
        recorder: FlightRecorder,
        size_limit: int = 4 * 1024 * 1024,
        node: str = "",
    ):
        from .autofile import Group

        self.recorder = recorder
        self.node = node
        self._group = Group(
            path,
            head_size_limit=max(4096, size_limit // 4),
            group_size_limit=size_limit,
        )
        self._watermark = 0  # next recorder seq to spool
        self._lock = threading.Lock()
        self._closed = False
        self._hooks_installed = False
        self._prev_excepthook = None
        self._hook_fn = None
        # run id: the spool file survives restarts (append-mode head) but
        # recorder seqs restart at 0 per process — without a per-run tag
        # the replay's seq-dedup would keep the OLD run's events and
        # present the previous run as the pre-crash evidence
        self.run_id = os.urandom(4).hex()
        self.flushes = 0
        self.spooled = 0
        self.lost = 0  # ring-wrap losses between flushes, cumulative

    def flush(self, sync: bool = False) -> int:
        """Append every ring event past the watermark; returns the number
        written.  `sync=True` adds an fsync (crash hooks / close)."""
        with self._lock:
            if self._closed:
                return 0
            events = self.recorder.events(since=self._watermark)
            lost = 0
            if events and events[0]["seq"] > self._watermark and self._watermark > 0:
                lost = events[0]["seq"] - self._watermark
            elif not events:
                # ring may have wrapped past the watermark with everything
                # already evicted (huge burst between flushes)
                lost = max(0, self.recorder._seq - self.recorder.size - self._watermark)
                if lost == 0 and self.recorder._seq == self._watermark:
                    return 0  # nothing new; skip the anchor line too
            self.lost += lost
            lines = [
                json.dumps(
                    {
                        "type": "anchor",
                        "run": self.run_id,
                        "mono_ns": time.monotonic_ns(),
                        "wall_ns": self.recorder._wall_ns_fn(),
                        "node": self.node,
                        "lost": self.lost,
                    },
                    separators=(",", ":"),
                )
            ]
            for ev in events:
                lines.append(json.dumps(ev, separators=(",", ":"), default=repr))
            self._group.write(("\n".join(lines) + "\n").encode())
            if sync:
                self._group.sync()
            else:
                self._group.flush()
            self._group.maybe_rotate()
            # enforce the size cap on EVERY flush, not only at rotation:
            # Group defers enforcement to rotate(), which lets the total
            # overshoot by up to a head file between rotations — the
            # spool's contract is a hard disk bound
            self._group._enforce_group_limit()
            if events:
                self._watermark = events[-1]["seq"] + 1
            else:
                self._watermark = self.recorder._seq
            self.flushes += 1
            self.spooled += len(events)
            return len(events)

    def install_crash_hooks(self) -> None:
        """Flush on interpreter exit and on an unhandled exception — the
        crash classes a periodic task never gets to run for.  (SIGINT/
        SIGTERM go through node.stop → close(); SIGKILL is covered only by
        the cadence.)"""
        if self._hooks_installed:
            return
        import atexit
        import sys

        atexit.register(self._crash_flush)
        self._prev_excepthook = sys.excepthook

        def hook(exc_type, exc, tb):
            self._crash_flush()
            (self._prev_excepthook or sys.__excepthook__)(exc_type, exc, tb)

        sys.excepthook = hook
        self._hook_fn = hook
        self._hooks_installed = True

    def remove_crash_hooks(self) -> None:
        if not self._hooks_installed:
            return
        import atexit
        import sys

        atexit.unregister(self._crash_flush)
        # restore only if OUR hook object is still installed — another
        # spool's hook (in-proc multi-node) or anything chained on top
        # must not be uninstalled out from under its owner
        if sys.excepthook is self._hook_fn and self._prev_excepthook is not None:
            sys.excepthook = self._prev_excepthook
        self._hooks_installed = False

    def _crash_flush(self) -> None:
        try:
            self.flush(sync=True)
        except Exception:  # noqa: BLE001 — never mask the original crash
            pass

    def close(self) -> None:
        self.flush(sync=True)
        with self._lock:
            self._closed = True
            self._group.close()
        self.remove_crash_hooks()


def spool_paths(head_path: str) -> List[str]:
    """Rotated chunks (oldest first) + head — the on-disk read order for a
    spool at `head_path`.  Standalone (no Group): reading a dead node's
    spool must not open-for-append or touch the files."""
    d = os.path.dirname(head_path) or "."
    base = os.path.basename(head_path)
    pat = re.compile(re.escape(base) + r"\.(\d{3,})$")
    chunks = []
    try:
        for name in os.listdir(d):
            m = pat.match(name)
            if m:
                chunks.append((int(m.group(1)), os.path.join(d, name)))
    except FileNotFoundError:
        return []
    out = [p for _, p in sorted(chunks)]
    if os.path.exists(head_path):
        out.append(head_path)
    return out


def read_spool(path: str, name: str = "") -> dict:
    """Offline spool replay → a dump-shaped dict (the same shape
    `dump_flight_recorder` serves), so tracemerge / span_report / `debug
    dump` work on a DEAD node's disk exactly like on a live node's RPC.

    Torn-tail tolerant: a process killed mid-append leaves a partial (or
    otherwise undecodable) final line — it is skipped and counted in
    `torn`, and every decodable record before it is kept, the same
    retained-suffix discipline as the mempool WAL replay.  `dropped`
    reports events known to be missing from the replay (ring-wrap losses
    recorded by the writer, rotated-away chunks, torn lines) so
    span_report can classify prefix-truncated heights honestly.

    The spool file survives restarts while recorder seqs restart at 0 per
    process, so anchors carry a per-spool-session `run` id and the replay
    SEGREGATES runs, returning the NEWEST (the crash under investigation —
    earlier runs' events would otherwise collide on seq and replace the
    evidence with stale data); `runs` reports how many sessions the file
    holds."""
    # per-run collection, runs in first-appearance (= file/time) order
    run_events: "dict[str, dict]" = {}  # run -> {"events": {seq: ev}, "anchor", "node", "lost"}
    run_order: List[str] = []
    current: Optional[str] = None
    pending: List[dict] = []  # events before the first surviving anchor
    torn = 0

    def _bucket(run: str) -> dict:
        if run not in run_events:
            run_events[run] = {"events": {}, "anchor": None, "node": "", "lost": 0}
            run_order.append(run)
        return run_events[run]

    for p in spool_paths(path):
        try:
            with open(p, "rb") as fh:
                data = fh.read()
        except OSError:
            continue
        for raw in data.split(b"\n"):
            if not raw:
                continue
            try:
                rec = json.loads(raw)
            except (ValueError, UnicodeDecodeError):
                torn += 1
                continue
            if not isinstance(rec, dict):
                torn += 1
                continue
            if rec.get("type") == "anchor":
                current = str(rec.get("run", ""))
                b = _bucket(current)
                b["anchor"] = {"mono_ns": rec.get("mono_ns", 0),
                               "wall_ns": rec.get("wall_ns", 0)}
                b["node"] = rec.get("node") or b["node"]
                b["lost"] = max(b["lost"], int(rec.get("lost", 0) or 0))
                if pending:
                    # events whose own anchor was rotated away belong to
                    # the run of the FIRST surviving anchor (each flush
                    # batch is anchor-first, so only a truncated batch
                    # head lands here)
                    for ev in pending:
                        b["events"].setdefault(ev["seq"], ev)
                    pending = []
            elif "seq" in rec and "kind" in rec:
                if current is None:
                    pending.append(rec)
                else:
                    run_events[current]["events"].setdefault(rec["seq"], rec)
            else:
                torn += 1
    if pending and not run_order:
        _bucket("")["events"].update({ev["seq"]: ev for ev in pending})
    # the NEWEST run is the one being investigated
    chosen = run_events[run_order[-1]] if run_order else {
        "events": {}, "anchor": None, "node": "", "lost": 0}
    events = sorted(chosen["events"].values(), key=lambda ev: ev["seq"])
    anchor, node, lost = chosen["anchor"], chosen["node"], chosen["lost"]
    # seq holes in the replay cover every loss class at once: events never
    # spooled (ring wrap — the writer's `lost` counter), rotated-away
    # chunks, and pre-spool ring history; `first` is the evicted prefix
    gaps = 0
    for a, b in zip(events, events[1:]):
        gaps += max(0, b["seq"] - a["seq"] - 1)
    first = events[0]["seq"] if events else 0
    return {
        "enabled": True,
        "source": "spool",
        "node": name or node or os.path.splitext(os.path.basename(path))[0],
        "size": len(events),
        "next_seq": (events[-1]["seq"] + 1) if events else 0,
        "since": 0,
        "dropped": first + gaps + torn,
        "torn": torn,
        "writer_lost": lost,
        "runs": len(run_order),
        "anchor": anchor,
        "events": events,
    }
