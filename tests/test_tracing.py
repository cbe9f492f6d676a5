"""Flight recorder (libs/tracing.py): ring semantics, overhead budget,
span-chain analysis, the dump_flight_recorder RPC route and the
verify-engine event stream."""

import asyncio
import os
import time

import pytest

from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.tracing import FlightRecorder, NopRecorder


class TestRing:
    def test_wraps_and_keeps_last_size_events(self):
        r = FlightRecorder(size=8)
        for i in range(20):
            r.record("step", height=i)
        evs = r.events()
        assert len(evs) == 8
        assert [e["height"] for e in evs] == list(range(12, 20))
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)

    def test_t_ns_monotonic(self):
        r = FlightRecorder(size=64)
        for i in range(32):
            r.record("step", height=i)
        ts = [e["t_ns"] for e in r.events()]
        assert all(a <= b for a, b in zip(ts, ts[1:]))

    def test_since_watermark(self):
        r = FlightRecorder(size=64)
        for i in range(10):
            r.record("step", height=i)
        snap = r.snapshot()
        assert snap["next_seq"] == 10 and snap["dropped"] == 0
        r.record("step", height=10)
        fresh = r.events(since=snap["next_seq"])
        assert [e["height"] for e in fresh] == [10]

    def test_events_since_costs_and_returns_what_a_full_walk_would(self):
        """events(since) indexes the ring from max(since, seq - size): the
        same answer as walking and sorting all of it, before the ring is
        full, across the wrap and far beyond it."""

        def full_walk(r, since, kinds):
            pref = tuple(kinds) if kinds else None
            kept = sorted(
                (ev for ev in r._buf if ev is not None and ev[0] >= since
                 and (pref is None or ev[2].startswith(pref))),
                key=lambda ev: ev[0],
            )
            return [{"seq": q, "t_ns": t, "kind": k, **f} for q, t, k, f in kept]

        r = FlightRecorder(size=8)
        for i in range(21):
            r.record("step" if i % 3 else "verify.flush", height=i)
            for since in (0, i - 9, i - 3, i, i + 1, i + 5):
                for kinds in (None, ["verify."]):
                    assert r.events(max(since, 0), kinds) == full_walk(r, max(since, 0), kinds)
        assert [e["seq"] for e in r.events(since=18)] == [18, 19, 20]
        assert r.events(since=21) == []

    def test_disabled_and_nop_record_nothing(self):
        for r in (FlightRecorder(size=8, enabled=False), NopRecorder()):
            r.record("step", height=1)
            assert r.events() == []
            assert r.snapshot()["enabled"] is False

    def test_record_overhead_budget(self):
        # contract: < 1 us/event enabled; tripwire at 5 us so CI-host
        # noise can't flake the suite while a 10x regression still fails
        r = FlightRecorder(size=4096)
        n = 50_000
        t0 = time.perf_counter()
        for i in range(n):
            r.record("verify.flush", batch=4, wait_ms=0.2, quantum_ms=0.2)
        per_event = (time.perf_counter() - t0) / n
        assert per_event < 5e-6, f"record() took {per_event * 1e6:.2f} us/event"


class _Hist:
    def __init__(self):
        self.seen = []

    def observe(self, v):
        self.seen.append(v)


class TestSpans:
    def test_one_event_when_it_closes_with_the_span_shape(self):
        r = FlightRecorder(size=64)
        t0 = time.monotonic_ns()
        with r.span("fastsync.block", id=7, pending=3) as span:
            assert r.events() == []  # nothing until it closes
            time.sleep(0.002)
            assert span.lap("parts_ms") >= 2.0
            span.lap("verify_ms")
            span.set(peer="abcd")
        t1 = time.monotonic_ns()
        (ev,) = r.events()
        assert ev["kind"] == "fastsync.block" and ev["id"] == 7 and ev["parent"] is None
        assert ev["pending"] == 3 and ev["peer"] == "abcd"
        assert t0 <= ev["t_ns"] - ev["dur_ns"] and ev["t_ns"] <= t1  # t_ns is the end
        # laps tile the span from its start: they sum to no more than it
        assert 2.0 <= ev["parts_ms"] + ev["verify_ms"] <= ev["dur_ns"] / 1e6 + 0.001
        assert ev["parts_ms"] == round(ev["parts_ms"], 3)

    def test_a_lap_taken_twice_sums(self):
        laps = tracing.Laps()
        first = laps.lap("fetch_ms")
        time.sleep(0.001)
        second = laps.lap("fetch_ms")
        assert second >= 1.0
        assert laps.fields == {"fetch_ms": pytest.approx(first + second)}

    def test_a_lap_can_end_at_a_reading_its_callee_took(self):
        laps = tracing.Laps()
        time.sleep(0.001)
        mark = time.monotonic_ns()  # taken inside the callee, where the stage ended
        time.sleep(0.002)
        deliver, rest = laps.lap("deliver_ms", at_ns=mark), laps.lap("mempool_ms")
        assert 1.0 <= deliver < rest and rest >= 2.0
        # a stale reading (the callee was replaced and never took one) is not used
        assert laps.lap("save_state_ms", at_ns=mark) >= 0.0

    def test_nested_spans_and_point_events_carry_parent_and_id(self):
        r = FlightRecorder(size=64)
        with r.span("fastsync.block", id=12):
            with tracing.child_span("verify.commit", height=11) as commit:
                assert commit.recorder is r
                r.record("verify.table", hit=True, n=4)
                with r.span("verify.dispatch", id=999, n=4):  # the root names the id
                    pass
            tracing.annotate(deliver_ms=1.5)
        r.record("verify.table", hit=True, n=4)  # outside: no parent
        table, dispatch, commit, block, outside = r.events()
        assert (table["parent"], table["id"]) == ("verify.commit", 12)
        assert (dispatch["parent"], dispatch["id"]) == ("verify.commit", 12)
        assert (commit["parent"], commit["id"], commit["height"]) == ("fastsync.block", 12, 11)
        assert block["parent"] is None and block["deliver_ms"] == 1.5
        assert "parent" not in outside and "id" not in outside
        assert tracing.current_span() is None
        # self time: a span's length minus its children's
        assert block["dur_ns"] >= commit["dur_ns"] >= dispatch["dur_ns"]

    async def test_parent_and_id_follow_a_task_through_an_await(self):
        r = FlightRecorder(size=64)

        async def replay(height):
            with r.span("fastsync.block", id=height):
                await asyncio.sleep(0.001)  # the other task runs meanwhile
                with tracing.child_span("verify.commit", height=height):
                    await asyncio.sleep(0.001)
                    r.record("verify.table", hit=True, n=1)

        await asyncio.gather(replay(5), replay(6))
        by_kind = {}
        for ev in r.events():
            by_kind.setdefault(ev["kind"], []).append(ev)
        assert sorted(e["id"] for e in by_kind["verify.table"]) == [5, 6]
        assert all(e["parent"] == "verify.commit" for e in by_kind["verify.table"])
        assert all(e["id"] == e["height"] for e in by_kind["verify.commit"])

    async def test_a_task_started_inside_a_span_outlives_it_without_a_parent(self):
        r = FlightRecorder(size=64)
        go = asyncio.Event()

        async def later():
            await go.wait()
            r.record("commit", height=1)
            with r.span("fastsync.block", id=2):
                pass

        with r.span("fastsync.block", id=1):
            task = asyncio.ensure_future(later())  # copies the context, span and all
        go.set()
        await task
        _, point, own = r.events()
        assert "parent" not in point
        assert own["id"] == 2 and own["parent"] is None

    def test_begin_end_and_drop(self):
        r = FlightRecorder(size=64)
        span = r.begin("fastsync.block", id=3)
        assert tracing.current_span() is span
        assert span.end() == r.events()[0]["dur_ns"]
        dropped = r.begin("fastsync.block", id=4)
        dropped.drop()
        assert tracing.current_span() is None
        assert [e["id"] for e in r.events()] == [3]
        span.end()  # closing twice writes nothing more
        assert len(r.events()) == 1

    def test_the_histogram_takes_the_same_reading(self):
        r, hist = FlightRecorder(size=64), _Hist()
        with r.span("verify.commit", hist=hist):
            time.sleep(0.001)
        assert hist.seen == [r.events()[0]["dur_ns"] / 1e9]

    def test_detached_spans_time_their_laps_and_record_nothing(self):
        hist = _Hist()
        for r in (FlightRecorder(size=8, enabled=False), NopRecorder()):
            with r.span("verify.dispatch", hist=hist, n=1) as span:
                time.sleep(0.001)
                assert span.lap("device_ms") >= 1.0  # the other sink still reads it
                assert tracing.current_span() is None  # and it is no one's parent
            assert r.events() == []
        assert len(hist.seen) == 2 and min(hist.seen) >= 0.001
        with tracing.child_span("verify.commit") as span:  # no caller's span to join
            assert span.recorder is None

    def test_live_recorders_holds_the_enabled_ones_weakly(self):
        import gc

        on, off = FlightRecorder(size=8), FlightRecorder(size=8, enabled=False)
        assert on in tracing.live_recorders() and off not in tracing.live_recorders()
        ident = id(on)
        del on
        gc.collect()
        assert ident not in {id(r) for r in tracing.live_recorders()}

    def test_span_overhead_budget(self):
        # contract: a span with two laps costs about three record() calls;
        # tripwire at 15 us (record()'s is 5) so CI-host noise can't flake
        r = FlightRecorder(size=4096)
        n = 20_000
        t0 = time.perf_counter()
        for i in range(n):
            with r.span("fastsync.block", id=i, pending=2) as span:
                span.lap("parts_ms")
                span.lap("verify_ms")
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 15e-6, f"span() took {per_span * 1e6:.2f} us"

    def test_mirrored_on_the_host_plane_of_a_running_profile(self, tmp_path):
        """While a jax.profiler trace runs, a span is a TraceAnnotation of
        the same name: host stages and device ops on one clock."""
        import jax

        from benchmarks import trace as tracelib

        r = FlightRecorder(size=64)
        with r.span("fastsync.block", id=1):
            pass  # no trace running: nothing to mirror
        anchor_ns = tracelib.start(str(tmp_path))
        try:
            with r.span("fastsync.block", id=2):
                with tracing.child_span("verify.commit", height=2):
                    time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        events = tracelib.load_xplane(str(tmp_path))
        shift = anchor_ns - next(ev for ev in events if ev[2] == tracelib.ANCHOR)[3]
        named = {ev[2]: ev for ev in events if ev[2] in ("fastsync.block", "verify.commit")}
        assert set(named) == {"fastsync.block", "verify.commit"}
        assert all(ev[0].startswith("/host:") for ev in named.values())
        assert sum(ev[2] == "fastsync.block" for ev in events) == 1
        # the recorder's monotonic clock and the profile's, anchored, agree
        block = r.events()[-1]
        start_ns = named["fastsync.block"][3] + shift
        assert abs(start_ns - (block["t_ns"] - block["dur_ns"])) < 1e6


class TestSampling:
    def test_one_in_n_with_factor_recorded(self):
        r = FlightRecorder(size=256, sample_high_rate=4)
        for _ in range(16):
            r.record_sampled("gossip.wakeup", peer="ab")
        evs = r.events()
        assert len(evs) == 4  # 1-in-4
        assert all(e["sampled"] == 4 for e in evs)
        # consumers re-scale by the recorded factor
        assert sum(e["sampled"] for e in evs) == 16

    def test_default_factor_preserves_record_everything(self):
        r = FlightRecorder(size=256)  # sample_high_rate=1, the small-net default
        for _ in range(10):
            r.record_sampled("gossip.wakeup", peer="ab")
        evs = r.events()
        assert len(evs) == 10
        assert all("sampled" not in e for e in evs)

    def test_counters_are_per_kind_and_low_rate_kinds_unaffected(self):
        r = FlightRecorder(size=256, sample_high_rate=8)
        for i in range(8):
            r.record_sampled("gossip.wakeup", peer="ab")
            r.record("commit", height=i)  # plain record never sampled
        kinds = [e["kind"] for e in r.events()]
        assert kinds.count("gossip.wakeup") == 1
        assert kinds.count("commit") == 8

    def test_disabled_recorder_samples_nothing(self):
        r = FlightRecorder(size=8, enabled=False, sample_high_rate=4)
        r.record_sampled("gossip.wakeup")
        assert r.events() == []
        NopRecorder().record_sampled("gossip.wakeup")  # must not raise

    def test_factor_must_be_positive(self):
        import pytest

        with pytest.raises(ValueError):
            FlightRecorder(size=8, sample_high_rate=0)


class TestKindsFilterAndAnchor:
    def test_events_kinds_prefix_filter(self):
        r = FlightRecorder(size=64)
        r.record("step", height=1, step="Propose")
        r.record("gossip.wakeup", peer="ab")
        r.record("gossip.votes", n=2)
        r.record("verify.flush", batch=2)
        assert [e["kind"] for e in r.events(kinds=["gossip."])] == [
            "gossip.wakeup", "gossip.votes",
        ]
        assert [e["kind"] for e in r.events(kinds=["step", "verify."])] == [
            "step", "verify.flush",
        ]
        snap = r.snapshot(kinds=["step"])
        assert [e["kind"] for e in snap["events"]] == ["step"]
        assert snap["next_seq"] == 4  # watermark unaffected by the filter

    def test_anchor_present_and_resampled_on_snapshot(self):
        r = FlightRecorder(size=8)
        a1 = r.snapshot()["anchor"]
        assert a1["mono_ns"] >= r.anchor_mono_ns
        assert set(a1) == {"mono_ns", "wall_ns"}
        time.sleep(0.002)
        a2 = r.snapshot()["anchor"]
        # re-sampled at dump time, not the construction-time anchor
        assert a2["mono_ns"] > a1["mono_ns"]

    def test_anchor_wall_fn_pluggable_via_skewed_clock(self):
        from tendermint_tpu.chaos.clock import SkewedClock

        clock = SkewedClock(3.0)
        r = FlightRecorder(size=8, wall_ns_fn=clock.time_ns)
        a = r.snapshot()["anchor"]
        assert abs(a["wall_ns"] - 3_000_000_000 - time.time_ns()) < 1_000_000_000


class TestSpanReport:
    def _events(self, spec):
        """spec: {height: [steps]} recorded in height order."""
        r = FlightRecorder(size=1024)
        for h in sorted(spec):
            for step in spec[h]:
                r.record("step", height=h, round=0, step=step)
        return r.events()

    def test_complete_interior_heights(self):
        evs = self._events({h: list(tracing.REQUIRED_STEPS) for h in (1, 2, 3, 4)})
        rep = tracing.span_report(evs)
        assert rep["complete"] == [2, 3]
        assert rep["truncated"] == [] and rep["bad"] == {}
        assert rep["interior"] == 2

    def test_prefix_hole_is_truncated_when_ring_wrapped(self):
        # height 3 lost its Propose+Prevote to eviction: with dropped>0
        # that is honest ring wrap (oldest-first), NOT a failure — the
        # fix for `trace --check` being useless on busy nets
        spec = {h: list(tracing.REQUIRED_STEPS) for h in (1, 2, 4, 5)}
        spec[3] = list(tracing.REQUIRED_STEPS[2:])
        evs = self._events(spec)
        rep = tracing.span_report(evs, dropped=17)
        assert rep["truncated"] == [3]
        assert rep["bad"] == {}
        assert rep["complete"] == [2, 4]
        # a `since` watermark truncates the same way (dump streamed fresh)
        rep = tracing.span_report(evs, since=5)
        assert rep["truncated"] == [3] and rep["bad"] == {}

    def test_prefix_hole_without_wrap_is_a_failure(self):
        spec = {h: list(tracing.REQUIRED_STEPS) for h in (1, 2, 4)}
        spec[3] = list(tracing.REQUIRED_STEPS[1:])
        rep = tracing.span_report(self._events(spec), dropped=0)
        assert rep["bad"] == {3: [tracing.REQUIRED_STEPS[0]]}
        assert rep["truncated"] == []

    def test_mid_chain_hole_is_a_failure_even_wrapped(self):
        # a LATER step present while an earlier one is missing cannot be
        # oldest-first eviction — real instrumentation/consensus bug
        spec = {h: list(tracing.REQUIRED_STEPS) for h in (1, 2, 4)}
        spec[3] = [s for s in tracing.REQUIRED_STEPS if s != "Precommit"]
        rep = tracing.span_report(self._events(spec), dropped=999)
        assert rep["bad"] == {3: ["Precommit"]}

    def test_edge_heights_excluded(self):
        evs = self._events({1: ["Commit"], 2: list(tracing.REQUIRED_STEPS), 3: ["Propose"]})
        rep = tracing.span_report(evs)
        assert rep["complete"] == [2] and rep["interior"] == 1


class TestSpanChains:
    def _chain_events(self, heights, skip=()):
        r = FlightRecorder(size=1024)
        for h in heights:
            for step in ("NewHeight", "NewRound", *tracing.REQUIRED_STEPS):
                if (h, step) not in skip:
                    r.record("step", height=h, round=0, step=step)
        return r.events()

    def test_step_chains_and_complete_heights(self):
        evs = self._chain_events([5, 6, 7], skip={(6, "Precommit")})
        chains = tracing.step_chains(evs)
        assert set(chains) == {5, 6, 7}
        assert tracing.complete_heights(chains) == [5, 7]

    def test_block_breakdown_medians(self):
        evs = self._chain_events([1, 2, 3, 4])
        bd = tracing.block_breakdown(evs)
        assert bd is not None
        assert bd["source"] == "flight_recorder"
        assert bd["blocks"] == 3  # heights 1-3 have a next-height Propose
        for k in ("propose_ms", "prevote_ms", "precommit_ms", "commit_ms", "block_ms"):
            assert bd[k] >= 0

    def test_block_breakdown_needs_consecutive_chains(self):
        assert tracing.block_breakdown(self._chain_events([3])) is None
        assert tracing.block_breakdown([]) is None


class TestReplayBudget:
    def _events(self):
        """Two blocks by hand: 10 ms and 20 ms long, the second 5 ms after
        the first; two commits and two dispatches under each."""
        evs, t = [], 1_000_000_000
        for height, scale, wait in ((7, 1.0, None), (8, 2.0, 5.0)):
            t += int((wait or 0) * 1e6) + int(10e6 * scale)
            for h in (height, height - 1):
                evs.append({"kind": "verify.dispatch", "t_ns": t, "id": height,
                            "parent": "verify.commit", "n": 3, "path": "indexed",
                            "host_prep_ms": 0.25 * scale, "device_ms": 1.0 * scale,
                            "pack_ms": 0.1 * scale, "launch_ms": 0.4 * scale,
                            "fetch_ms": 0.5 * scale, "dur_ns": int(1.25e6 * scale)})
                evs.append({"kind": "verify.commit", "t_ns": t, "id": height, "height": h,
                            "parent": "fastsync.block", "n": 3, "sign_bytes_ms": 0.5 * scale,
                            "engine_ms": 1.5 * scale, "tally_ms": 0.0,
                            "dur_ns": int(2e6 * scale)})
            block = {"kind": "fastsync.block", "t_ns": t, "id": height, "parent": None,
                     "dur_ns": int(10e6 * scale), "parts_ms": 1.0 * scale,
                     "verify_ms": 2.0 * scale, "store_ms": 3.0 * scale, "apply_ms": 4.0 * scale,
                     "deliver_ms": 1.5 * scale, "decode_ms": 0.75, "pending": 4}
            if wait is not None:
                block["wait_ms"] = wait
            evs.append(block)
        evs.append({"kind": "verify.dispatch", "t_ns": t + 1, "n": 3, "path": "indexed",
                    "host_prep_ms": 9.0, "device_ms": 9.0})  # the harness's own call: no id
        return evs

    def test_stages_per_block_and_children_summed_under_their_block(self):
        budget = tracing.replay_budget(self._events())
        assert budget["blocks"] == 2 and budget["heights"] == [7, 8]
        assert budget["interval_ms"] == 17.5  # (10 + 0) and (20 + 5)
        st = budget["stages"]
        assert st["block_ms"]["mean_ms"] == 15.0
        assert st["wait_ms"]["mean_ms"] == 2.5
        assert st["store_ms"]["mean_ms"] == 4.5 and st["deliver_ms"]["mean_ms"] == 2.25
        assert st["commit.sign_bytes_ms"]["mean_ms"] == 1.5  # two a block: 1.0 and 2.0
        assert st["dispatch.fetch_ms"]["mean_ms"] == 1.5
        assert st["dispatch.host_prep_ms"]["mean_ms"] == 0.75  # the id-less call is no block's
        assert "commit.tally_ms" not in st and "queued_ms" not in st  # all zero or absent
        assert list(st)[:6] == ["block_ms", "wait_ms", "parts_ms", "verify_ms", "store_ms", "apply_ms"]
        table = tracing.format_replay_budget(budget)
        assert "block interval 17.5 ms" in table and "dispatch.launch_ms" in table

    def test_the_dump_says_how_many_commit_messages_came_from_a_template(self):
        events = self._events()
        assert tracing.replay_budget(events)["commit_messages"] == {"n": 12, "templated": 0}
        for ev in events:  # a node of this tree: every commit span carries the counter
            if ev["kind"] == "verify.commit":
                ev["templated"] = ev["n"]
        budget = tracing.replay_budget(events)
        assert budget["commit_messages"] == {"n": 12, "templated": 12}
        assert "commit messages: 12 of 12 from a template" in tracing.format_replay_budget(budget)

    def test_the_dump_says_where_the_dispatches_ran_and_how_full_the_shards_were(self):
        events = self._events()
        assert "dispatch_placement" not in tracing.replay_budget(events)  # an older node's events
        for i, ev in enumerate(e for e in events if e["kind"] == "verify.dispatch" and "id" in e):
            if i == 0:  # a small batch, routed to one device of the mesh
                ev.update(n=3, bucket=16, shards=1, kernel="ladder", shard_n=[3], device=0)
            else:
                ev.update(n=45, bucket=64, shards=4, kernel="ladder", shard_n=[16, 16, 13, 0],
                          put_ms=0.2)
        budget = tracing.replay_budget(events)
        assert budget["dispatch_placement"] == {"ladder x1 (device 0)": 1, "ladder x4": 3}
        assert budget["min_shard_fill"] == 0.0
        assert budget["stages"]["dispatch.put_ms"]["mean_ms"] == 0.3  # 1 and 2 a block
        table = tracing.format_replay_budget(budget)
        assert "device dispatches: 1 on ladder x1 (device 0), 3 on ladder x4" in table
        assert "dispatch.put_ms" in table

    def test_the_set_hash_stage_is_listed_and_printed(self):
        """`set_hash_ms` and `set_hashes` (state/validation.py) are fields of
        `fastsync.block`: in the rows `trace --replay` prints, in the module's
        list of the span's fields, and in the table for a recorded chain."""
        own = [name for kind, names in tracing.REPLAY_ROWS if kind == "fastsync.block"
               for name in names]
        assert {"set_hash_ms", "set_hashes"} <= set(own) and len(own) == len(set(own))
        listed = tracing.__doc__.split("fastsync.block    SPAN", 1)[1].split("gossip (", 1)[0]
        assert "set_hash_ms" in listed and "set_hashes" in listed and "validate_ms" in listed
        events = self._events()
        assert "set_hash_ms" not in tracing.replay_budget(events)["stages"]  # an older node
        blocks = [ev for ev in events if ev["kind"] == "fastsync.block"]
        blocks[0].update(validate_ms=3.0, set_hash_ms=1.5, set_hashes=2)  # the first block applied
        blocks[1].update(validate_ms=1.0, set_hash_ms=0.002, set_hashes=0)  # an unchanged set
        st = tracing.replay_budget(events)["stages"]
        assert st["set_hashes"]["mean_ms"] == 1.0 and st["set_hashes"]["p90_ms"] == 2
        assert st["set_hash_ms"]["mean_ms"] == 0.751
        assert list(st).index("validate_ms") < list(st).index("set_hash_ms") \
            < list(st).index("commit.sign_bytes_ms")
        table = tracing.format_replay_budget(tracing.replay_budget(events))
        assert "  set_hash_ms " in table and "  set_hashes " in table
        for ev in blocks:  # a warm static set: the count reads 0 and its row goes
            ev.update(set_hash_ms=0.002, set_hashes=0)
        st = tracing.replay_budget(events)["stages"]
        assert "set_hashes" not in st and st["set_hash_ms"]["mean_ms"] == 0.002

    def test_the_store_encode_counts_are_listed_and_printed(self):
        """`commit_encodes` (BlockStore.save_block) and `set_encodes`
        (StateStore.save) are fields of `fastsync.block`: a row of the
        budget `trace --replay` prints, and in the module's list of the
        span's fields."""
        own = [name for kind, names in tracing.REPLAY_ROWS if kind == "fastsync.block"
               for name in names]
        assert {"commit_encodes", "set_encodes"} <= set(own) and len(own) == len(set(own))
        listed = tracing.__doc__.split("fastsync.block    SPAN", 1)[1].split("gossip (", 1)[0]
        assert "commit_encodes" in listed and "set_encodes" in listed
        events = self._events()
        assert not {"commit_encodes", "set_encodes"} & set(tracing.replay_budget(events)["stages"])
        blocks = [ev for ev in events if ev["kind"] == "fastsync.block"]
        blocks[0].update(commit_encodes=2, set_encodes=3)  # the first block applied
        blocks[1].update(commit_encodes=1, set_encodes=1)  # steady
        st = tracing.replay_budget(events)["stages"]
        assert st["commit_encodes"]["mean_ms"] == 1.5 and st["set_encodes"]["p90_ms"] == 3
        table = tracing.format_replay_budget(tracing.replay_budget(events))
        assert "  commit_encodes " in table and "  set_encodes " in table

    def test_the_basic_and_median_stages_are_listed_and_printed(self):
        """`basic_ms`, `commit_hashes` and `median_ms` (state/validation.py,
        PR 29) are fields of `fastsync.block` like PR 27's two: in the rows
        `trace --replay` prints, in validate_block's order, and in the
        module's list of the span's fields."""
        (inside_validate,) = [names for kind, names in tracing.REPLAY_ROWS if "set_hash_ms" in names]
        assert inside_validate == ("basic_ms", "commit_hashes", "set_hash_ms", "set_hashes",
                                   "median_ms")
        listed = tracing.__doc__.split("fastsync.block    SPAN", 1)[1].split("gossip (", 1)[0]
        assert all(name in listed for name in inside_validate)
        events = self._events()
        stages = tracing.replay_budget(events)["stages"]  # an older node's events
        assert not {"basic_ms", "commit_hashes", "median_ms"} & set(stages)
        blocks = [ev for ev in events if ev["kind"] == "fastsync.block"]
        blocks[0].update(validate_ms=3.0, basic_ms=1.25, commit_hashes=1, median_ms=0.5)
        blocks[1].update(validate_ms=1.0, basic_ms=0.25, commit_hashes=0, median_ms=0.25)
        st = tracing.replay_budget(events)["stages"]
        assert st["basic_ms"]["mean_ms"] == 0.75 and st["median_ms"]["mean_ms"] == 0.375
        assert st["commit_hashes"]["mean_ms"] == 0.5  # a count a block, under the table's heading
        order = list(st)
        assert order.index("validate_ms") < order.index("basic_ms") < order.index("commit_hashes") \
            < order.index("median_ms") < order.index("commit.sign_bytes_ms")
        table = tracing.format_replay_budget(tracing.replay_budget(events))
        assert "  basic_ms " in table and "  commit_hashes " in table and "  median_ms " in table

    def test_nothing_to_budget_without_a_block_span(self):
        assert tracing.replay_budget([{"kind": "verify.commit", "id": 3}]) is None
        assert "nothing to budget" in tracing.format_replay_budget(None)


class TestRPCRoute:
    async def test_dump_flight_recorder_route(self):
        from tendermint_tpu.rpc.core import RPCCore

        class _StubNode:
            flight_recorder = FlightRecorder(size=32)

        node = _StubNode()
        node.flight_recorder.record("step", height=1, round=0, step="Propose")
        core = RPCCore(node)
        snap = await core.call("dump_flight_recorder")
        assert snap["enabled"] is True
        assert snap["events"][0]["kind"] == "step"
        assert snap["events"][0]["height"] == 1
        # seq watermark polling: nothing new -> empty
        again = await core.call("dump_flight_recorder", {"since": snap["next_seq"]})
        assert again["events"] == []

    async def test_route_kinds_filter_anchor_and_moniker(self):
        from tendermint_tpu.rpc.core import RPCCore

        class _Base:
            moniker = "trace-node"

        class _Cfg:
            base = _Base()

        class _StubNode:
            flight_recorder = FlightRecorder(size=32)
            config = _Cfg()

        node = _StubNode()
        node.flight_recorder.record("step", height=1, round=0, step="Propose")
        node.flight_recorder.record("gossip.wakeup", peer="ab")
        node.flight_recorder.record("commit", height=1, txs=0, block="aa")
        core = RPCCore(node)
        # comma-separated string form (what a URL query carries)
        snap = await core.call("dump_flight_recorder", {"kinds": "step,commit"})
        assert [e["kind"] for e in snap["events"]] == ["step", "commit"]
        # list form (programmatic callers)
        snap = await core.call("dump_flight_recorder", {"kinds": ["gossip."]})
        assert [e["kind"] for e in snap["events"]] == ["gossip.wakeup"]
        # the cross-node alignment surface: anchor + node label
        assert set(snap["anchor"]) == {"mono_ns", "wall_ns"}
        assert snap["node"] == "trace-node"

    async def test_route_survives_node_without_recorder(self):
        from tendermint_tpu.rpc.core import RPCCore

        snap = await RPCCore(object()).call("dump_flight_recorder")
        assert snap == {
            "enabled": False, "size": 0, "next_seq": 0, "dropped": 0, "events": [],
        }


class TestVerifyEngineEvents:
    async def test_async_batcher_emits_enqueue_and_flush_spans(self):
        from tendermint_tpu.crypto.batch_verifier import AsyncBatchVerifier, BatchVerifier
        from tendermint_tpu.crypto.keys import Ed25519PrivKey

        rec = FlightRecorder(size=256)
        # min_device_batch above any test batch: the host path serves, no
        # device compile — this test is about the event stream, not JAX
        bv = BatchVerifier(min_device_batch=1 << 30, recorder=rec)
        svc = AsyncBatchVerifier(bv)
        await svc.start()
        try:
            k = Ed25519PrivKey.from_secret(b"trace")
            msg = b"\x08\x02\x11" + bytes(40)
            assert await svc.verify_one(k.pub_key().bytes(), msg, k.sign(msg))
        finally:
            await svc.stop()
        kinds = [e["kind"] for e in rec.events()]
        assert "verify.enqueue" in kinds
        assert "verify.flush" in kinds
        assert "verify.dispatch" in kinds
        flush = next(e for e in rec.events() if e["kind"] == "verify.flush")
        assert flush["batch"] >= 1 and flush["wait_ms"] >= 0
        dispatch = next(e for e in rec.events() if e["kind"] == "verify.dispatch")
        assert dispatch["path"] == "host" and dispatch["n"] >= 1


def _signed(n):
    from tendermint_tpu.crypto.keys import Ed25519PrivKey

    keys = [Ed25519PrivKey.from_secret(f"span-{i}".encode()) for i in range(n)]
    msgs = [b"\x08\x02\x11" + bytes([i]) * 40 for i in range(n)]
    return [k.pub_key().bytes() for k in keys], msgs, [k.sign(m) for k, m in zip(keys, msgs)]


DEVICE_PATHS = ("device", "indexed", "chunked")


class TestDispatchSpans:
    """verify.dispatch on every path: `host_prep_ms + device_ms` is the
    call's wall time, and on the device paths host prep is measured (the
    chunked path used to report the constant 0.0) and `pack_ms + launch_ms
    + fetch_ms` split the rest."""

    @pytest.mark.parametrize("path", ("host", "host-cold") + DEVICE_PATHS)
    def test_prep_and_device_tile_the_calls_wall_time(self, path, monkeypatch):
        from tendermint_tpu.crypto import batch_verifier as bv

        rec, prep_hist, dev_hist = FlightRecorder(size=256), _Hist(), _Hist()
        n = 70 if path == "chunked" else 6
        pubkeys, msgs, sigs = _signed(6)
        idxs = [i % 6 for i in range(n)]
        msgs, sigs = [msgs[i] for i in idxs], [sigs[i] for i in idxs]
        engine = bv.BatchVerifier(
            recorder=rec, min_device_batch=1 << 30 if path == "host" else 1
        )
        engine._pallas = False  # the XLA kernel: any shape, no interpreter
        engine.metrics.host_prep_seconds = prep_hist
        engine.metrics.device_seconds = dev_hist

        def call():
            if path in ("host", "host-cold", "device"):
                return engine.verify([pubkeys[i] for i in idxs], msgs, sigs)
            return table.verify_indexed(idxs, msgs, sigs)

        if path == "host-cold":
            engine._warmup_mode = True
            engine._compiling_buckets.add(engine._bucket(n))  # as if its compile were running
        elif path in ("indexed", "chunked"):
            if path == "chunked":
                monkeypatch.setattr(bv, "_CHUNK", 32)
            table = bv.PubkeyTable(pubkeys, engine)
            table.chunked_single_shot = path == "chunked"
        call()  # a compile, a library's first load: outside the timed call
        seq = rec.snapshot()["next_seq"]
        t0 = time.monotonic_ns()
        assert call() == [True] * n
        wall_ms = (time.monotonic_ns() - t0) / 1e6
        (ev,) = rec.events(since=seq, kinds=["verify.dispatch"])
        assert (ev["path"], ev["n"], ev["shards"]) == (path, n, 1)
        assert "ok" not in ev  # the harness reads ok=False on a verify.* event as a failure
        # ... to within the lines that close the span, which run on cold caches
        # (and, on the table paths, the row list built from the indices)
        tiled = ev["host_prep_ms"] + ev["device_ms"] + ev.get("rows_ms", 0.0)
        assert tiled == pytest.approx(ev["dur_ns"] / 1e6, abs=0.25)
        assert ("rows_ms" in ev) == (path in ("indexed", "chunked"))
        # from outside, the call is that span and a few lines around it
        assert wall_ms - 1.0 <= ev["dur_ns"] / 1e6 <= wall_ms
        if path in DEVICE_PATHS:
            assert ev["host_prep_ms"] > 0
            assert ev["pack_ms"] > 0 and ev["launch_ms"] > 0 and ev["fetch_ms"] > 0
            assert ev["pack_ms"] + ev["launch_ms"] + ev["fetch_ms"] == pytest.approx(
                ev["device_ms"], abs=0.01)
            assert ev["bucket"] == (32 if path == "chunked" else engine._bucket(n))
            # one device, no mesh: all the useful rows in its one shard, no device named
            assert ev["shard_n"] == [n] and "device" not in ev
            assert ev["kernel"] == "straus"
            assert ("put_ms" in ev) == (path == "chunked")  # only the chunks are put by hand
            # one reading, two sinks: the histograms saw the event's numbers
            assert prep_hist.seen[-1] * 1e3 == pytest.approx(ev["host_prep_ms"], abs=0.001)
            assert dev_hist.seen[-1] * 1e3 == pytest.approx(ev["device_ms"], abs=0.001)
        else:
            assert ev["host_prep_ms"] == 0.0 and ev["device_ms"] > 0 and ev["bucket"] in (
                0, engine._bucket(n))
            assert "launch_ms" not in ev

    def test_a_dispatch_inside_a_commit_says_which_block_it_served(self):
        from tendermint_tpu.crypto.batch_verifier import BatchVerifier

        rec = FlightRecorder(size=64)
        engine = BatchVerifier(recorder=rec, min_device_batch=1 << 30)
        pubkeys, msgs, sigs = _signed(3)
        with rec.span("fastsync.block", id=41):
            with tracing.child_span("verify.commit", height=40):
                engine.verify(pubkeys, msgs, sigs)
        dispatch = rec.events()[0]
        assert (dispatch["kind"], dispatch["parent"], dispatch["id"]) == (
            "verify.dispatch", "verify.commit", 41)

    def test_no_direct_batch_event(self):
        import inspect

        from tendermint_tpu.crypto import batch_verifier as bv

        assert "verify.direct_batch" not in inspect.getsource(bv)


class TestCommitSpans:
    """verify.commit as fast sync leaves it: two a block, each one event
    with its three laps, `n`, and `templated` == `n` (every message came
    from the commit's templates: none through the per-vote encoder)."""

    CHAIN = "span-chain"

    def _chain(self, heights):
        from tendermint_tpu.types import (
            PRECOMMIT_TYPE, BlockID, Commit, CommitSig, MockPV, PartSetHeader, Validator,
            ValidatorSet, Vote,
        )

        pvs = sorted((MockPV() for _ in range(7)), key=lambda pv: pv.address())
        vset = ValidatorSet([Validator.new(pv.get_pub_key(), 10) for pv in pvs])
        commits = {}
        for h in heights:
            bid = BlockID(bytes([h]) * 32, PartSetHeader(1, bytes([h]) * 32))
            sigs = []
            for i, pv in enumerate(pvs):
                if i == 1 + h % 6:
                    sigs.append(CommitSig.absent())
                    continue
                # slot 0 votes nil: verified, not counted
                vote = Vote(PRECOMMIT_TYPE, h, 0, BlockID() if i == 0 else bid,
                            1_700_000_000_000_000_000 + 1000 * h + i, pv.address(), i)
                pv.sign_vote(self.CHAIN, vote)
                sigs.append(vote.commit_sig())
            commits[h] = (bid, Commit(h, 0, bid, sigs))
        return vset, commits

    def test_seven_events_a_block_and_every_message_templated(self, monkeypatch):
        from tendermint_tpu.crypto import batch as crypto_batch
        from tendermint_tpu.crypto import batch_verifier as bv

        rec = FlightRecorder(size=256)
        engine = bv.BatchVerifier(recorder=rec, min_device_batch=1)
        engine._pallas = False  # the XLA kernel: any shape, no interpreter
        monkeypatch.setattr(crypto_batch, "_indexed_verifier", bv.TableCache(engine).verify_indexed)
        vset, commits = self._chain(range(1, 5))
        vset.verify_commit(self.CHAIN, commits[1][0], 1, commits[1][1])  # table build, compile
        seq = rec.snapshot()["next_seq"]
        for h in (2, 3, 4):
            # as _try_sync does: the block's own commit, then its LastCommit in validation
            with rec.span("fastsync.block", id=h):
                vset.verify_commit(self.CHAIN, commits[h][0], h, commits[h][1])
                vset.verify_commit(self.CHAIN, commits[h - 1][0], h - 1, commits[h - 1][1])
        events = rec.events(since=seq)
        for h in (2, 3, 4):
            mine = [e for e in events if e.get("id") == h]
            assert sorted(e["kind"] for e in mine) == [
                "fastsync.block", "verify.commit", "verify.commit", "verify.dispatch",
                "verify.dispatch", "verify.table", "verify.table"]  # 7, of a budget of 8
            commit_events = [e for e in mine if e["kind"] == "verify.commit"]
            assert [c["height"] for c in commit_events] == [h, h - 1]
            for c in commit_events:
                assert c["parent"] == "fastsync.block" and "ok" not in c
                assert c["n"] == c["templated"] == 6  # seven slots, one absent
                assert min(c["sign_bytes_ms"], c["engine_ms"], c["tally_ms"]) >= 0
                assert c["sign_bytes_ms"] + c["engine_ms"] + c["tally_ms"] <= c["dur_ns"] / 1e6 + 0.005
            for d in (e for e in mine if e["kind"] == "verify.dispatch"):
                assert (d["parent"], d["path"], d["n"]) == ("verify.commit", "indexed", 6)

    def test_the_set_hash_stage_is_two_fields_and_no_event(self):
        """`validate_block` under an open span: the two validator-set roots
        are a stage on that span's one event, so a block's 7 events stay 7.
        Height 1 has no LastCommit, so the span is all there is."""
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.state.validation import validate_block
        from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

        pvs = [MockPV() for _ in range(7)]

        def genesis_state():
            return make_genesis_state(GenesisDoc(
                chain_id=self.CHAIN, genesis_time_ns=1_700_000_000_000_000_000,
                validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs]))

        proposer = genesis_state()
        block = proposer.make_block(1, [b"a=b"], None, [], proposer.validators.get_proposer().address)
        state = genesis_state()  # the validating node's own: no root taken yet
        rec = FlightRecorder(size=16)
        for _ in range(2):
            with rec.span("fastsync.block", id=1):
                validate_block(state, block)
        first, second = rec.events()
        assert first["kind"] == second["kind"] == "fastsync.block"
        assert (first["set_hashes"], second["set_hashes"]) == (2, 0)
        assert 0 < second["set_hash_ms"] < first["set_hash_ms"] <= first["dur_ns"] / 1e6
        validate_block(state, block)  # outside any span: nothing to annotate, nothing raised
        assert len(rec.events()) == 2

    def test_the_basic_stage_is_fields_and_no_event_and_height_1_has_no_commit(self):
        """`validate_block` under an open span leaves `basic_ms` and
        `commit_hashes` on that span's one event; height 1 has no LastCommit,
        so no root is built and no median taken.  (A block with one:
        tests/test_fastsync.py::TestSetHashStage.)"""
        from tendermint_tpu.state import make_genesis_state
        from tendermint_tpu.state.validation import validate_block
        from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

        pvs = [MockPV() for _ in range(7)]
        state = make_genesis_state(GenesisDoc(
            chain_id=self.CHAIN, genesis_time_ns=1_700_000_000_000_000_000,
            validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs]))
        block = state.make_block(1, [b"a=b"], None, [], state.validators.get_proposer().address)
        rec = FlightRecorder(size=16)
        kinds_before = set(tracing.__doc__.split())
        with rec.span("fastsync.block", id=1):
            validate_block(state, block)
        (ev,) = rec.events()
        assert ev["kind"] == "fastsync.block" and ev["commit_hashes"] == 0
        assert 0 < ev["basic_ms"] <= ev["dur_ns"] / 1e6 and "median_ms" not in ev
        assert set(tracing.__doc__.split()) == kinds_before
        validate_block(state, block)  # outside any span: nothing to annotate, nothing raised
        assert len(rec.events()) == 1

    def test_the_trusting_check_closes_the_same_span(self):
        rec = FlightRecorder(size=16)
        vset, commits = self._chain([9])
        with rec.span("lite.verify", id=9):
            vset.verify_commit_trusting(self.CHAIN, commits[9][0], 9, commits[9][1], 1, 3)
        (c,) = [e for e in rec.events() if e["kind"] == "verify.commit"]
        assert c["n"] == c["templated"] == 6 and (c["parent"], c["id"], c["height"]) == ("lite.verify", 9, 9)
        assert {"sign_bytes_ms", "engine_ms", "tally_ms", "dur_ns"} <= set(c)


class TestFlightSpool:
    """Crash-persistent spool ([instrumentation] flight_spool): rotation
    under the size cap, torn-tail-tolerant replay, wrap accounting, and
    the hot-path contract (the recorder never pays for the spool)."""

    def _steps(self, rec, heights, round_=0):
        for h in heights:
            for s in ("Propose", "Prevote", "Precommit", "Commit"):
                rec.record("step", height=h, step=s, round=round_)
            rec.record("commit", height=h, txs=0, block="ab")

    def test_roundtrip_replay_matches_ring(self, tmp_path):
        from tendermint_tpu.libs.tracing import FlightSpool, read_spool

        rec = FlightRecorder(size=4096)
        sp = FlightSpool(str(tmp_path / "flight.spool"), rec, node="n7")
        self._steps(rec, range(1, 8))
        sp.flush()
        sp.close()
        dump = read_spool(str(tmp_path / "flight.spool"))
        assert dump["node"] == "n7" and dump["source"] == "spool"
        assert dump["dropped"] == 0 and dump["torn"] == 0
        assert [e["seq"] for e in dump["events"]] == [
            e["seq"] for e in rec.events()
        ]
        assert dump["anchor"] is not None and dump["anchor"]["wall_ns"] > 0
        rep = tracing.span_report(dump["events"], dropped=dump["dropped"])
        assert rep["bad"] == {} and len(rep["complete"]) == rep["interior"] == 5

    def test_torn_tail_kill_mid_append_keeps_retained_suffix(self, tmp_path):
        """Simulate a SIGKILL landing mid-write: the final record is cut
        at an arbitrary byte.  Replay must keep every complete record,
        count the torn line, and span_report must stay clean."""
        from tendermint_tpu.libs.tracing import FlightSpool, read_spool

        path = str(tmp_path / "flight.spool")
        rec = FlightRecorder(size=4096)
        sp = FlightSpool(path, rec, node="torn")
        self._steps(rec, range(1, 6))
        sp.flush()
        # the spool is abandoned un-closed (the kill); chop the file tail
        # mid-record instead of at a line boundary
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(size - 7)
        dump = read_spool(path)
        assert dump["torn"] == 1
        # every complete line survived: only the final record was cut
        assert len(dump["events"]) >= 5 * 5 - 1
        rep = tracing.span_report(dump["events"], dropped=dump["dropped"])
        assert rep["bad"] == {}
        # garbage bytes appended by a dying disk are skipped the same way
        with open(path, "ab") as f:
            # leading newline: the truncated line above has no terminator,
            # so raw bytes would otherwise merge into the same torn line
            f.write(b"\n\xff\xfe{{{ not json\n")
        dump2 = read_spool(path)
        assert dump2["torn"] == 2
        assert len(dump2["events"]) == len(dump["events"])

    def test_rotation_bounds_disk_and_reports_dropped_prefix(self, tmp_path):
        from tendermint_tpu.libs.tracing import FlightSpool, read_spool, spool_paths

        path = str(tmp_path / "flight.spool")
        rec = FlightRecorder(size=1 << 16)
        cap = 16 * 1024
        sp = FlightSpool(path, rec, size_limit=cap, node="rot")
        for h in range(1, 200):
            self._steps(rec, [h])
            sp.flush()
        sp.close()
        total = sum(os.path.getsize(p) for p in spool_paths(path))
        assert total <= cap, f"spool grew past its cap: {total} > {cap}"
        dump = read_spool(path)
        assert dump["dropped"] > 0  # rotated-away prefix is reported
        assert dump["events"], "the retained suffix must replay"
        # the newest heights survived (oldest-first eviction)
        rep = tracing.span_report(dump["events"], dropped=dump["dropped"])
        assert rep["bad"] == {}, "rotation must only ever truncate a PREFIX"
        assert 198 in tracing.step_chains(dump["events"])

    def test_ring_wrap_between_flushes_is_accounted(self, tmp_path):
        from tendermint_tpu.libs.tracing import FlightSpool, read_spool

        rec = FlightRecorder(size=8)
        sp = FlightSpool(str(tmp_path / "w.spool"), rec, node="w")
        for i in range(30):
            rec.record("x", i=i)
        sp.flush()
        for i in range(30):
            rec.record("y", i=i)
        sp.flush()
        sp.close()
        dump = read_spool(str(tmp_path / "w.spool"))
        assert len(dump["events"]) == 16  # two ring-fulls
        assert dump["writer_lost"] == 22  # wrap losses the writer observed
        assert dump["dropped"] == 60 - 16  # replay holes cover all classes

    def test_record_hot_path_unchanged_with_spool_attached(self, tmp_path):
        """The acceptance tripwire: spool writes happen OFF the recording
        path — record() with a spool attached stays under the same 5 µs
        budget the bare recorder is held to."""
        from tendermint_tpu.libs.tracing import FlightSpool

        rec = FlightRecorder(size=8192)
        sp = FlightSpool(str(tmp_path / "hot.spool"), rec, node="hot")
        n = 20_000
        t0 = time.perf_counter()
        for i in range(n):
            rec.record("step", height=i, step="Propose", round=0)
        per_event = (time.perf_counter() - t0) / n
        sp.flush()
        sp.close()
        assert per_event < 5e-6, (
            f"record() with spool enabled took {per_event * 1e6:.2f} us/event"
        )

    def test_flush_idempotent_and_empty_flush_writes_nothing(self, tmp_path):
        from tendermint_tpu.libs.tracing import FlightSpool

        path = str(tmp_path / "idle.spool")
        rec = FlightRecorder(size=64)
        sp = FlightSpool(path, rec, node="idle")
        rec.record("step", height=1, step="Propose")
        assert sp.flush() == 1
        size_after = os.path.getsize(path)
        # nothing new: no bytes written (an idle node must not grow its
        # spool with anchor-only batches every flush interval)
        assert sp.flush() == 0
        sp._group.flush()
        assert os.path.getsize(path) == size_after
        sp.close()

    def test_crash_hooks_flush_on_excepthook(self, tmp_path):
        import sys

        from tendermint_tpu.libs.tracing import FlightSpool, read_spool

        path = str(tmp_path / "hook.spool")
        rec = FlightRecorder(size=64)
        sp = FlightSpool(path, rec, node="hook")
        sp.install_crash_hooks()
        try:
            rec.record("step", height=1, step="Propose")
            # simulate the interpreter's unhandled-exception path
            try:
                raise RuntimeError("boom")
            except RuntimeError:
                sys.excepthook(*sys.exc_info())
            dump = read_spool(path)
            assert len(dump["events"]) == 1, "excepthook must flush the spool"
        finally:
            sp.close()
        assert sys.excepthook is sys.__excepthook__ or not hasattr(
            sys.excepthook, "__self__"
        )

    def test_recorder_dropped_property(self):
        rec = FlightRecorder(size=4)
        assert rec.dropped == 0
        for i in range(10):
            rec.record("x", i=i)
        assert rec.dropped == 6

    def test_two_spools_crash_hooks_are_independent(self, tmp_path):
        """In-proc multi-node: removing spool A's crash hook must not
        uninstall spool B's (the excepthook chain is per-object, and only
        the OWNING hook may be restored away)."""
        import sys

        from tendermint_tpu.libs.tracing import FlightSpool, read_spool

        rec_a, rec_b = FlightRecorder(size=64), FlightRecorder(size=64)
        sp_a = FlightSpool(str(tmp_path / "a.spool"), rec_a, node="a")
        sp_b = FlightSpool(str(tmp_path / "b.spool"), rec_b, node="b")
        sp_a.install_crash_hooks()
        sp_b.install_crash_hooks()
        try:
            sp_a.close()  # removes A's hooks; B's chain must survive
            assert sys.excepthook is sp_b._hook_fn, (
                "closing spool A must not uninstall spool B's crash hook"
            )
            rec_b.record("step", height=1, step="Propose")
            try:
                raise RuntimeError("boom")
            except RuntimeError:
                sys.excepthook(*sys.exc_info())
            assert len(read_spool(str(tmp_path / "b.spool"))["events"]) == 1
        finally:
            sp_b.close()

    def test_restart_reuses_spool_but_replay_returns_newest_run(self, tmp_path):
        """The spool file survives restarts (append-mode head) while
        recorder seqs restart at 0 per process — the replay must return
        the NEWEST run's events, not let the old run's colliding seqs
        replace the crash evidence with stale data."""
        from tendermint_tpu.libs.tracing import FlightSpool, read_spool

        path = str(tmp_path / "flight.spool")
        # run 1: heights 1-5, clean stop
        rec1 = FlightRecorder(size=4096)
        sp1 = FlightSpool(path, rec1, node="boot1")
        self._steps(rec1, range(1, 6))
        sp1.flush()
        sp1.close()
        # run 2 (restart, same home): heights 100-102, SIGKILLed
        rec2 = FlightRecorder(size=4096)
        sp2 = FlightSpool(path, rec2, node="boot2")
        self._steps(rec2, range(100, 103))
        sp2.flush()  # no close: the crash
        dump = read_spool(path)
        assert dump["runs"] == 2
        assert dump["node"] == "boot2"
        heights = {e.get("height") for e in dump["events"] if e["kind"] == "step"}
        assert heights == {100, 101, 102}, (
            f"replay must carry the crashing run's heights, got {heights}"
        )
        assert len(dump["events"]) == len(rec2.events())
        # legacy single-run spools (and every earlier test) keep working:
        # a one-run file reports runs == 1 with identical semantics
        solo = read_spool(str(tmp_path / "flight.spool") + ".none")
        assert solo["events"] == [] and solo["runs"] == 0
