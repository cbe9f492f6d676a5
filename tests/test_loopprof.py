"""Asyncio scheduler profiler (libs/loopprof.py): category rules, the
resume-timing trampoline (values, exceptions and cancellation must pass
through unchanged), process-hook ownership, GC accounting, the lag
histogram, per-block attribution math, and the overhead contract the
enabled path must honor (the recorder's own per-event tripwire)."""

import asyncio
import gc
import time

import pytest

from tendermint_tpu.libs import loopprof
from tendermint_tpu.libs.loopprof import LoopProfiler
from tendermint_tpu.libs.service import Service
from tendermint_tpu.libs.tracing import FlightRecorder


class TestCategorize:
    def test_spawn_sites_map_to_their_subsystem(self):
        assert loopprof.categorize("ConsensusState", "recv-routine") == "consensus"
        assert loopprof.categorize("ConsensusReactor", "gossip-data-ab12") == "gossip"
        assert loopprof.categorize("ConsensusReactor", "maj23-queries") == "gossip"
        assert loopprof.categorize("batch-verifier", "flush-loop") == "verify"
        assert loopprof.categorize("MConnection", "send-routine") == "p2p-conn"
        assert loopprof.categorize("Switch", "accept-routine") == "p2p-conn"
        assert loopprof.categorize("MempoolReactor", "broadcast") == "mempool"
        assert loopprof.categorize("RPCServer") == "rpc"
        assert loopprof.categorize("SomethingElse") == "other"

    def test_the_replay_loop_has_a_category_of_its_own(self):
        # `Service.spawn` names the reactor's tasks "blockchain-reactor/<task>"
        for task in ("pool", "status-bcast", "refill"):
            assert loopprof.categorize("blockchain-reactor", task) == "fastsync"
        assert "fastsync" in loopprof.CATEGORIES
        # a peer's receive task, where a block response is decoded, stays p2p's
        assert loopprof.categorize("mconn", "recv") == "p2p-conn"

    def test_every_rule_lands_in_a_known_category(self):
        for _, cat in loopprof._RULES:
            assert cat in loopprof.CATEGORIES


class _Yield:
    """Awaitable that yields once to whatever drives the coroutine —
    lets tests step the trampoline by hand, no event loop involved."""

    def __await__(self):
        yield None


def _drive_to_completion(coro):
    steps = 0
    try:
        while True:
            coro.send(None)
            steps += 1
    except StopIteration as stop:
        return stop.value, steps


class TestTrampoline:
    def test_return_value_passes_through(self):
        prof = LoopProfiler()

        async def work():
            await _Yield()
            await _Yield()
            return 42

        value, steps = _drive_to_completion(prof.wrap(work(), "consensus"))
        assert value == 42
        assert steps == 2
        # every resume (2 yields + the final run to StopIteration) accounted
        assert prof.steps["consensus"] == 3
        assert prof.busy_ns["consensus"] > 0

    def test_exception_passes_through_and_is_accounted(self):
        prof = LoopProfiler()

        async def boom():
            await _Yield()
            raise ValueError("boom")

        coro = prof.wrap(boom(), "verify")
        coro.send(None)
        with pytest.raises(ValueError, match="boom"):
            coro.send(None)
        assert prof.steps["verify"] == 2

    async def test_cancellation_reaches_the_inner_coroutine(self):
        prof = LoopProfiler()
        cleaned = asyncio.Event()

        async def forever():
            try:
                await asyncio.sleep(3600)
            except asyncio.CancelledError:
                cleaned.set()
                raise

        task = asyncio.get_event_loop().create_task(prof.wrap(forever(), "gossip"))
        await asyncio.sleep(0.01)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        assert cleaned.is_set(), "CancelledError never reached the wrapped coroutine"

    async def test_values_sent_by_the_loop_pass_through(self):
        # futures resolve THROUGH the trampoline: the loop sends the
        # result back and the inner coroutine must receive it
        prof = LoopProfiler()
        loop = asyncio.get_event_loop()
        fut = loop.create_future()

        async def waiter():
            return await fut

        task = loop.create_task(prof.wrap(waiter(), "other"))
        await asyncio.sleep(0.01)
        fut.set_result("payload")
        assert await task == "payload"

    def test_wrap_overhead_per_resume_budget(self):
        # contract: ~1 us per task resume; tripwire at 5 us (the
        # recorder's own per-event budget) so CI noise can't flake while
        # a 10x regression still fails
        prof = LoopProfiler()
        n = 20_000

        async def hot():
            for _ in range(n):
                await _Yield()

        t0 = time.perf_counter()
        _drive_to_completion(prof.wrap(hot(), "consensus"))
        per_step = (time.perf_counter() - t0) / n
        assert per_step < 5e-6, f"trampoline resume took {per_step * 1e6:.2f} us"


class TestLifecycleAndSpawn:
    async def test_first_profiler_owns_process_hooks(self):
        assert loopprof.active() is None, "a previous test leaked the spawn hook"
        a = LoopProfiler(interval=0.05)
        b = LoopProfiler(interval=0.05)
        await a.start()
        await b.start()
        try:
            assert loopprof.active() is a
            assert a._owns_hooks and not b._owns_hooks
        finally:
            await b.stop()
            assert loopprof.active() is a  # non-owner stop doesn't release
            await a.stop()
        assert loopprof.active() is None

    async def test_spawn_accounts_to_category_when_active(self):
        prof = LoopProfiler(interval=0.05)
        await prof.start()
        svc = Service("MempoolReactor")
        done = asyncio.Event()

        async def job():
            await asyncio.sleep(0)
            done.set()

        try:
            svc.spawn(job(), "broadcast")
            await asyncio.wait_for(done.wait(), 5)
            await asyncio.sleep(0)  # let the trampoline run to StopIteration
            assert prof.busy_ns["mempool"] > 0
            assert prof.steps["mempool"] >= 1
        finally:
            await svc.stop()
            await prof.stop()

    async def test_spawn_untouched_without_profiler(self):
        assert loopprof.active() is None
        svc = Service("ConsensusState")
        done = asyncio.Event()

        async def job():
            done.set()

        try:
            svc.spawn(job(), "recv-routine")
            await asyncio.wait_for(done.wait(), 5)
        finally:
            await svc.stop()


class TestProbe:
    async def test_probe_emits_lag_busy_queue_and_gc_events(self):
        rec = FlightRecorder(size=512)
        prof = LoopProfiler(interval=0.02, recorder=rec)
        prof.add_queue_probe("stub_queue", lambda: 7)
        prof.add_queue_probe("dead_probe", lambda: 1 // 0)  # raises -> -1
        await prof.start()
        try:
            # accounted work + a forced collection inside the window
            async def spin():
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < 0.01:
                    await asyncio.sleep(0)

            await prof.wrap(spin(), "consensus")
            gc.collect()
            await asyncio.sleep(0.08)
            snap = prof.snapshot()  # before stop() releases the hooks
        finally:
            await prof.stop()
        kinds = {e["kind"] for e in rec.events()}
        assert "loop.lag" in kinds
        assert "loop.busy" in kinds
        assert "loop.gc_pause" in kinds
        assert "loop.queue" in kinds
        q = next(e for e in rec.events() if e["kind"] == "loop.queue")
        assert q["stub_queue"] == 7
        assert q["dead_probe"] == -1
        busy = next(e for e in rec.events() if e["kind"] == "loop.busy")
        assert loopprof.busy_categories(busy).get("consensus", 0) > 0
        assert prof.lag_samples > 0
        assert prof.gc_total_ms >= 0
        assert snap["lag_samples"] > 0
        assert snap["owns_hooks"] is True

    async def test_busy_interval_is_the_time_elapsed_not_the_nominal_one(self):
        """A task that holds the loop for three probe intervals: the
        `loop.busy` event that accounts it says how long it has really been
        since the last one, so the category's share cannot pass 100%."""
        rec = FlightRecorder(size=512)
        interval = 0.02
        prof = LoopProfiler(interval=interval, recorder=rec)
        await prof.start()
        try:
            async def hold():
                await asyncio.sleep(interval / 2)  # let the probe arm its timer first
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < 3 * interval:
                    pass  # no await: the probe cannot tick meanwhile

            t_start = time.monotonic_ns()
            await prof.wrap(hold(), "fastsync")
            await asyncio.sleep(3 * interval)
            t_end = time.monotonic_ns()
        finally:
            await prof.stop()
        busy = [e for e in rec.events() if e["kind"] == "loop.busy"]
        held = max(busy, key=lambda e: e.get("fastsync_ms", 0.0))
        assert held["fastsync_ms"] >= 3 * interval * 1e3
        assert held["interval_ms"] >= held["fastsync_ms"]  # was the nominal 20.0
        assert all(
            sum(loopprof.busy_categories(e).values()) <= e["interval_ms"] + 0.5 for e in busy
        )
        # the events tile the loop's time: their intervals sum to what elapsed
        assert sum(e["interval_ms"] for e in busy) <= (t_end - t_start) / 1e6 + 0.5
        assert sum(e["interval_ms"] for e in busy) >= 3 * interval * 1e3

    def test_lag_histogram_p90(self):
        prof = LoopProfiler()
        for _ in range(90):
            prof._observe_lag(0.0002)  # 0.2 ms
        for _ in range(10):
            prof._observe_lag(0.2)  # 200 ms
        assert prof.lag_samples == 100
        assert prof.lag_p90_ms() == 0.25  # bucket upper edge
        assert prof.lag_max_ms == pytest.approx(200.0)
        assert prof.lag_p90_ms() <= prof.lag_max_ms

    def test_interval_must_be_positive(self):
        with pytest.raises(ValueError):
            LoopProfiler(interval=0)


class TestAttribution:
    def test_shares_sum_to_interval_and_lag_is_capped(self):
        # 1000 ms interval: 400 ms consensus + 100 ms verify busy, 50 ms
        # GC, 600 ms claimed lag -> capped at the 450 ms unaccounted
        # remainder so double counting can't push the sum past 100%
        events = [
            {"t_ns": 500_000_000, "kind": "loop.busy", "interval_ms": 250.0,
             "consensus_ms": 400.0, "verify_ms": 100.0},
            {"t_ns": 600_000_000, "kind": "loop.gc_pause", "n": 2, "ms": 50.0},
            {"t_ns": 700_000_000, "kind": "loop.lag", "lag_ms": 600.0},
        ]
        att = loopprof.attribution(events, 0, 1_000_000_000)
        assert att["wall_ms"] == 1000.0
        assert att["consensus_pct"] == 40.0
        assert att["verify_pct"] == 10.0
        assert att["gc_pct"] == 5.0
        assert att["loop_lag_pct"] == 45.0
        assert att["idle_pct"] == 0.0
        total = sum(v for k, v in att.items() if k.endswith("_pct"))
        assert total == pytest.approx(100.0, abs=0.5)

    def test_idle_fills_the_remainder(self):
        events = [
            {"t_ns": 100, "kind": "loop.busy", "interval_ms": 250.0,
             "gossip_ms": 100.0},
        ]
        att = loopprof.attribution(events, 0, 1_000_000_000)
        assert att["gossip_pct"] == 10.0
        assert att["idle_pct"] == 90.0

    def test_events_outside_the_interval_are_excluded(self):
        inside = {"t_ns": 500, "kind": "loop.busy", "interval_ms": 1.0, "rpc_ms": 1.0}
        before = {"t_ns": 0, "kind": "loop.busy", "interval_ms": 1.0, "rpc_ms": 99.0}
        after = {"t_ns": 2_000, "kind": "loop.busy", "interval_ms": 1.0, "rpc_ms": 99.0}
        att = loopprof.attribution([before, inside, after], 0, 1_000)
        assert att is not None and "rpc_pct" in att

    def test_none_without_profiler_events(self):
        assert loopprof.attribution([{"t_ns": 5, "kind": "commit"}], 0, 10) is None
        assert loopprof.attribution([], 0, 1_000) is None
        assert loopprof.attribution([], 10, 10) is None  # empty interval
