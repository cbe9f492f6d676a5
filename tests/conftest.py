"""Test configuration.

Tests run JAX on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (real-chip execution is covered by
chip_smoke.py).  Environment must be set before jax imports.  The compile
cache is placed by tendermint_tpu/ops/__init__.py.

Every coroutine test runs under a leak guard: a test that returns while
asyncio tasks are still alive on its loop FAILS (the reference runs
leaktest on every net test — long-lived stray tasks are exactly how the
round-4 reactor-starvation bug class recurs).
"""

import os

# Force cpu even if the ambient environment points at an accelerator: a
# chip belongs to one process, and the suite starts many.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
import asyncio  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def event_loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture(autouse=True)
def _loopprof_hook_guard():
    """The scheduler profiler's spawn + GC hooks are process-wide
    (libs/loopprof.py); a test that crashes between a Node's start and
    stop would leak them into every later test's Service.spawn.  Restore
    a clean slate after each test."""
    yield
    import gc

    from tendermint_tpu.libs import loopprof

    prof = loopprof._ACTIVE
    if prof is not None:
        loopprof._ACTIVE = None
        if prof._gc_cb is not None and prof._gc_cb in gc.callbacks:
            gc.callbacks.remove(prof._gc_cb)


def pytest_collection_modifyitems(config, items):
    # Provide asyncio support without the pytest-asyncio plugin: run
    # coroutine tests on a fresh event loop.
    pass


def _drain_leaked_tasks(loop, leaked):
    for t in leaked:
        t.cancel()

    async def _reap():
        await asyncio.gather(*leaked, return_exceptions=True)

    loop.run_until_complete(asyncio.wait_for(_reap(), timeout=10))


def pytest_pyfunc_call(pyfuncitem):
    import inspect

    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(asyncio.wait_for(func(**kwargs), timeout=120))
            # leak guard: the test owns this loop, so anything still alive
            # is an un-stopped service/server/background task.  Candidates
            # get a short real drain first: a cancellation cascade mid-
            # unwind (wait_for abandons the inner future on outer cancel,
            # bpo semantics) finishes in a few cycles, while a genuinely
            # un-stopped task survives the window and is flagged.
            leaked = [t for t in asyncio.all_tasks(loop) if not t.done()]
            if leaked:
                # Progress-based drain, not one fixed window: a cancellation
                # cascade mid-unwind (peer ping/send tasks, BLS pairings
                # HOLDING the GIL on executor threads) can need seconds of
                # loop time on a saturated box, but it keeps RESOLVING tasks
                # while it does — so keep draining while the pending count
                # shrinks (hard cap 10 s) and give up only once the set
                # stops making progress for 2 s.  A genuinely un-stopped
                # task (server, ticker, routine) never progresses and is
                # flagged after the same ~2 s a quiet box always paid; a
                # loaded box no longer flakes on a cascade that merely
                # needed longer (the PEX churn-soak flake class).
                deadline = loop.time() + 10.0
                last_n, last_progress = len(leaked), loop.time()
                pending = leaked
                while pending and loop.time() < deadline:
                    loop.run_until_complete(asyncio.wait(pending, timeout=0.25))
                    pending = [t for t in pending if not t.done()]
                    now = loop.time()
                    if len(pending) < last_n:
                        last_n, last_progress = len(pending), now
                    elif now - last_progress > 2.0:
                        break  # stuck, not slow: stop extending the window
                leaked = pending
            if leaked:
                names = ", ".join(
                    f"{t.get_name()}<{getattr(t.get_coro(), '__qualname__', t.get_coro())}>"
                    for t in leaked
                )
                _drain_leaked_tasks(loop, leaked)
                pytest.fail(
                    f"leak guard: test left {len(leaked)} live asyncio task(s) "
                    f"behind: {names}",
                    pytrace=False,
                )
        finally:
            loop.close()
        return True
    return None
