"""The lite rig with the program's real verify engine on the CPU (the XLA
Straus kernel; the other whole runs stand the device in): `lite_start.with_engine`
installs it from outside as Node.start does, the rig finds it on the proxy,
warms it through `until_device` and `settle`, and reads its own
`verify.dispatch` events.  The whole run is marked slow (the kernel's compile
takes 40 s of every core here and a commit over a second, which a loaded
tier-1 run cannot spare: the chip runs are its proof) and drives its own
event loop (the suite's coroutine tests are cut at 120 s); what
`with_engine` installs is checked without a compile."""

import asyncio
import os
import sys
import time
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import lite_start  # noqa: E402
from test_bench_lite import BENCH, CONFIG, SEED, failed_checks  # noqa: E402

from benchmarks import harness  # noqa: E402
from benchmarks.rigs import lite  # noqa: E402

HEIGHTS = 80


async def test_with_engine_installs_what_node_start_installs(monkeypatch):
    """Without a compile: the hooks, the warm-up mode, the recorder's event,
    the default `[tpu]` config; and a proxy that came with an engine keeps it."""
    from tendermint_tpu.config import TPUConfig
    from tendermint_tpu.crypto import batch as crypto_batch
    from tendermint_tpu.crypto.batch_verifier import BatchVerifier, TableCache

    def start_warmup(self):
        self._warmup_mode = True  # and no bucket compiled behind the test's back
        return self

    monkeypatch.setattr(BatchVerifier, "start_warmup", start_warmup)
    monkeypatch.setattr(BatchVerifier, "chunked_auto", lambda self: False)  # the RTT probe's thread
    proxy = types.SimpleNamespace()

    async def program_start(**settings):
        assert settings == {"chain_id": "toy"}
        return proxy

    monkeypatch.setattr(lite, "program_start", program_start)
    try:
        assert await lite_start.with_engine(chain_id="toy") is proxy
        verifier, cache = proxy.batch_verifier, proxy.table_cache
        assert isinstance(verifier, BatchVerifier) and isinstance(cache, TableCache)
        assert crypto_batch.get_verifier() == verifier.verify
        assert crypto_batch.get_indexed_verifier() == cache.verify_indexed
        assert cache.verifier is verifier and verifier.recorder is proxy.flight_recorder
        assert verifier._warmup_mode and verifier.min_device_batch == TPUConfig().min_device_batch
        (engine,) = proxy.flight_recorder.events(kinds=["verify.engine"])
        assert engine["ok"] is True and engine["shards"] == verifier.shards
        assert await lite_start.with_engine(chain_id="toy") is proxy  # the program's own is kept
        assert proxy.batch_verifier is verifier and proxy.table_cache is cache
        stripped = await lite_start.without_engine(chain_id="toy")
        assert stripped.batch_verifier is None and stripped.flight_recorder is not None
    finally:
        harness.unplant_faults()
    assert crypto_batch.get_indexed_verifier() is None


@pytest.mark.slow  # ~100 s of XLA compile and kernel on the CPU: run it by hand, `-m slow`
def test_the_engine_installed_from_outside_serves_a_whole_run(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    traffic = {"name": "lite-sequence", "kind": "lite", "mode": "sequence", "gap": 1,
               "txs_per_block": 1, "tx_bytes": 40, "warm_in_blocks": 2, "heights": {"toy-24": HEIGHTS}}
    cell = harness.Cell("toy-24.lite-sequence", 1, CONFIG, traffic, HEIGHTS,
                        end_to_end=BENCH["end_to_end"], per_layer=BENCH["per_layer"])
    result = asyncio.run(harness.run_cell(
        cell, SEED, 5.0, True, time.monotonic(), start=lite_start.with_engine,
    ))
    assert failed_checks(result) == set(), result["checks"]
    assert result["correct"] is True and result["attempted"] >= 1
    warm = result["context"]["warm"]
    assert warm["path"] == "indexed" and warm["bucket"] >= 24 and warm["shards"] == 1
    assert result["context"]["rtt_probe"] is not None  # the engine's own, found on the proxy
    got = result["metrics"]
    assert got["dispatches_per_block"]["value"] == 1.0 and got["table_hit_share"]["value"] == 100.0
    assert got["engine_wait_ms_per_block"]["value"] > 1.0  # a real kernel, on the CPU
    assert 0 < got["useful_rows_share"]["value"] <= 100.0
    # the hooks went with the run
    from tendermint_tpu.crypto import batch as crypto_batch

    assert crypto_batch.get_indexed_verifier() is None
