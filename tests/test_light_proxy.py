"""`tendermint_tpu light` on the verify engine: the engine's one set-up that
the node and the light proxy share (crypto/engine.py), `start_proxy` /
`run_proxy`, the `light` command's options, and the light client's
`lite.header` span per header made its own.

No test here compiles a kernel: warm-up mode is switched on without its
bucket compile, the RTT probe and the table builds are left out, and the
toy commits (4 signatures) ride the host path below `min_device_batch`.
"""

import asyncio
import json

import pytest
from aiohttp import web

from test_lite2 import CHAIN, PERIOD, SEC, T0, make_chain, rand_vset
from tendermint_tpu import cli
from tendermint_tpu.config import Config, TPUConfig, save_config
from tendermint_tpu.config import test_config as make_test_cfg
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto import engine as engine_mod
from tendermint_tpu.crypto.batch_verifier import BatchVerifier, TableCache
from tendermint_tpu.libs.tracing import STAMPED_KINDS, FlightRecorder
from tendermint_tpu.lite2 import BISECTION, SEQUENCE, Client, MemStore, MockProvider, TrustOptions
from tendermint_tpu.lite2 import proxy as proxy_mod
from tendermint_tpu.rpc.client import HTTPClient
from tendermint_tpu.rpc.jsonrpc import from_jsonable, make_response

HEIGHTS = 12
NOW = T0 + (HEIGHTS + 5) * SEC
STAGES = ("fetch_ms", "decode_ms", "set_hash_ms", "verify_ms", "cross_check_ms", "store_ms")
ENGINE_FIELDS = {"ok", "shards", "mesh", "host_tier"}


@pytest.fixture
def no_compile(monkeypatch):
    """The engine as it is installed, less what would compile on the CPU;
    yields the verifiers whose warm-up was switched on, once each call."""
    warmed = []

    def start_warmup(self):
        warmed.append(self)
        self._warmup_mode = True
        return self

    monkeypatch.setattr(BatchVerifier, "start_warmup", start_warmup)
    monkeypatch.setattr(BatchVerifier, "chunked_auto", lambda self: False)
    monkeypatch.setattr(TableCache, "_build_in_background", lambda self, *a, **kw: None)
    yield warmed
    crypto_batch.set_verifier(None)
    crypto_batch.set_indexed_verifier(None)


@pytest.fixture(scope="module")
def chain():
    vset, pvs = rand_vset(4)
    return make_chain(HEIGHTS, {1: (vset, pvs)})


async def serve(headers, vals):
    """A primary over JSON-RPC: `commit` and `validators` (paged) of a chain."""
    async def handle(request):
        req = json.loads(await request.text())
        params = from_jsonable(req.get("params") or {})
        h = params.get("height") or max(headers)
        if req["method"] == "commit":
            result = {"signed_header": headers[h], "canonical": True}
        else:
            page, per = params.get("page", 1), params.get("per_page", 30)
            members = vals[h].validators
            result = {"block_height": h, "total": len(members),
                      "validators": [v.to_dict() for v in members[(page - 1) * per: page * per]]}
        return web.json_response(make_response(req.get("id"), result))

    app = web.Application()
    app.router.add_post("/", handle)
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    return runner, "%s:%d" % site._server.sockets[0].getsockname()[:2]


def settings(headers, addr, **more):
    return dict(
        chain_id=CHAIN, primary_addr=addr, witness_addrs=[addr], laddr="tcp://127.0.0.1:0",
        trust_height=1, trust_hash=headers[1].header.hash(), trusting_period_s=PERIOD / SEC,
        now_fn=lambda: NOW, **more,
    )


async def close(proxy):
    await proxy.stop()
    client = proxy.client
    for provider in [client.primary, *client.witnesses]:
        await provider.close()


def header_spans(recorder):
    return recorder.events(kinds=["lite.header"])


# -- the engine's one set-up ----------------------------------------------------------


async def test_start_proxy_starts_the_engine_on_its_own_recorder(chain, no_compile):
    headers, vals = chain
    runner, addr = await serve(headers, vals)
    try:
        proxy = await proxy_mod.start_proxy(**settings(headers, addr, mode=SEQUENCE))
        try:
            verifier, cache, recorder = proxy.batch_verifier, proxy.table_cache, proxy.flight_recorder
            assert isinstance(verifier, BatchVerifier) and isinstance(cache, TableCache)
            assert proxy.listen_addr and isinstance(recorder, FlightRecorder)
            assert verifier.recorder is recorder and cache.verifier is verifier
            assert proxy.client.recorder is recorder
            assert crypto_batch.get_verifier() == verifier.verify
            assert crypto_batch.get_indexed_verifier() == cache.verify_indexed
            assert no_compile == [verifier] and verifier.min_device_batch == TPUConfig().min_device_batch
            (event,) = recorder.events(kinds=["verify.engine"])
            assert set(event) - {"seq", "t_ns", "kind"} == ENGINE_FIELDS
            assert event["ok"] is True and event["shards"] == verifier.shards

            rpc = HTTPClient(proxy.listen_addr)
            got = await rpc.commit(6)
            await rpc.close()
            assert got["signed_header"].header.hash() == headers[6].header.hash()
            spans = header_spans(recorder)
            assert [ev["id"] for ev in spans] == [2, 3, 4, 5, 6]
            for ev in spans:
                assert ev["mode"] == SEQUENCE and ev["parent"] is None and "stamps" in ev
                assert ev["fetch_ms"] > 0 and ev["verify_ms"] > 0
                assert ev["decode_ms"] == 0.0  # inside fetch_ms: no provider times it apart
                assert sum(ev[s] for s in STAGES) <= ev["dur_ns"] / 1e6 + 0.01
            # the witness is asked about the header asked for, not the steps before it
            assert [ev["cross_check_ms"] > 0 for ev in spans] == [False] * 4 + [True]
            commits = recorder.events(kinds=["verify.commit"])
            assert [(ev["parent"], ev["id"]) for ev in commits] == [("lite.header", h) for h in range(2, 7)]
        finally:
            await close(proxy)
        assert crypto_batch.get_verifier() == crypto_batch.host_batch_verify
        assert crypto_batch.get_indexed_verifier() is None
    finally:
        await runner.cleanup()


async def test_start_proxy_with_the_engine_off_starts_none(chain, no_compile):
    headers, vals = chain
    runner, addr = await serve(headers, vals)
    try:
        proxy = await proxy_mod.start_proxy(**settings(headers, addr, tpu=TPUConfig(enabled=False)))
        try:
            assert proxy.batch_verifier is None and proxy.table_cache is None
            assert proxy.flight_recorder is not None and proxy.client.mode == BISECTION
            assert proxy.flight_recorder.events(kinds=["verify.engine"]) == []
            assert crypto_batch.get_verifier() == crypto_batch.host_batch_verify
            assert crypto_batch.get_indexed_verifier() is None
        finally:
            await close(proxy)
    finally:
        await runner.cleanup()


async def test_run_proxy_runs_until_cancelled(chain, no_compile, monkeypatch):
    headers, vals = chain
    runner, addr = await serve(headers, vals)
    started = []
    start = proxy_mod.start_proxy

    async def recording(**kw):
        started.append(await start(**kw))
        return started[-1]

    monkeypatch.setattr(proxy_mod, "start_proxy", recording)
    try:
        task = asyncio.ensure_future(proxy_mod.run_proxy(**settings(headers, addr)))
        while not started:
            await asyncio.sleep(0.01)
        (proxy,) = started
        assert crypto_batch.get_verifier() == proxy.batch_verifier.verify
        await asyncio.sleep(0.2)
        assert not task.done()
        task.cancel()
        assert await task is None
        assert crypto_batch.get_verifier() == crypto_batch.host_batch_verify  # stopped with it
        for provider in [proxy.client.primary, *proxy.client.witnesses]:
            await provider.close()
    finally:
        await runner.cleanup()


async def test_node_start_sets_the_engine_up_through_the_shared_function(tmp_path, no_compile, monkeypatch):
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

    calls = []
    shared = engine_mod.start_engine

    def recording(tpu_cfg, recorder, **kw):
        calls.append((tpu_cfg, recorder, kw))
        return shared(tpu_cfg, recorder, **kw)

    monkeypatch.setattr(engine_mod, "start_engine", recording)
    pv = MockPV()
    cfg = make_test_cfg(str(tmp_path / "node"))
    cfg.rpc.laddr = ""
    cfg.p2p.laddr = "127.0.0.1:0"
    cfg.base.db_backend = "memdb"
    cfg.tpu.enabled = True
    gen = GenesisDoc(chain_id=CHAIN, genesis_time_ns=T0,
                     validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10)])
    node = Node(cfg, gen, priv_validator=pv, db_backend="memdb")
    await node.start()
    try:
        ((tpu_cfg, recorder, kw),) = calls
        assert tpu_cfg is cfg.tpu and recorder is node.flight_recorder
        assert kw["metrics"] is node.metrics_provider.verify
        assert crypto_batch.get_verifier() == node.batch_verifier.verify
        assert crypto_batch.get_indexed_verifier() == node.table_cache.verify_indexed
        assert node.async_verifier.verifier is node.batch_verifier
        assert node.batch_verifier.metrics is node.metrics_provider.verify
        (event,) = node.flight_recorder.events(kinds=["verify.engine"])
        assert set(event) - {"seq", "t_ns", "kind"} == ENGINE_FIELDS
        assert event["shards"] == node.batch_verifier.shards
        assert no_compile == [node.batch_verifier]  # warm-up switched on once, by the AsyncBatchVerifier
    finally:
        await node.stop()
    assert crypto_batch.get_verifier() == crypto_batch.host_batch_verify
    assert crypto_batch.get_indexed_verifier() is None


# -- the `light` command ---------------------------------------------------------------


@pytest.fixture
def light_settings(monkeypatch):
    got = {}

    async def run_proxy(**kw):
        got.update(kw)

    monkeypatch.setattr(proxy_mod, "run_proxy", run_proxy)
    return got


def light(home, *more):
    return cli.main(["--home", str(home), "light", "--chain-id", CHAIN, "--primary", "127.0.0.1:1",
                     "--height", "1", "--hash", "ab" * 32, *more])


def test_light_skips_by_default_and_runs_the_default_engine(tmp_path, light_settings):
    assert light(tmp_path) == 0
    assert light_settings["mode"] == BISECTION
    assert light_settings["tpu"] == TPUConfig()
    assert light_settings["trust_hash"] == b"\xab" * 32 and light_settings["witness_addrs"] == []


def test_light_sequential_passes_sequence_to_the_client(tmp_path, light_settings):
    assert light(tmp_path, "--sequential") == 0
    assert light_settings["mode"] == SEQUENCE
    assert light_settings["tpu"] == TPUConfig()


def test_light_takes_the_tpu_table_of_its_home(tmp_path, light_settings):
    cfg = Config(home=str(tmp_path))
    cfg.tpu.min_device_batch, cfg.tpu.enabled = 48, False
    cfg.ensure_dirs()
    save_config(cfg, str(tmp_path / "config" / "config.toml"))
    assert light(tmp_path) == 0
    assert light_settings["tpu"].min_device_batch == 48 and not light_settings["tpu"].enabled


# -- a span per header ------------------------------------------------------------------


def client_over(headers, vals, mode, recorder, witness=True):
    provider = MockProvider(CHAIN, headers, vals)
    return Client(
        CHAIN, TrustOptions(PERIOD, 1, headers[1].header.hash()), provider,
        witnesses=[MockProvider(CHAIN, headers, vals)] if witness else [], store=MemStore(),
        mode=mode, now_fn=lambda: NOW, recorder=recorder,
    )


@pytest.mark.parametrize("mode", [SEQUENCE, BISECTION])
async def test_one_header_span_per_header_made_own(chain, mode):
    headers, vals = chain
    recorder = FlightRecorder()
    client = client_over(headers, vals, mode, recorder)
    await client.verify_header_at_height(5)
    await client.verify_header_at_height(9)
    await client.verify_header_at_height(7)  # below the trusted: backwards, no span
    spans = header_spans(recorder)
    made_own = [2, 3, 4, 5, 6, 7, 8, 9] if mode == SEQUENCE else [5, 9]
    assert [ev["id"] for ev in spans] == made_own
    assert set(made_own) | {7} <= set(client.store.heights())
    for ev in spans:
        assert set(STAGES) <= set(ev) and ev["mode"] == mode and "ok" not in ev
        assert ev["decode_ms"] == 0.0  # a provider that hands objects: nothing to decode
        assert ev["stamps"] and "lite.header" in STAMPED_KINDS
        if ev["id"] not in (5, 9):
            assert ev["cross_check_ms"] == 0.0
    # the commit checks open under the header: one a step, two a jump (the
    # trusted set's by address, then the new set's)
    commits = recorder.events(kinds=["verify.commit"])
    assert all(ev["parent"] == "lite.header" for ev in commits)
    per_header = {h: sum(1 for ev in commits if ev["id"] == h) for h in made_own}
    assert set(per_header.values()) == ({1} if mode == SEQUENCE else {2})


async def test_a_jump_the_trusted_set_cannot_vouch_for_is_the_next_headers_span():
    vset_a, pvs_a = rand_vset(4)
    vset_b, pvs_b = rand_vset(4)
    headers, vals = make_chain(HEIGHTS, {1: (vset_a, pvs_a), 7: (vset_b, pvs_b)})
    recorder = FlightRecorder()
    client = client_over(headers, vals, BISECTION, recorder, witness=False)
    await client.verify_header_at_height(HEIGHTS)
    spans = header_spans(recorder)
    new = sorted(set(client.store.heights()) - {1})
    assert sorted(ev["id"] for ev in spans) == new and spans[-1]["id"] == HEIGHTS
    assert len(recorder.events(kinds=["verify.commit"])) > 2 * len(spans) - 1  # the failed jumps' too


async def test_a_header_that_fails_closes_its_span_as_failed(chain):
    headers, vals = chain
    other, _ = rand_vset(4)
    recorder = FlightRecorder()
    client = client_over(headers, {**vals, 4: other}, SEQUENCE, recorder)
    with pytest.raises(Exception, match="to match those supplied"):
        await client.verify_header_at_height(6)
    spans = header_spans(recorder)
    assert [ev["id"] for ev in spans] == [2, 3, 4]
    assert spans[-1]["ok"] is False and "to match those supplied" in spans[-1]["error"]
    assert client.store.signed_header(4) is None
