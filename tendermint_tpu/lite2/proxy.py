"""Light-client RPC proxy: serve a verifying subset of the RPC surface.

Reference parity: lite2/proxy/proxy.go + lite2/rpc/client.go (`tendermint
lite`): every header/commit the proxy serves has been light-verified
against the trust root; blocks are checked against their verified header
before forwarding.

`start_proxy` starts what `tendermint_tpu light` runs: the client, the
verify engine the node runs too (crypto/engine.py, from a `[tpu]` config),
and the proxy, all reporting to one flight recorder.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Optional

from aiohttp import web

from ..libs.log import get_logger
from ..rpc.jsonrpc import (
    INTERNAL_ERROR,
    INVALID_PARAMS,
    RPCError,
    make_response,
    read_bounded_body,
)
from .client import BISECTION, Client, TrustOptions
from .provider import HTTPProvider

#: same default budget as RPCConfig.max_body_bytes — a light proxy faces
#: the same untrusted clients a full node's RPC does
DEFAULT_MAX_BODY_BYTES = 1_000_000


class LightProxy:
    """Wraps a lite2.Client + the primary's RPC client; exposes verified
    routes over HTTP JSON-RPC (GET URI + POST envelope)."""

    def __init__(self, client: Client, laddr: str, max_body_bytes: int = DEFAULT_MAX_BODY_BYTES):
        self.client = client
        self.laddr = laddr
        self.max_body_bytes = max_body_bytes
        self.log = get_logger("lite2.proxy")
        self._runner: Optional[web.AppRunner] = None
        self.listen_addr = ""
        # set by start_proxy: what the client's spans and the engine report
        # to, and the engine (None where [tpu] is off)
        self.flight_recorder = None
        self.batch_verifier = None
        self.table_cache = None

    # -- verified handlers -------------------------------------------------

    async def _commit(self, height: int = 0) -> dict:
        if height == 0:
            sh = await self.client.update()
            if sh is None:
                sh = await self.client.trusted_header()
        else:
            sh = await self.client.verify_header_at_height(height)
        return {"signed_header": sh, "canonical": True}

    async def _block(self, height: int = 0) -> dict:
        sh = (await self._commit(height))["signed_header"]
        res = await self.client.primary.client.block(sh.height)
        blk = res.get("block")
        if blk is None or blk.hash() != sh.header.hash():
            raise RPCError(INTERNAL_ERROR, "primary served a block not matching verified header")
        return res

    async def _validators(self, height: int = 0) -> dict:
        sh = (await self._commit(height))["signed_header"]
        vals = self.client.store.validator_set(sh.height)
        if vals is None:
            vals = await self.client.primary.validator_set(sh.height)
            if sh.header.validators_hash != vals.hash():
                raise RPCError(INTERNAL_ERROR, "primary served wrong validator set")
        return {
            "block_height": sh.height,
            "validators": [v.to_dict() for v in vals.validators],
            "total": vals.size(),
        }

    async def _status(self) -> dict:
        latest = await self.client.trusted_header()
        return {
            "light_client": True,
            "chain_id": self.client.chain_id,
            "latest_trusted_height": latest.height if latest else 0,
            "latest_trusted_hash": latest.header.hash() if latest else b"",
        }

    ROUTES = {
        "commit": "_commit",
        "block": "_block",
        "validators": "_validators",
        "status": "_status",
    }

    # -- server ------------------------------------------------------------

    async def start(self) -> None:
        await self.client.initialize()
        app = web.Application()
        app.router.add_post("/", self._handle_post)
        app.router.add_get("/{method}", self._handle_get)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        addr = self.laddr.split("://", 1)[-1]
        host, _, port = addr.rpartition(":")
        site = web.TCPSite(self._runner, host or "127.0.0.1", int(port))
        await site.start()
        server = site._server  # noqa: SLF001
        if server and server.sockets:
            self.listen_addr = "%s:%d" % server.sockets[0].getsockname()[:2]
        self.log.info("light proxy listening", laddr=self.listen_addr)

    async def stop(self) -> None:
        from ..crypto.engine import stop_engine

        if self._runner is not None:
            await self._runner.cleanup()
        stop_engine(self.batch_verifier, self.table_cache)

    async def _dispatch(self, method: str, params: dict, req_id) -> dict:
        name = self.ROUTES.get(method)
        if name is None:
            return make_response(req_id, error=RPCError(INVALID_PARAMS, f"unknown route {method}"))
        try:
            return make_response(req_id, await getattr(self, name)(**params))
        except RPCError as e:
            return make_response(req_id, error=e)
        except Exception as e:  # noqa: BLE001
            return make_response(req_id, error=RPCError(INTERNAL_ERROR, repr(e)))

    async def _handle_post(self, request: web.Request) -> web.Response:
        from ..rpc.jsonrpc import from_jsonable

        # bounded read BEFORE json.loads — the lite proxy rides the same
        # discipline as the full node's RPC ingress (rpc/server.py)
        try:
            body = await read_bounded_body(request, self.max_body_bytes)
        except RPCError as e:
            return web.json_response(make_response(None, error=e))
        try:
            req = json.loads(body)
        except (ValueError, UnicodeDecodeError):
            return web.json_response(make_response(None, error=RPCError(-32700, "bad JSON")))
        if not isinstance(req, dict):
            return web.json_response(
                make_response(None, error=RPCError(-32600, "malformed request"))
            )
        params = from_jsonable(req.get("params") or {})
        return web.json_response(await self._dispatch(req.get("method", ""), params, req.get("id")))

    async def _handle_get(self, request: web.Request) -> web.Response:
        params = {}
        for k, v in request.query.items():
            try:
                params[k] = int(v)
            except ValueError:
                params[k] = v
        return web.json_response(
            await self._dispatch(request.match_info["method"], params, -1)
        )


async def start_proxy(
    chain_id: str,
    primary_addr: str,
    witness_addrs,
    laddr: str,
    trust_height: int,
    trust_hash: bytes,
    trusting_period_s: float,
    mode: str = BISECTION,
    now_fn=time.time_ns,
    tpu=None,
) -> LightProxy:
    """Build the client over the primary and its witnesses, start the verify
    engine of `tpu` (a `config.TPUConfig`; default its defaults) on a flight
    recorder of the proxy's own, and start the proxy; returns it with
    `flight_recorder`, `batch_verifier` and `table_cache` set."""
    from ..config import TPUConfig
    from ..crypto.engine import start_engine
    from ..libs.tracing import FlightRecorder

    recorder = FlightRecorder()
    client = Client(
        chain_id,
        TrustOptions(int(trusting_period_s * 1e9), trust_height, trust_hash),
        HTTPProvider(chain_id, primary_addr),
        witnesses=[HTTPProvider(chain_id, w) for w in witness_addrs],
        mode=mode,
        now_fn=now_fn,
        recorder=recorder,
    )
    proxy = LightProxy(client, laddr)
    proxy.flight_recorder = recorder
    proxy.batch_verifier, proxy.table_cache = start_engine(
        tpu if tpu is not None else TPUConfig(), recorder, log=proxy.log
    )
    if proxy.batch_verifier is not None:
        # no AsyncBatchVerifier here to switch it on, as the node's does
        proxy.batch_verifier.start_warmup()  # compiles on its own thread; host path until warm
    try:
        await proxy.start()
    except BaseException:
        await proxy.stop()
        raise
    return proxy


async def run_proxy(**settings) -> None:
    """CLI entry (`light` command): start_proxy(**settings), then run until
    cancelled."""
    proxy = await start_proxy(**settings)
    try:
        while True:
            await asyncio.sleep(3600)
    except asyncio.CancelledError:
        pass
    finally:
        await proxy.stop()
