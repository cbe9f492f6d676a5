"""A primary (or a witness) for a light client: a generated chain served
over the node's JSON-RPC, as the program's own `HTTPProvider` asks for it.

`python benchmarks/primary.py --chain <dir> --config <file> --seed <n>` prints
one JSON line `{"addr": "127.0.0.1:<port>"}` and serves until its stdin
closes.  A line `{"chain": <dir>, "lie_at": <height>}` on its stdin makes it
serve that chain from then on, under the same address (it answers
`{"heights": n}`).  When its stdin closes it prints one more line, what it served:
`{"calls": {"commit": n, "validators": n, "status": n}, "commits": [[t_ns,
height], ...]}`, each `/commit` with the `time.monotonic_ns()` at which it
was answered (one clock for every process of a machine, so the rig can tell
which of them fell into its window).

It is to RPC what source.py is to p2p.  Three routes and no more: `commit`
(`height`; none or 0 is the last height whose commit the chain holds, one
short of its length: a height's commit rides in the next block),
`validators` (`height`, `page`, `per_page`: 100 a page as the provider pages,
so two pages at 175) and `status`.  The answers are in the program's own
JSON encoding (rpc/jsonrpc.py), built from the program's types over the
generator's bytes; nothing is verified here and nothing executed.  A commit
is decoded and encoded as it is asked for, and the one the client is likely
to ask for next (the same stride on) while the loop is idle, as a node would
have it in a cache; a validator set's pages are encoded once per set.

`--lie-at <height>`: at that height, serve a header with another app hash,
under a commit that names the forged header's hash and carries the true
commit's signatures, which therefore do not verify over it.  Every other
check a light client makes passes (the header's own consistency, the
validator hashes, the time, and a witness that tells the same lie), so that
signature verification is the one defence left: the control
`--fault lying_primary` of benchmarks/rigs/lite.py.

Never imports JAX: it is started with JAX_PLATFORMS=cpu and touches no
engine.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import mmap
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import chain as chainlib  # noqa: E402

PER_PAGE_MAX = 100  # rpc/core's cap, which the provider asks for
KEPT_COMMITS = 64  # encoded answers kept: the client's next few, not the chain


class ChainRPC:
    """The three routes over one generated chain."""

    def __init__(self, chain_dir: str, config: dict, seed: int, lie_at: int = 0):
        _, pubs, powers = chainlib.committee(seed, config)
        self._genesis = list(zip(pubs, powers))
        self.calls = {"commit": 0, "validators": 0, "status": 0}
        self.commits_served: list = []  # [time.monotonic_ns(), height]
        self._last_asked = 0
        self.load(chain_dir, lie_at)

    def load(self, chain_dir: str, lie_at: int = 0) -> None:
        """Serve this chain from now on (the same seed's, longer: the rig
        starts its primaries over the chain's first heights while the rest
        is made)."""
        self.meta = chainlib.load_meta(chain_dir)
        if self.meta is None:
            raise SystemExit(f"primary.py: no finished chain in {chain_dir}")
        self._file = open(os.path.join(chain_dir, "blocks.bin"), "rb")
        self._map = mmap.mmap(self._file.fileno(), 0, access=mmap.ACCESS_READ)
        self.history = chainlib.set_history(self._genesis, self.meta)
        self.lie_at = lie_at
        self.tip = self.meta["heights"] - 1  # the last height with its commit in the chain
        self._commit_json: dict = {}  # height -> the answer's `result`, as JSON text
        self._pages_json: dict = {}  # (set index, page, per_page) -> the same

    def _block(self, height: int):
        from tendermint_tpu.types import Block

        offsets = self.meta["offsets"]
        return Block.deserialize(self._map[offsets[height - 1]: offsets[height]])

    def signed_header(self, height: int):
        """The header of `height` and its commit, which the next block
        carries; forged at `lie_at`."""
        from tendermint_tpu.types import BlockID, Commit, SignedHeader

        header, commit = self._block(height).header, self._block(height + 1).last_commit
        if height == self.lie_at:
            header = dataclasses.replace(header, app_hash=bytes(32))
            forged_id = BlockID(header.hash(), commit.block_id.parts_header)
            commit = Commit(commit.height, commit.round, forged_id, list(commit.signatures))
        return SignedHeader(header, commit)

    def _commit_result(self, height: int) -> str:
        from tendermint_tpu.rpc.jsonrpc import to_jsonable

        text = self._commit_json.get(height)
        if text is None:
            text = json.dumps(to_jsonable(
                {"signed_header": self.signed_header(height), "canonical": True}
            ))
            while len(self._commit_json) >= KEPT_COMMITS:
                self._commit_json.pop(next(iter(self._commit_json)))
            self._commit_json[height] = text
        return text

    def _height(self, height) -> int:
        from tendermint_tpu.rpc.jsonrpc import INVALID_PARAMS, RPCError

        h = self.tip if not height else int(height)
        if not 1 <= h <= self.tip:
            raise RPCError(INVALID_PARAMS, f"height {h} is not in 1..{self.tip}")
        return h

    def commit(self, height=None) -> str:
        h = self._height(height)
        self.calls["commit"] += 1
        text = self._commit_result(h)
        self.commits_served.append([time.monotonic_ns(), h])
        return text

    def encode_ahead(self, height: int) -> None:
        """Encode what a client that asked for `height` is likely to ask for
        next: the height as far on as this one was from the one before."""
        stride, self._last_asked = height - self._last_asked, height
        if 1 <= height + stride <= self.tip:
            self._commit_result(height + stride)

    def validators(self, height=None, page: int = 1, per_page: int = 30) -> str:
        from tendermint_tpu.crypto.keys import Ed25519PubKey
        from tendermint_tpu.rpc.jsonrpc import INVALID_PARAMS, RPCError, to_jsonable
        from tendermint_tpu.types import Validator

        h = self._height(height)
        self.calls["validators"] += 1
        per_page = max(1, min(int(per_page), PER_PAGE_MAX))
        key = (self.history.index(h), int(page), per_page)
        text = self._pages_json.get(key)
        if text is None:
            pubs, powers = self.history.at(h)
            lo = (key[1] - 1) * per_page
            if not 0 <= lo < len(pubs):
                raise RPCError(INVALID_PARAMS, f"page {page} is past the set's {len(pubs)}")
            members = [
                Validator.new(Ed25519PubKey(pub), power).to_dict()
                for pub, power in zip(pubs[lo: lo + per_page], powers[lo: lo + per_page])
            ]
            text = self._pages_json[key] = json.dumps(to_jsonable(
                {"validators": members, "count": len(members), "total": len(pubs)}
            ))
        # the height leads, so that a set's pages are encoded once for all heights
        return '{"block_height": %d, %s' % (h, text[1:])

    def status(self) -> str:
        from tendermint_tpu.rpc.jsonrpc import to_jsonable

        self.calls["status"] += 1
        return json.dumps(to_jsonable({
            "node_info": {"network": self.meta["chain_id"], "moniker": "bench-primary"},
            "sync_info": {
                "latest_block_height": self.tip,
                "latest_block_hash": bytes.fromhex(self.meta["hashes"][self.tip - 1]),
                "catching_up": False,
            },
        }))


async def serve(rpc: ChainRPC) -> None:
    from aiohttp import web

    from tendermint_tpu.rpc.jsonrpc import INTERNAL_ERROR, INVALID_PARAMS, RPCError

    routes = {"commit": rpc.commit, "validators": rpc.validators, "status": rpc.status}
    loop = asyncio.get_running_loop()

    def answer(req_id, method: str, params: dict) -> web.Response:
        try:
            route = routes.get(method)
            if route is None:
                raise RPCError(INVALID_PARAMS, f"this primary serves {sorted(routes)}, not {method!r}")
            body = '{"jsonrpc": "2.0", "id": %s, "result": %s}' % (
                json.dumps(req_id), route(**params)
            )
        except RPCError as exc:
            body = json.dumps({"jsonrpc": "2.0", "id": req_id, "error": exc.to_dict()})
        except Exception as exc:  # noqa: BLE001 — the client gets the error, as from a node
            body = json.dumps({"jsonrpc": "2.0", "id": req_id,
                               "error": RPCError(INTERNAL_ERROR, repr(exc)).to_dict()})
        if method == "commit" and params.get("height"):
            loop.call_soon(rpc.encode_ahead, int(params["height"]))
        return web.Response(text=body, content_type="application/json")

    async def post(request: web.Request) -> web.Response:
        req = json.loads(await request.read())
        params = {k: v for k, v in (req.get("params") or {}).items() if v is not None}
        return answer(req.get("id"), req.get("method", ""), params)

    app = web.Application()
    app.router.add_post("/", post)  # the JSON-RPC envelope, as the program's HTTPClient sends it
    runner = web.AppRunner(app, access_log=None)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]  # noqa: SLF001
    print(json.dumps({"addr": f"127.0.0.1:{port}"}), flush=True)
    try:
        while True:  # until the rig closes stdin
            line = await loop.run_in_executor(None, sys.stdin.readline)
            if not line:
                break
            order = json.loads(line)  # {"chain": <dir>, "lie_at": <height>}
            rpc.load(order["chain"], order.get("lie_at", 0))
            print(json.dumps({"heights": rpc.meta["heights"]}), flush=True)
    finally:
        await runner.cleanup()
        print(json.dumps({"calls": rpc.calls, "commits": rpc.commits_served}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="serve one generated chain over JSON-RPC")
    ap.add_argument("--chain", required=True, help="a generated chain's directory")
    ap.add_argument("--config", required=True, help="the configuration's file (the committee)")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--lie-at", type=int, default=0,
                    help="serve a forged header at this height (the control)")
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    from tendermint_tpu.libs.log import setup as log_setup

    log_setup(module_levels={"*": "error"})
    asyncio.run(serve(ChainRPC(args.chain, config, args.seed, args.lie_at)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
