"""Transaction indexer: indexes TxResults by hash + composite event keys.

Reference parity: state/txindex/ (TxIndexer iface indexer.go,
IndexerService indexer_service.go — subscribes to the EventBus;
kv impl state/txindex/kv/kv.go — keys `<event.key>/<value>/<height>/<index>`
powering tx_search).
"""

from __future__ import annotations

import collections
from typing import Deque, Dict, List, Optional, Tuple

from ..encoding import codec
from ..libs.events import Query
from ..libs.kvstore import KVStore
from ..libs.service import Service
from ..types import events as tme
from ..types.tx import tx_hash


class TxIndexer:
    """kv indexer (state/txindex/kv/kv.go)."""

    def __init__(self, db: KVStore, index_all_events: bool = True):
        self.db = db
        self.index_all_events = index_all_events

    @staticmethod
    def _k_hash(h: bytes) -> bytes:
        return b"tx.hash/" + h

    @staticmethod
    def _esc(s: str) -> str:
        # '/' delimits key segments; attacker-controlled ABCI event values
        # must not be able to inject separators into the composite key
        from urllib.parse import quote

        return quote(s, safe="")

    @classmethod
    def _k_event(cls, key: str, value: str, height: int, index: int) -> bytes:
        return f"ev/{cls._esc(key)}/{cls._esc(value)}/{height:020d}/{index:010d}".encode()

    def index(self, tx_result: dict, events: Optional[Dict[str, List[str]]] = None) -> None:
        """tx_result = {"height", "index", "tx", "result": {...}}."""
        h = tx_hash(tx_result["tx"])
        payload = codec.dumps(tx_result)
        sets = [(self._k_hash(h), payload)]
        if self.index_all_events and events:
            for key, values in events.items():
                if key == tme.TX_HASH_KEY:
                    continue
                for v in values:
                    sets.append(
                        (
                            self._k_event(key, v, tx_result["height"], tx_result["index"]),
                            h,
                        )
                    )
        # reserved height key always indexed (kv/kv.go indexes tx.height)
        sets.append(
            (
                self._k_event(tme.TX_HEIGHT_KEY, str(tx_result["height"]), tx_result["height"], tx_result["index"]),
                h,
            )
        )
        self.db.write_batch(sets)

    def get(self, h: bytes) -> Optional[dict]:
        raw = self.db.get(self._k_hash(h))
        return codec.loads(raw) if raw else None

    def search(self, query: Query | str, limit: int = 100) -> List[dict]:
        """Subset of kv.go Search: equality + range conditions over indexed
        event keys, intersected."""
        if isinstance(query, str):
            query = Query.parse(query)
        from urllib.parse import unquote

        result_sets: List[set] = []
        for cond in query.conditions:
            hashes = set()
            if cond.op == "=":
                prefix = f"ev/{self._esc(cond.tag)}/{self._esc(str(cond.operand))}/".encode()
                for _, h in self.db.iterate_prefix(prefix):
                    hashes.add(h)
            else:
                # range/exists scans walk every value under the tag
                prefix = f"ev/{self._esc(cond.tag)}/".encode()
                for k, h in self.db.iterate_prefix(prefix):
                    value = unquote(k.decode().split("/")[2])
                    if cond.matches({cond.tag: [value]}):
                        hashes.add(h)
            result_sets.append(hashes)
        if not result_sets:
            return []
        matched = set.intersection(*result_sets)
        out = []
        for h in sorted(matched):
            r = self.get(h)
            if r is not None:
                out.append(r)
            if len(out) >= limit:
                break
        return out


class NullTxIndexer:
    """state/txindex/null — indexing disabled."""

    def index(self, tx_result: dict, events=None) -> None:
        pass

    def get(self, h: bytes) -> Optional[dict]:
        return None

    def search(self, query, limit: int = 100) -> List[dict]:
        return []


class IndexerService(Service):
    """Subscribes to the event bus and feeds the indexer
    (state/txindex/indexer_service.go).

    The subscription is buffered and the bus cancels one that is full
    (libs/events.py, "out of capacity"): under sustained load the index then
    stops for good.  That is counted here, not mended: `block_closed()` says
    how many blocks the index is behind and how many txs it took in since
    the block before, and the cut leaves one `txindex.cut` event and one
    error line."""

    SUBSCRIBER = "tx-indexer"
    BUFFER = 10000

    def __init__(self, indexer, event_bus: tme.EventBus):
        super().__init__("indexer-service")
        self.indexer = indexer
        self.event_bus = event_bus
        self.recorder = None  # the node's FlightRecorder, for `txindex.cut`
        self.metrics = None  # the node's libs.metrics.StateMetrics
        self._task = None
        self._last = (0, -1)  # (height, index) of the last tx indexed
        self._indexed = 0  # txs indexed since the service started
        self._indexed_at_close = 0  # of them, by the last block closed
        # blocks closed and not yet wholly indexed, oldest first: (height, its last tx's index)
        self._closed: Deque[Tuple[int, int]] = collections.deque()
        self._height = 0  # the last block closed
        self.indexed_through: Optional[int] = None  # last height whose txs are all indexed

    async def on_start(self) -> None:
        sub = await self.event_bus.subscribe(
            self.SUBSCRIBER, tme.query_for_event(tme.EVENT_TX), buffer=self.BUFFER
        )
        self._sub = sub

        async def run():
            async for msg in sub:
                data = msg.data.data  # Event.data
                self.indexer.index(
                    {
                        "height": data["height"],
                        "index": data["index"],
                        "tx": data["tx"],
                        "result": data["result"],
                    },
                    msg.events,
                )
                self._last = (data["height"], data["index"])
                self._indexed += 1
            if not self._stopped:
                self._cut(sub.cancel_reason)

        # spawned, so that the loop profiler accounts its time (category `other`)
        self._task = self.spawn(run(), name="tx-indexer")

    def _cut(self, reason: str) -> None:
        """The subscription was cancelled under the service: nothing further
        will be indexed.  What was queued before the cut has been."""
        self._settle()
        self._closed.clear()
        through = self.indexed_through or 0
        self.logger.error(
            "tx indexing stopped: subscription cancelled (%s) at height %d, indexed through %d",
            reason, self._height, through,
        )
        if self.recorder is not None:
            self.recorder.record(
                "txindex.cut", height=self._height, reason=reason, indexed_through=through
            )
        if self.metrics is not None:
            self.metrics.tx_index_cuts.inc()

    def _settle(self) -> None:
        closed = self._closed
        while closed and (closed[0][1] < 0 or closed[0] <= self._last):
            self.indexed_through = closed.popleft()[0]

    def block_closed(self, height: int, n_txs: int) -> Dict[str, int]:
        """Block `height` (of `n_txs` txs, all published by now) has been
        applied.  Returns the open span's fields: `txs`, `txs_indexed` (txs
        indexed since the block before was closed) and `index_lag` (this
        height less the last one whose txs are all indexed)."""
        if self.indexed_through is None:
            self.indexed_through = height - 1
        self._height = height
        if self._task is not None and not self._task.done():
            self._closed.append((height, n_txs - 1))
            self._settle()
        lag = height - self.indexed_through
        if self.metrics is not None:
            self.metrics.tx_index_lag.set(lag)
        indexed = self._indexed - self._indexed_at_close
        self._indexed_at_close = self._indexed
        return {"txs": n_txs, "txs_indexed": indexed, "index_lag": lag}

    async def on_stop(self) -> None:
        await self.event_bus.unsubscribe_all(self.SUBSCRIBER)
