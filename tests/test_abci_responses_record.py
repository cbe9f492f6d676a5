"""The record `apply_block` saves of a block's ABCI responses
(`state/execution._responses_to_dict` -> `StateStore.save_abci_responses`):
built field by field from the exact response classes, it encodes to the
bytes `dataclasses.asdict` gave, shares no list or dict with the responses,
and anything else takes `asdict` and is counted as `responses_generic`."""

import copy
import random
from dataclasses import asdict, dataclass
from types import SimpleNamespace

import pytest

from tendermint_tpu.abci import types as abci
from tendermint_tpu.consensus.replay import _StoredResponsesApp
from tendermint_tpu.encoding import codec
from tendermint_tpu.libs import tracing
from tendermint_tpu.state import execution
from tendermint_tpu.state.execution import _responses_to_dict

SEEDS = (1, 2_150_400_001, 2**31 + 7)


def asdict_record(responses: dict) -> dict:
    """The record as `asdict` builds it for all three (the encoding the
    store has always held)."""
    return {
        "begin_block": asdict(responses["begin_block"]),
        "deliver_txs": [asdict(r) for r in responses["deliver_txs"]],
        "end_block": asdict(responses["end_block"]),
    }


def _events(rng, n, extra_keys=False):
    events = []
    for _ in range(n):
        attributes = []
        for _ in range(rng.randint(1, 3)):
            attr = {"key": rng.randbytes(rng.randint(1, 12)), "value": rng.randbytes(rng.randint(0, 40))}
            if extra_keys:
                attr["index"] = rng.random() < 0.5
                attr["note"] = "n%d" % rng.randrange(1000)
            attributes.append(attr)
        events.append(abci.Event(type=rng.choice(["app", "transfer", "message"]), attributes=attributes))
    return events


def _kvstore_tx(rng):
    key = b"k%06d" % rng.randrange(10**6)
    return abci.ResponseDeliverTx(events=[abci.Event(type="app", attributes=[
        {"key": b"creator", "value": b"tendermint_tpu"}, {"key": b"key", "value": key}])])


def _coded_tx(rng, n_events, extra_keys=False):
    return abci.ResponseDeliverTx(
        code=rng.choice([0, 1, 5, 2**31 - 1]),
        data=rng.randbytes(rng.randint(0, 32)),
        log="log %d" % rng.randrange(10**6),
        info=rng.choice(["", "info", "ünïcode"]),
        gas_wanted=rng.randrange(10**9),
        gas_used=rng.randrange(10**9),
        events=_events(rng, n_events, extra_keys),
        codespace=rng.choice(["", "sdk", "bank"]),
    )


def _responses(case: str, seed: int) -> dict:
    rng = random.Random(seed)
    begin, end = abci.ResponseBeginBlock(), abci.ResponseEndBlock()
    if case == "kvstore":
        txs = [_kvstore_tx(rng) for _ in range(rng.randint(5, 40))]
    elif case == "codes_and_fields":
        txs = [_coded_tx(rng, rng.randint(0, 2)) for _ in range(rng.randint(5, 20))]
    elif case == "no_events":
        txs = [_coded_tx(rng, 0) for _ in range(rng.randint(1, 10))]
    elif case == "one_event":
        txs = [_coded_tx(rng, 1) for _ in range(rng.randint(1, 10))]
    elif case == "several_events_extra_keys":
        txs = [_coded_tx(rng, rng.randint(2, 5), extra_keys=True) for _ in range(rng.randint(1, 10))]
    elif case == "begin_block_events":
        begin = abci.ResponseBeginBlock(events=_events(rng, rng.randint(1, 4)))
        txs = [_kvstore_tx(rng) for _ in range(3)]
    elif case == "end_block_updates":
        end = abci.ResponseEndBlock(
            validator_updates=[
                abci.ValidatorUpdate(pub_key=rng.randbytes(32), power=rng.randrange(100))
                for _ in range(rng.randint(1, 4))
            ],
            consensus_param_updates={
                "block": {"max_bytes": rng.randrange(1, 2**22), "max_gas": -1},
                "evidence": {"max_age_num_blocks": rng.randrange(1, 10**5)},
            },
            events=_events(rng, rng.randint(0, 2)),
        )
        txs = [_coded_tx(rng, 1) for _ in range(3)]
    else:
        assert case == "empty_block"
        txs = []
    return {"begin_block": begin, "deliver_txs": txs, "end_block": end}


CASES = ("kvstore", "codes_and_fields", "no_events", "one_event", "several_events_extra_keys",
         "begin_block_events", "end_block_updates", "empty_block")


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("case", CASES)
def test_the_record_encodes_to_the_bytes_asdict_gave(case, seed):
    responses = _responses(case, seed)
    record = _responses_to_dict(responses)
    assert record == asdict_record(responses)
    assert list(record) == ["begin_block", "deliver_txs", "end_block"]
    assert [list(r) for r in record["deliver_txs"]] == \
        [list(r) for r in asdict_record(responses)["deliver_txs"]]
    assert codec.dumps(record) == codec.dumps(asdict_record(responses))


def _scribble(obj) -> None:
    """Change every list and dict reachable from `obj`, in place."""
    if isinstance(obj, dict):
        for value in list(obj.values()):
            _scribble(value)
        obj["scribbled"] = True
    elif isinstance(obj, list):
        for value in obj:
            _scribble(value)
        obj.append("scribbled")


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("case", ["several_events_extra_keys", "end_block_updates"])
def test_changing_the_record_leaves_the_responses_as_they_were(case, seed):
    responses = _responses(case, seed)
    before = copy.deepcopy(responses)
    _scribble(_responses_to_dict(responses))
    assert responses == before
    assert codec.dumps(_responses_to_dict(responses)) == codec.dumps(asdict_record(before))


@dataclass
class TaggedDeliverTx(abci.ResponseDeliverTx):
    """An app's own response class, with a field of its own."""

    tag: str = ""


@dataclass
class TaggedEvent(abci.Event):
    tag: str = ""


def _counted(responses: dict):
    rec = tracing.FlightRecorder(size=8)
    with rec.span("fastsync.block", id=1):
        record = _responses_to_dict(responses)
    (ev,) = rec.events()
    return record, ev["responses_generic"]


def test_a_subclass_takes_asdict_and_is_counted():
    rng = random.Random(7)
    txs = [_kvstore_tx(rng), TaggedDeliverTx(code=3, tag="mine"), _coded_tx(rng, 2),
           abci.ResponseDeliverTx(events=[TaggedEvent(type="t", attributes=[{"key": b"k"}], tag="x")]),
           TaggedDeliverTx(events=_events(rng, 1), tag="again")]
    responses = {"begin_block": abci.ResponseBeginBlock(), "deliver_txs": txs,
                 "end_block": abci.ResponseEndBlock()}
    record, generic = _counted(responses)
    assert generic == 3  # two subclassed responses, one with a subclassed event
    assert record["deliver_txs"][1]["tag"] == "mine"
    assert record["deliver_txs"][3]["events"][0]["tag"] == "x"
    assert codec.dumps(record) == codec.dumps(asdict_record(responses))
    assert abci.deliver_tx_record(txs[1]) is None and abci.event_record(txs[3].events[0]) is None


def test_the_exact_classes_are_not_counted():
    responses = _responses("several_events_extra_keys", 11)
    record, generic = _counted(responses)
    assert generic == 0 and codec.dumps(record) == codec.dumps(asdict_record(responses))


def test_the_handshakes_stored_responses_take_asdict_and_save_the_same_record():
    """The handshake replays saved responses through `_StoredResponsesApp`,
    whose DeliverTx responses carry their events as plain dicts: `asdict`
    saves them as it always did, and the record is the one stored."""
    responses = _responses("codes_and_fields", 5)
    stored = codec.loads(codec.dumps(_responses_to_dict(responses)))
    app = _StoredResponsesApp(b"hash", stored)
    replayed = {
        "begin_block": app.begin_block(None),
        "deliver_txs": [app.deliver_tx(None) for _ in stored["deliver_txs"]],
        "end_block": app.end_block(None),
    }
    record, generic = _counted(replayed)
    with_events = sum(1 for r in replayed["deliver_txs"] if r.events)
    assert with_events and generic == with_events
    assert codec.dumps(record) == codec.dumps(_responses_to_dict(responses))


async def test_apply_block_saves_the_asdict_bytes_and_counts_nothing(monkeypatch):
    """A kvstore chain through `apply_block` under an open `fastsync.block`
    span: `responses_generic` 0 on every block, and the record stored at
    each height is what `asdict` gave for its responses, as
    `load_abci_responses` and RPC `block_results` read it."""
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.abci.examples import KVStoreApplication
    from tendermint_tpu.libs.kvstore import MemDB
    from tendermint_tpu.mempool import NopMempool
    from tendermint_tpu.rpc.core import RPCCore
    from tendermint_tpu.state import StateStore, make_genesis_state
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV
    from tests.test_types import CHAIN_ID, make_commit

    seen = {}
    real = execution._responses_to_dict

    def keep(responses):
        seen[len(seen) + 1] = responses
        return real(responses)

    monkeypatch.setattr(execution, "_responses_to_dict", keep)
    pvs = sorted((MockPV() for _ in range(4)), key=lambda pv: pv.address())
    genesis = GenesisDoc(
        chain_id=CHAIN_ID, genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs])
    state = make_genesis_state(genesis)
    db = MemDB()
    store = StateStore(db)
    app = LocalClient(KVStoreApplication())
    await app.start()
    ex = BlockExecutor(store, app, NopMempool())
    rec = tracing.FlightRecorder(size=32)
    commit = None
    try:
        for h in range(1, 5):
            txs = [b"k%d.%d=v%d" % (h, i, i) for i in range(h * 3)]
            block = state.make_block(h, txs, commit, [], state.validators.get_proposer().address)
            block_id = block.block_id(65536)
            commit = make_commit(state.validators, pvs, h, 0, block_id)
            with rec.span("fastsync.block", id=h):
                state, _ = await ex.apply_block(state, block_id, block)
    finally:
        await app.stop()
    blocks = [ev for ev in rec.events() if ev["kind"] == "fastsync.block"]
    assert [ev["responses_generic"] for ev in blocks] == [0] * 4
    node = SimpleNamespace(state_store=store,
                           block_store=SimpleNamespace(height=lambda: 4, base=lambda: 1))
    for h, responses in seen.items():
        assert len(responses["deliver_txs"]) == h * 3
        want = codec.dumps(asdict_record(responses))
        assert db.get(b"abciResponsesKey:%d" % h) == want
        assert store.load_abci_responses(h) == codec.loads(want)
        assert await RPCCore(node).block_results(h) == {"height": h, "results": codec.loads(want)}
