"""The store's write path encodes a validator set only when it changed:
`ValidatorSet.packed()` keeps the members' encoding (carried by `copy()`,
dropped by every method that writes a priority, a power or the
membership), and `State.bytes()` splices the three sets in.  Every byte
written is held here against the encoding the parent wrote, computed from
scratch: msgpack of `to_dict()`.
"""

import random

import msgpack
import pytest

from tendermint_tpu.abci import types as abci
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.encoding import codec
from tendermint_tpu.libs import tracing
from tendermint_tpu.libs.kvstore import MemDB
from tendermint_tpu.state import State, StateStore, make_genesis_state
from tendermint_tpu.state.execution import update_state
from tendermint_tpu.types import BlockID, GenesisDoc, GenesisValidator, Validator, ValidatorSet


def scratch(vset: ValidatorSet) -> bytes:
    return msgpack.packb(vset.to_dict(), use_bin_type=True)


def parent_bytes(state: State) -> bytes:
    """What the parent's `State.bytes()` wrote: `codec.dumps` through `to_dict`."""
    d = state.to_dict()
    d["@t"] = "tm/State"
    return msgpack.packb(d, use_bin_type=True)


def _keys(n, seed):
    rng = random.Random(seed)
    return [Ed25519PrivKey(rng.randbytes(32)).pub_key() for _ in range(n)]


def _members(n, seed=0):
    rng = random.Random(seed)
    return [Validator.new(pk, rng.randint(1, 50)) for pk in _keys(n, seed)]


def assert_kept_is_scratch(vset):
    assert bytes(vset.packed()) == scratch(vset)
    if vset._packed is not None:  # what is kept is the members' part, as they stand
        assert vset._packed == msgpack.packb(
            [v.to_dict() for v in vset.validators], use_bin_type=True)


OPS = ("copy", "increment", "add", "remove", "power", "fresh_proposer", "from_dict",
       "rescale", "mutate_original")


@pytest.mark.parametrize("seed", range(12))
def test_a_random_walk_keeps_the_bytes_of_a_fresh_encoding(seed):
    rng = random.Random(seed)
    spare = _members(8, seed=1000 + seed)
    vset = ValidatorSet(_members(rng.randint(1, 12), seed=seed))
    assert vset._packed is None
    assert_kept_is_scratch(vset)
    older = []  # sets copied from along the way, checked again at the end
    for _ in range(40):
        op = rng.choice(OPS)
        if op == "copy":
            vset.packed()
            older.append(vset)
            vset = vset.copy()
            assert vset._packed is older[-1]._packed  # carried, not re-encoded
        elif op == "increment":
            vset.increment_proposer_priority(rng.randint(1, 3))
        elif op == "add" and spare:
            vset.update_with_change_set([spare.pop()])
        elif op == "remove" and len(vset) > 1:
            v = rng.choice(vset.validators)
            vset.update_with_change_set([Validator(v.address, v.pub_key, 0)])
        elif op == "power":
            v = rng.choice(vset.validators)
            vset.update_with_change_set([Validator(v.address, v.pub_key, v.voting_power + 7)])
        elif op == "fresh_proposer":
            # a set read without its proposer fills it lazily
            d = vset.to_dict()
            d["proposer"] = None
            vset = ValidatorSet.from_dict(d)
            vset.packed()
            vset.get_proposer()
        elif op == "from_dict":
            vset = codec.loads(codec.dumps(vset))
            assert vset._packed is None
        elif op == "rescale":
            vset.rescale_priorities(rng.randint(1, 40))
        elif op == "mutate_original" and older:
            # copy() shares the proposer between sets: the set copied from
            # moves on, the copy's bytes follow what its to_dict() says
            older[-1].increment_proposer_priority(1)
        assert_kept_is_scratch(vset)
    for old in older:
        assert_kept_is_scratch(old)


def test_an_unchanged_copy_is_not_encoded_again():
    vset = ValidatorSet(_members(50))
    first = bytes(vset.packed())
    kept = vset._packed
    copied = vset.copy()
    assert bytes(copied.packed()) == first and copied._packed is kept
    copied.increment_proposer_priority(1)
    assert copied._packed is None and bytes(copied.packed()) == scratch(copied) != first
    assert vset._packed is kept and bytes(vset.packed()) == first


def test_an_empty_set_encodes_as_its_dict():
    assert bytes(ValidatorSet().packed()) == scratch(ValidatorSet())


def _genesis(n, seed=7):
    keys = _keys(n, seed)
    return GenesisDoc(
        chain_id="state-bytes",
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(k.address(), k, 10 + i % 5) for i, k in enumerate(keys)],
    )


def _next(state, updates=()):
    """update_state's sets for a block: next rotated (and changed by
    `updates`), the other two promoted by copy()."""
    responses = {"deliver_txs": [], "end_block": abci.ResponseEndBlock()}
    return update_state(
        state, BlockID(), _Block(state.last_block_height + 1), responses, list(updates))


class _Block:
    def __init__(self, height):
        self.height = height
        self.time_ns = 1_700_000_000_000_000_000 + height


@pytest.mark.parametrize("n", [1, 175, 1000])
def test_a_state_writes_the_bytes_the_parent_wrote(n):
    state = make_genesis_state(_genesis(n))
    assert state.bytes() == parent_bytes(state) == codec.dumps(state)
    for _ in range(4):
        state = _next(state)
        assert state.bytes() == parent_bytes(state) == codec.dumps(state)
    back = codec.loads(state.bytes())
    assert back.bytes() == state.bytes() and back.equals(state)


@pytest.mark.parametrize("last", ["empty", "none"])
def test_a_state_without_last_validators_writes_the_parent_bytes(last):
    state = make_genesis_state(_genesis(4))
    assert len(state.last_validators) == 0
    if last == "none":
        state.last_validators = None
    assert state.bytes() == parent_bytes(state) == codec.dumps(state)
    assert codec.loads(state.bytes()).last_validators is None


def test_a_state_with_no_validators_writes_the_parent_bytes():
    state = State(chain_id="empty")
    assert state.bytes() == parent_bytes(state)
    state.validators = ValidatorSet()
    assert state.bytes() == parent_bytes(state)


def test_the_save_encodes_one_set_a_block_and_writes_the_parent_bytes():
    """`set_encodes` on the open span: the genesis save encodes its two
    sets (its last set is empty), then each block the rotated
    next_validators alone; every record the store writes is what the
    parent's encoding gives."""
    db = MemDB()
    store = StateStore(db)
    state = make_genesis_state(_genesis(50))
    rec = tracing.FlightRecorder(size=32)
    for h in range(6):
        if h:
            state = _next(state)
        with rec.span("fastsync.block", id=h):
            store.save(state)
        assert db.get(b"stateKey") == parent_bytes(state)
    encodes = [e["set_encodes"] for e in rec.events() if e["kind"] == "fastsync.block"]
    assert encodes == [2, 1, 1, 1, 1, 1]
    # the genesis set's full record, in the parent's dict form; then pointers
    full = codec.loads(db.get(b"validatorsKey:1"))
    assert db.get(b"validatorsKey:1") == msgpack.packb(
        {"last_changed": 1, "validators": full["validators"]}, use_bin_type=True)
    assert ValidatorSet.from_dict(full["validators"]).hash() == state.validators.hash()
    for height in range(2, 8):
        assert db.get(b"validatorsKey:%d" % height) == msgpack.packb(
            {"last_changed": 1, "validators": None}, use_bin_type=True)
    assert store.load().bytes() == state.bytes()


def test_a_set_change_writes_its_full_record_as_the_parent_did():
    """A power change at block 3 takes effect at height 5: that height's
    full record and every state after it are the parent's bytes, and the
    changed set is encoded once for both records of its first save."""
    db = MemDB()
    store = StateStore(db)
    state = make_genesis_state(_genesis(20))
    store.save(state)
    rec = tracing.FlightRecorder(size=32)
    v = state.validators.validators[3]
    for h in range(1, 7):
        updates = [Validator(v.address, v.pub_key, v.voting_power + 9)] if h == 3 else []
        state = _next(state, updates)
        with rec.span("fastsync.block", id=h):
            store.save(state)
        assert db.get(b"stateKey") == parent_bytes(state)
    assert state.last_height_validators_changed == 5
    raw = db.get(b"validatorsKey:5")
    full = codec.loads(raw)
    assert full["validators"] is not None and raw == msgpack.packb(
        {"last_changed": 5, "validators": full["validators"]}, use_bin_type=True)
    assert ValidatorSet.from_dict(full["validators"]).hash() == state.validators.hash()
    assert [e["set_encodes"] for e in rec.events()] == [1] * 6


def test_dumps_map_splices_a_packed_value_as_dumps_would_encode_it():
    inner = {"a": [1, 2, b"\x00"], "b": None}
    fields = {"x": 1, "inner": codec.Packed(codec.dumps(inner)), "s": "t"}
    assert codec.dumps_map(fields) == codec.dumps({"x": 1, "inner": inner, "s": "t"})
    nested = {"outer": codec.packed_map(fields)}  # a Packed of pieces, spliced whole
    assert codec.dumps_map(nested) == codec.dumps({"outer": {"x": 1, "inner": inner, "s": "t"}})
    many = {f"k{i}": i for i in range(20)}  # a map16 header, not a fixmap
    assert codec.dumps_map(many) == codec.dumps(many)
