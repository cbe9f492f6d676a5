"""A commit's Merkle root in one pass (types/block.py Commit.hash).

`CommitSig.encode()` through the generic field encoders is the definition;
`commit_sig_leaf_hashes` is held to it for every value the wire can carry,
since a peer's block reaches `hash()` before `validate_basic` has run.
"""

import hashlib
import itertools
import random

import pytest

from tendermint_tpu.crypto import merkle
from tendermint_tpu.encoding import codec, proto
from tendermint_tpu.types import BlockID, Commit, CommitSig, PartSetHeader
from tendermint_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    commit_sig_leaf_hashes,
)
from tests.test_merkle_fold import root_by_definition as merkle_root_by_definition

SECOND = 1_000_000_000
T0 = 1_700_000_000 * SECOND


def leaf_by_definition(cs: CommitSig) -> bytes:
    return hashlib.sha256(b"\x00" + cs.encode()).digest()


def root_by_definition(signatures) -> bytes:
    """The generic encodings under the recursive tree (simple_tree.go:9)."""
    return merkle_root_by_definition([cs.encode() for cs in signatures])


def _slots(n: int, seed: int = 29, absent_share: float = 0.05):
    rng = random.Random(seed)
    return [
        CommitSig.absent() if rng.random() < absent_share else CommitSig(
            BLOCK_ID_FLAG_NIL if rng.random() < 0.02 else BLOCK_ID_FLAG_COMMIT,
            rng.randbytes(20), T0 + rng.randrange(3 * SECOND), rng.randbytes(64))
        for _ in range(n)
    ]


def _commit(signatures) -> Commit:
    return Commit(7, 0, BlockID(b"\x11" * 32, PartSetHeader(1, b"\x22" * 32)), signatures)


TIMESTAMPS = {
    "zero": 0, "whole-seconds": T0, "1ns": 1, "second+1ns": T0 + 1, "127ns": T0 + 127,
    "128ns": T0 + 128, "2^14ns": T0 + (1 << 14), "2^21-1ns": T0 + (1 << 21) - 1,
    "2^21ns": T0 + (1 << 21), "2^28-1ns": T0 + (1 << 28) - 1, "2^28ns": T0 + (1 << 28),
    "last-ns": T0 + SECOND - 1, "2^63-1": (1 << 63) - 1, "2^64-1": (1 << 64) - 1,
    "minus-1ns": -1, "minus-a-second": -SECOND, "negative": -T0 - 5, "minus-2^63": -(1 << 63),
}


class TestLeafHashes:
    @pytest.mark.parametrize("ts", TIMESTAMPS.values(), ids=TIMESTAMPS.keys())
    @pytest.mark.parametrize("flag", [BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT,
                                      BLOCK_ID_FLAG_NIL, 9],
                             ids=["absent", "for-block", "nil", "unknown"])
    def test_a_slot_of_any_flag_and_time(self, flag, ts):
        cs = CommitSig(flag, b"\xaa" * 20, ts, b"\x55" * 64)
        assert commit_sig_leaf_hashes([cs]) == [leaf_by_definition(cs)]

    @pytest.mark.parametrize("addr_len, sig_len",
                             itertools.product([0, 20, 21, 127, 128], [0, 64, 96, 127, 128, 300]))
    def test_a_slot_of_any_lengths(self, addr_len, sig_len):
        cs = CommitSig(BLOCK_ID_FLAG_COMMIT, b"\xaa" * addr_len, T0 + 5, b"\x55" * sig_len)
        assert commit_sig_leaf_hashes([cs]) == [leaf_by_definition(cs)]

    @pytest.mark.parametrize("cs", [
        CommitSig.absent(),
        CommitSig(0, b"\xaa" * 20, T0, b"\x55" * 64),
        CommitSig(127, b"\xaa" * 20, T0, b"\x55" * 64),
        CommitSig(128, b"\xaa" * 20, T0, b"\x55" * 64),
        CommitSig(300, b"\xaa" * 20, T0, b"\x55" * 64),
        CommitSig(-1, b"\xaa" * 20, T0, b"\x55" * 64),
        CommitSig(True, b"\xaa" * 20, T0, b"\x55" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, "a" * 20, T0, b"\x55" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, "é" * 20, T0, "é" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, None, T0, None),
        CommitSig(BLOCK_ID_FLAG_COMMIT, bytearray(b"\xaa" * 20), T0, b"\x55" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\xaa" * 20, False, b"\x55" * 64),
        CommitSig(BLOCK_ID_FLAG_ABSENT, b"", 0.0, b""),
    ], ids=["absent", "flag-0", "flag-127", "flag-128", "flag-300", "flag-negative", "flag-bool",
            "address-str", "non-ascii-strs", "nones", "address-bytearray", "time-bool",
            "time-float-zero"])
    def test_a_slot_off_the_common_shape_is_encoded_by_the_definition(self, cs):
        assert commit_sig_leaf_hashes([cs]) == [leaf_by_definition(cs)]

    @pytest.mark.parametrize("cs", [
        CommitSig(2.0, b"\xaa" * 20, T0, b"\x55" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\xaa" * 20, float(T0), b"\x55" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, 20, T0, b"\x55" * 64),
        CommitSig(BLOCK_ID_FLAG_COMMIT, b"\xaa" * 20, T0, [1, 2]),
        CommitSig("2", b"\xaa" * 20, T0, b"\x55" * 64),
    ], ids=["flag-float", "time-float", "address-int", "signature-list", "flag-str"])
    def test_what_the_definition_refuses_is_refused_the_same_way(self, cs):
        with pytest.raises(TypeError) as want:
            cs.encode()
        with pytest.raises(TypeError) as got:
            commit_sig_leaf_hashes([CommitSig.absent(), cs])
        assert str(got.value) == str(want.value)

    def test_the_seconds_are_encoded_once_per_distinct_second(self, monkeypatch):
        from tendermint_tpu.types import block as block_mod

        slots = _slots(400)
        want = [leaf_by_definition(cs) for cs in slots]
        calls = []
        real = proto.field_varint
        monkeypatch.setattr(block_mod, "field_varint",
                            lambda num, value, **kw: calls.append(value) or real(num, value, **kw))
        assert commit_sig_leaf_hashes(slots) == want
        assert sorted(calls) == sorted({cs.timestamp_ns // SECOND for cs in slots})

    def test_a_slot_from_the_wire_is_hashed_as_it_arrived(self):
        """What msgpack hands `from_dict` is what gets hashed: a peer's odd
        slot is not normalised on the way to the root."""
        odd = CommitSig(77, b"\x01" * 33, -5, b"\x02" * 130)
        commit = codec.loads(codec.dumps(_commit([odd, *_slots(9)])))
        assert commit.signatures[0] == odd
        assert commit.hash() == root_by_definition(commit.signatures)


class TestCommitHash:
    @pytest.mark.parametrize("n", [1, 2, 3, 175, 9500])
    def test_the_root_is_the_root_over_the_generic_encodings(self, n):
        slots = _slots(n)
        if n >= 175:
            absent = sum(cs.is_absent() for cs in slots)
            assert 0.03 * n < absent < 0.07 * n
        want = root_by_definition(slots)
        assert _commit(slots).hash() == want
        assert merkle.hash_from_byte_slices([cs.encode() for cs in slots]) == want

    def test_the_root_is_built_once_per_object(self, monkeypatch):
        from tendermint_tpu.types import block as block_mod

        passes = []
        real = block_mod.commit_sig_leaf_hashes
        monkeypatch.setattr(block_mod, "commit_sig_leaf_hashes",
                            lambda sigs: passes.append(len(sigs)) or real(sigs))
        commit = _commit(_slots(50))
        assert commit._hash is None
        first = commit.hash()
        assert commit._hash == first and commit.hash() is first and passes == [50]
        again = codec.loads(codec.dumps(commit))  # another object: its own root, once
        assert again._hash is None and again.hash() == first and again.hash() == first
        assert passes == [50, 50]

    def test_every_slot_bears_on_the_root(self):
        slots = _slots(40)
        root = _commit(slots).hash()
        for i in (0, 17, 39):
            cs = slots[i]
            moved = CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp_ns + 1,
                              cs.signature)
            assert _commit(slots[:i] + [moved] + slots[i + 1:]).hash() != root
        assert _commit(slots[1:] + slots[:1]).hash() != root

    def test_the_definition_has_not_moved(self):
        """`CommitSig.encode()` itself, pinned: four generic fields."""
        cs = CommitSig(BLOCK_ID_FLAG_COMMIT, b"\xaa" * 20, T0 + 5, b"\x55" * 64)
        assert cs.encode().hex() == (
            "0802" + "1214" + "aa" * 20 + "1a08" + "0880e2cfaa06" + "1005" + "2240" + "55" * 64)
        assert CommitSig.absent().encode().hex() == "08011a00"
