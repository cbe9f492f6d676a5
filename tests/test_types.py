"""Types-layer tests.

Modeled on reference test strategy (SURVEY.md §4): proposer-priority math
(types/validator_set_test.go), vote accumulation (types/vote_set_test.go),
block/commit hashing (types/block_test.go), part sets
(types/part_set_test.go), evidence (types/evidence_test.go).
"""

import functools
import random
import time

import pytest

from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.types import (
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    Block,
    BlockID,
    Commit,
    CommitSig,
    DuplicateVoteEvidence,
    ErrVoteConflictingVotes,
    GenesisDoc,
    GenesisValidator,
    Header,
    MockPV,
    NotEnoughVotingPowerError,
    PartSetHeader,
    Proposal,
    Validator,
    ValidatorSet,
    Vote,
    VoteSet,
)
from tendermint_tpu.types.block import BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.types.tx import tx_proof, txs_hash
from tendermint_tpu.types.vote import VoteError, is_bls_key

CHAIN_ID = "test-chain"


def rand_validator_set(n, power=10):
    """types/validator_set.go:901 RandValidatorSet — privvals sorted by
    address to align with set order."""
    pvs = [MockPV() for _ in range(n)]
    vals = [Validator.new(pv.get_pub_key(), power) for pv in pvs]
    vset = ValidatorSet(vals)
    pvs.sort(key=lambda pv: pv.address())
    return vset, pvs


def make_block_id(seed=b"\x01"):
    return BlockID(hash=seed * 32, parts_header=PartSetHeader(total=1, hash=seed * 32))


def signed_vote(pv, vset, vote_type, height, round_, block_id, ts=None):
    idx, val = vset.get_by_address(pv.address())
    vote = Vote(
        type=vote_type,
        height=height,
        round=round_,
        block_id=block_id,
        timestamp_ns=ts if ts is not None else time.time_ns(),
        validator_address=pv.address(),
        validator_index=idx,
    )
    pv.sign_vote(CHAIN_ID, vote)
    return vote


def make_commit(vset, pvs, height, round_, block_id):
    vote_set = VoteSet(CHAIN_ID, height, round_, PRECOMMIT_TYPE, vset)
    for pv in pvs:
        vote_set.add_vote(signed_vote(pv, vset, PRECOMMIT_TYPE, height, round_, block_id))
    return vote_set.make_commit()


# ---------------------------------------------------------------------------
# canonical sign bytes
# ---------------------------------------------------------------------------


class TestSignBytes:
    def test_vote_sign_bytes_deterministic_and_distinct(self):
        bid = make_block_id()
        base = dict(
            type=PREVOTE_TYPE, height=5, round=2, block_id=bid, timestamp_ns=123456789,
            validator_address=b"\x01" * 20, validator_index=0,
        )
        v1, v2 = Vote(**base), Vote(**base)
        assert v1.sign_bytes(CHAIN_ID) == v2.sign_bytes(CHAIN_ID)
        variants = [
            Vote(**{**base, "type": PRECOMMIT_TYPE}),
            Vote(**{**base, "height": 6}),
            Vote(**{**base, "round": 3}),
            Vote(**{**base, "block_id": BlockID()}),
            Vote(**{**base, "timestamp_ns": 987654321}),
        ]
        seen = {v1.sign_bytes(CHAIN_ID)}
        for v in variants:
            sb = v.sign_bytes(CHAIN_ID)
            assert sb not in seen, f"sign-bytes collision for {v}"
            seen.add(sb)
        assert v1.sign_bytes("other-chain") not in seen

    def test_vote_sign_bytes_fixed_length_per_commit(self):
        # All vote sign-bytes in one commit batch differ only in timestamp
        # and must share a single static length (TPU batching invariant).
        bid = make_block_id()
        lens = set()
        for ts in (1, 10**9, 1234567890123456789, time.time_ns()):
            v = Vote(
                type=PRECOMMIT_TYPE, height=100, round=0, block_id=bid,
                timestamp_ns=ts, validator_address=b"\x02" * 20, validator_index=1,
            )
            lens.add(len(v.sign_bytes(CHAIN_ID)))
        assert len(lens) == 1

    def test_proposal_sign_bytes(self):
        p = Proposal(height=1, round=0, pol_round=-1, block_id=make_block_id(), timestamp_ns=42)
        p2 = Proposal(height=1, round=0, pol_round=2, block_id=make_block_id(), timestamp_ns=42)
        assert p.sign_bytes(CHAIN_ID) != p2.sign_bytes(CHAIN_ID)

    def test_mockpv_vote_verifies(self):
        pv = MockPV()
        vote = Vote(
            type=PREVOTE_TYPE, height=1, round=0, block_id=make_block_id(),
            timestamp_ns=time.time_ns(), validator_address=pv.address(), validator_index=0,
        )
        pv.sign_vote(CHAIN_ID, vote)
        vote.verify(CHAIN_ID, pv.get_pub_key())
        with pytest.raises(VoteError):
            vote.verify("wrong-chain", pv.get_pub_key())


# ---------------------------------------------------------------------------
# validator set — proposer priority (types/validator_set_test.go parity)
# ---------------------------------------------------------------------------


def _val(addr_byte, power, priority=0):
    pv = MockPV()
    v = Validator.new(pv.get_pub_key(), power)
    v.proposer_priority = priority
    return v


class TestValidatorSet:
    def test_sorted_by_address(self):
        vset, _ = rand_validator_set(10)
        addrs = [v.address for v in vset.validators]
        assert addrs == sorted(addrs)

    def test_total_voting_power(self):
        vset, _ = rand_validator_set(7, power=3)
        assert vset.total_voting_power() == 21

    def test_proposer_rotation_equal_power(self):
        # With equal power, proposer must rotate round-robin over N rounds.
        vset, _ = rand_validator_set(5, power=1)
        seen = []
        for _ in range(5):
            seen.append(vset.get_proposer().address)
            vset.increment_proposer_priority(1)
        assert sorted(seen) == sorted(v.address for v in vset.validators)

    def test_proposer_frequency_proportional_to_power(self):
        # types/validator_set_test.go TestAveragingInIncrementProposerPriority
        # spirit: over many rounds, selection frequency tracks voting power.
        pvs = [MockPV() for _ in range(3)]
        powers = [1, 2, 7]
        vals = [Validator.new(pv.get_pub_key(), p) for pv, p in zip(pvs, powers)]
        vset = ValidatorSet(vals)
        power_of = {v.address: v.voting_power for v in vset.validators}
        counts = {}
        rounds = 1000
        for _ in range(rounds):
            p = vset.get_proposer().address
            counts[p] = counts.get(p, 0) + 1
            vset.increment_proposer_priority(1)
        for addr, c in counts.items():
            expected = rounds * power_of[addr] // 10
            assert abs(c - expected) <= 1, f"{addr.hex()}: {c} vs {expected}"

    def test_priorities_centered_and_bounded(self):
        vset, _ = rand_validator_set(8, power=5)
        for _ in range(50):
            vset.increment_proposer_priority(1)
        prios = [v.proposer_priority for v in vset.validators]
        tvp = vset.total_voting_power()
        # centered near zero and within the 2*TVP window
        assert abs(sum(prios)) < tvp
        assert max(prios) - min(prios) <= 2 * tvp

    def test_copy_increment_does_not_mutate(self):
        vset, _ = rand_validator_set(4)
        before = [(v.address, v.proposer_priority) for v in vset.validators]
        vset.copy_increment_proposer_priority(3)
        after = [(v.address, v.proposer_priority) for v in vset.validators]
        assert before == after

    def test_update_with_change_set(self):
        vset, pvs = rand_validator_set(4, power=10)
        # update power of an existing validator
        target = vset.validators[0]
        upd = Validator(target.address, target.pub_key, 20)
        vset.update_with_change_set([upd])
        _, v = vset.get_by_address(target.address)
        assert v.voting_power == 20
        assert vset.total_voting_power() == 50
        # add a new validator
        new_pv = MockPV()
        vset.update_with_change_set([Validator.new(new_pv.get_pub_key(), 5)])
        assert vset.size() == 5
        # new validator starts with large negative priority
        _, nv = vset.get_by_address(new_pv.address())
        assert nv.proposer_priority < 0
        # remove one (power 0)
        vset.update_with_change_set([Validator(target.address, target.pub_key, 0)])
        assert vset.size() == 4
        assert not vset.has_address(target.address)

    def test_update_rejects_duplicates_and_negatives(self):
        vset, _ = rand_validator_set(3)
        v = vset.validators[0]
        with pytest.raises(ValueError):
            vset.update_with_change_set(
                [Validator(v.address, v.pub_key, 5), Validator(v.address, v.pub_key, 6)]
            )
        with pytest.raises(ValueError):
            vset.update_with_change_set([Validator(v.address, v.pub_key, -1)])

    def test_cannot_remove_all(self):
        vset, _ = rand_validator_set(2)
        deletes = [Validator(v.address, v.pub_key, 0) for v in vset.validators]
        with pytest.raises(ValueError):
            vset.update_with_change_set(deletes)

    def test_hash_changes_with_membership(self):
        vset, _ = rand_validator_set(3)
        h1 = vset.hash()
        vset2 = vset.copy()
        vset2.update_with_change_set([Validator.new(MockPV().get_pub_key(), 1)])
        assert vset2.hash() != h1
        # priority changes do NOT change the hash (excluded from bytes)
        vset3 = vset.copy()
        vset3.increment_proposer_priority(5)
        assert vset3.hash() == h1


# ---------------------------------------------------------------------------
# commit verification (batched)
# ---------------------------------------------------------------------------


class TestVerifyCommit:
    def test_verify_commit_ok(self):
        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 3, 0, bid)
        vset.verify_commit(CHAIN_ID, bid, 3, commit)

    def test_verify_commit_insufficient_power(self):
        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 3, 0, bid)
        # blank out two of four signatures → only 1/2 power remains
        commit.signatures[0] = CommitSig.absent()
        commit.signatures[1] = CommitSig.absent()
        commit._hash = None
        with pytest.raises(NotEnoughVotingPowerError):
            vset.verify_commit(CHAIN_ID, bid, 3, commit)

    def test_verify_commit_wrong_signature(self):
        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 3, 0, bid)
        cs = commit.signatures[2]
        commit.signatures[2] = CommitSig(
            cs.block_id_flag, cs.validator_address, cs.timestamp_ns, b"\x00" * 64
        )
        with pytest.raises(ValueError, match="wrong signature"):
            vset.verify_commit(CHAIN_ID, bid, 3, commit)

    def test_verify_commit_wrong_height_or_block(self):
        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 3, 0, bid)
        with pytest.raises(ValueError, match="height"):
            vset.verify_commit(CHAIN_ID, bid, 4, commit)
        with pytest.raises(ValueError, match="block ID"):
            vset.verify_commit(CHAIN_ID, make_block_id(b"\x09"), 3, commit)

    def test_verify_commit_size_mismatch(self):
        vset, pvs = rand_validator_set(4)
        other, _ = rand_validator_set(3)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 3, 0, bid)
        with pytest.raises(ValueError, match="wrong set size"):
            other.verify_commit(CHAIN_ID, bid, 3, commit)

    def test_verify_commit_trusting(self):
        vset, pvs = rand_validator_set(6)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 10, 0, bid)
        # the full (old==new) set trusts with 1/3 threshold
        vset.verify_commit_trusting(CHAIN_ID, bid, 10, commit, 1, 3)
        # a disjoint set can't tally anything
        strangers, _ = rand_validator_set(6)
        with pytest.raises(NotEnoughVotingPowerError):
            strangers.verify_commit_trusting(CHAIN_ID, bid, 10, commit, 1, 3)

    def test_verify_commit_trusting_bad_trust_level(self):
        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 3, 0, bid)
        with pytest.raises(ValueError, match="trustLevel"):
            vset.verify_commit_trusting(CHAIN_ID, bid, 3, commit, 1, 4)

    def test_verify_future_commit(self):
        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 3, 0, bid)
        vset.verify_future_commit(vset, CHAIN_ID, bid, 3, commit)


# ---------------------------------------------------------------------------
# a commit's verification batch, built from per-commit templates
# ---------------------------------------------------------------------------

REAL_TS = 1_700_000_000_123_456_789
BATCH_TIMESTAMPS = {
    "zero": 0, "one": 1, "real": REAL_TS, "int64-max": 2**63 - 1,
    "past-uint64": 2**64 + 12345, "negative": -7,
}
BATCH_FLAGS = ("for-block", "absent", "nil", "nil+absent")


@functools.lru_cache(maxsize=None)
def _batch_keys(kind, n):
    """Public keys of the right classes over random bytes: sign-bytes depend
    on a key's scheme, not on its value."""
    from tendermint_tpu.crypto.bls.keys import BlsPubKey
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.crypto.sr25519 import Sr25519PubKey

    rng = random.Random(f"{kind}-{n}")
    if kind == "sr25519":
        return [Sr25519PubKey(rng.randbytes(32)) for _ in range(n)]
    return [
        BlsPubKey(rng.randbytes(BlsPubKey.SIZE)) if kind == "mixed" and i % 3 == 1
        else Ed25519PubKey(rng.randbytes(32))
        for i in range(n)
    ]


def _batch_commit(n, flags, ts, bid):
    """Unsigned-but-shaped commit: every 20th slot absent and/or every 7th a
    nil vote as `flags` says; even slots carry `ts` itself, odd ones ts + i."""
    rng = random.Random(f"{n}-{flags}-{ts}")
    sigs = []
    for i in range(n):
        if "absent" in flags and i % 20 == 3 % n:
            sigs.append(CommitSig.absent())
            continue
        flag = BLOCK_ID_FLAG_NIL if "nil" in flags and i % 7 == 2 % n else BLOCK_ID_FLAG_COMMIT
        sigs.append(CommitSig(flag, rng.randbytes(20), ts + (i if i % 2 else 0), rng.randbytes(64)))
    return Commit(12, 1, bid, sigs)


def _signed_commit(vset, pvs, bid, height=5, nil=(), ts=REAL_TS):
    """A commit every validator really signed; slots in `nil` vote nil."""
    sigs = []
    for i, pv in enumerate(pvs):
        vote = signed_vote(pv, vset, PRECOMMIT_TYPE, height, 0, BlockID() if i in nil else bid, ts + i)
        sigs.append(vote.commit_sig())
    return Commit(height, 0, bid, sigs)


@functools.lru_cache(maxsize=None)
def _verdict_set(kind):
    """8 validators, power 10 + slot: all ed25519, or ed25519 with BLS and
    sr25519 slots between (PR 20's rotation leaves such sets)."""
    from tendermint_tpu.crypto.bls.keys import BlsPrivKey
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    pvs = [MockPV() for _ in range(8)]
    if kind == "mixed":
        pvs[1], pvs[6] = MockPV(BlsPrivKey.generate()), MockPV(BlsPrivKey.generate())
        pvs[4] = MockPV(Sr25519PrivKey(bytes(range(32))))
    pvs.sort(key=lambda pv: pv.address())
    vset = ValidatorSet([Validator.new(pv.get_pub_key(), 10 + i) for i, pv in enumerate(pvs)])
    assert [v.address for v in vset.validators] == [pv.address() for pv in pvs]
    return vset, pvs


def _check(method, vset, bid, commit):
    if method == "verify_commit":
        return vset.verify_commit(CHAIN_ID, bid, commit.height, commit)
    if method == "verify_future_commit":
        return vset.verify_future_commit(vset, CHAIN_ID, bid, commit.height, commit)
    return vset.verify_commit_trusting(CHAIN_ID, bid, commit.height, commit, 2, 3)


VERDICT_METHODS = ("verify_commit", "verify_commit_trusting", "verify_future_commit")


class TestCommitVoteBatch:
    @pytest.mark.parametrize("ts", list(BATCH_TIMESTAMPS))
    @pytest.mark.parametrize("flags", BATCH_FLAGS)
    @pytest.mark.parametrize("n", [1, 4, 175, 2000])
    @pytest.mark.parametrize("kind", ["ed25519", "mixed", "sr25519"])
    def test_messages_equal_the_single_vote_encoder(self, kind, n, flags, ts):
        keys = _batch_keys(kind, n)
        bid = make_block_id(b"\x05")
        commit = _batch_commit(n, flags, BATCH_TIMESTAMPS[ts], bid)
        batch = commit.vote_batch(CHAIN_ID, keys)
        present = [i for i, cs in enumerate(commit.signatures) if not cs.is_absent()]
        assert batch.idxs == present
        assert batch.msgs == [commit.vote_sign_bytes(CHAIN_ID, i, pub_key=keys[i]) for i in present]
        assert batch.pub_keys == [keys[i] for i in present]
        assert batch.sigs == [commit.signatures[i].signature for i in present]
        assert batch.for_block == [
            bid == commit.signatures[i].block_id(commit.block_id) for i in present
        ]
        assert batch.templated == len(batch.msgs) == len(present)
        if n >= 175:  # the cases hold what they are named for
            assert ("absent" in flags) == (len(present) < n)
            assert ("nil" in flags) == (False in batch.for_block)

    def test_a_slot_without_a_key_is_left_out(self):
        keys = list(_batch_keys("mixed", 175))
        keys[0] = keys[9] = keys[174] = None
        commit = _batch_commit(175, "nil+absent", REAL_TS, make_block_id())
        batch = commit.vote_batch(CHAIN_ID, keys)
        want = [
            i for i, cs in enumerate(commit.signatures)
            if not cs.is_absent() and keys[i] is not None
        ]
        assert batch.idxs == want and None not in batch.pub_keys
        assert batch.msgs == [commit.vote_sign_bytes(CHAIN_ID, i, pub_key=keys[i]) for i in want]

    @pytest.mark.parametrize("kind,flags,templates", [
        ("ed25519", "for-block", 1), ("sr25519", "nil+absent", 2), ("mixed", "nil+absent", 4),
    ])
    def test_the_canonical_encoder_runs_once_per_template(self, kind, flags, templates, monkeypatch):
        """2,000 votes, at most four encodings: one per (block-id flag, key
        scheme) the commit holds, never one per vote."""
        from tendermint_tpu.types import canonical

        calls = []
        for name in ("canonical_vote_sign_bytes", "canonical_vote_sign_bytes_no_ts"):
            def counted(*a, _real=getattr(canonical, name), _name=name, **kw):
                calls.append(_name)
                return _real(*a, **kw)

            monkeypatch.setattr(canonical, name, counted)
        commit = _batch_commit(2000, flags, REAL_TS, make_block_id())
        batch = commit.vote_batch(CHAIN_ID, _batch_keys(kind, 2000))
        assert len(batch.msgs) >= 1900 and batch.templated == len(batch.msgs)
        assert len(calls) == templates <= 4
        assert calls.count("canonical_vote_sign_bytes_no_ts") == (templates // 2 if kind == "mixed" else 0)

    @pytest.mark.parametrize("ts", list(BATCH_TIMESTAMPS))
    def test_the_template_cuts_at_the_timestamp(self, ts):
        from tendermint_tpu.types import canonical

        args = ("a-chain-id-of-some-length", PRECOMMIT_TYPE, 2**40, 3, b"\x07" * 32, 9, b"\x08" * 32)
        prefix, suffix = canonical.canonical_vote_template(*args)
        value = BATCH_TIMESTAMPS[ts]
        assert prefix + canonical.fixed64_bytes(value) + suffix == \
            canonical.canonical_vote_sign_bytes(*args, value)
        assert prefix[-1] == (5 << 3) | 1  # field 5, fixed64: the timestamp's tag

    @pytest.mark.parametrize("quarter", range(4))
    @pytest.mark.parametrize("method", VERDICT_METHODS)
    @pytest.mark.parametrize("kind", ["ed25519", "mixed"])
    def test_a_tampered_slot_is_named_by_its_index(self, kind, method, quarter):
        vset, pvs = _verdict_set(kind)
        bid = make_block_id(b"\x03")
        commit = _signed_commit(vset, pvs, bid, nil={2})
        _check(method, vset, bid, commit)  # sound, with its nil vote
        bad = 2 * quarter + 1
        cs = commit.signatures[bad]
        if is_bls_key(pvs[bad].get_pub_key()):  # its message has no timestamp
            tampered = (cs.timestamp_ns, cs.signature[:-1] + bytes([cs.signature[-1] ^ 1]))
        else:  # a valid signature, over another timestamp
            tampered = (cs.timestamp_ns + 1, cs.signature)
        commit.signatures[bad] = CommitSig(cs.block_id_flag, cs.validator_address, *tampered)
        with pytest.raises(ValueError, match=rf"wrong signature \(#{bad}\)"):
            _check(method, vset, bid, commit)

    @pytest.mark.parametrize("method", VERDICT_METHODS)
    @pytest.mark.parametrize("kind", ["ed25519", "mixed"])
    def test_a_nil_vote_verifies_and_does_not_count(self, kind, method):
        vset, pvs = _verdict_set(kind)
        bid = make_block_id(b"\x04")
        total = sum(10 + i for i in range(8))  # 108: more than 72 needed
        # slots 5..7 hold 48: without them 60 is not enough
        commit = _signed_commit(vset, pvs, bid, nil={5, 6, 7})
        with pytest.raises(NotEnoughVotingPowerError) as err:
            _check(method, vset, bid, commit)
        assert (err.value.got, err.value.needed) == (total - 48, total * 2 // 3)
        # absent instead of nil: the same tally
        for i in (5, 6, 7):
            commit.signatures[i] = CommitSig.absent()
        with pytest.raises(NotEnoughVotingPowerError) as err:
            _check(method, vset, bid, commit)
        assert (err.value.got, err.value.needed) == (60, 72)
        # a nil vote is still verified: a bad one fails the commit
        commit = _signed_commit(vset, pvs, bid, nil={0})
        _check(method, vset, bid, commit)
        cs = commit.signatures[0]
        commit.signatures[0] = CommitSig(cs.block_id_flag, cs.validator_address, cs.timestamp_ns,
                                         bytes(len(cs.signature)))
        with pytest.raises(ValueError, match=r"wrong signature \(#0\)"):
            _check(method, vset, bid, commit)


# ---------------------------------------------------------------------------
# vote set (types/vote_set_test.go parity)
# ---------------------------------------------------------------------------


class TestVoteSet:
    def test_majority_tracking(self):
        vset, pvs = rand_validator_set(10, power=1)
        vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vset)
        bid = make_block_id()
        # 6 votes: no 2/3 yet (need 7 = >2/3 of 10)
        for pv in pvs[:6]:
            assert vs.add_vote(signed_vote(pv, vset, PREVOTE_TYPE, 1, 0, bid))
        assert not vs.has_two_thirds_majority()
        assert not vs.has_two_thirds_any()
        # 7th vote crosses the threshold
        assert vs.add_vote(signed_vote(pvs[6], vset, PREVOTE_TYPE, 1, 0, bid))
        maj, ok = vs.two_thirds_majority()
        assert ok and maj == bid
        assert vs.has_two_thirds_any()

    def test_nil_votes_count_toward_any_not_block(self):
        vset, pvs = rand_validator_set(4, power=1)
        vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vset)
        for pv in pvs[:3]:
            vs.add_vote(signed_vote(pv, vset, PREVOTE_TYPE, 1, 0, BlockID()))
        assert vs.has_two_thirds_any()
        assert not vs.has_two_thirds_majority() or vs.maj23.is_zero()

    def test_duplicate_vote_returns_false(self):
        vset, pvs = rand_validator_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vset)
        v = signed_vote(pvs[0], vset, PREVOTE_TYPE, 1, 0, make_block_id())
        assert vs.add_vote(v)
        assert vs.add_vote(v) is False

    def test_wrong_height_round_type_rejected(self):
        vset, pvs = rand_validator_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vset)
        bid = make_block_id()
        with pytest.raises(VoteError, match="unexpected step"):
            vs.add_vote(signed_vote(pvs[0], vset, PREVOTE_TYPE, 2, 0, bid))
        with pytest.raises(VoteError, match="unexpected step"):
            vs.add_vote(signed_vote(pvs[0], vset, PREVOTE_TYPE, 1, 1, bid))
        with pytest.raises(VoteError, match="unexpected step"):
            vs.add_vote(signed_vote(pvs[0], vset, PRECOMMIT_TYPE, 1, 0, bid))

    def test_invalid_signature_rejected(self):
        vset, pvs = rand_validator_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vset)
        v = signed_vote(pvs[0], vset, PREVOTE_TYPE, 1, 0, make_block_id())
        v.signature = b"\x01" * 64
        with pytest.raises(VoteError):
            vs.add_vote(v)

    def test_conflicting_votes_produce_evidence(self):
        vset, pvs = rand_validator_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vset)
        pv = pvs[0]
        vs.add_vote(signed_vote(pv, vset, PREVOTE_TYPE, 1, 0, make_block_id(b"\x01")))
        with pytest.raises(ErrVoteConflictingVotes) as ei:
            vs.add_vote(signed_vote(pv, vset, PREVOTE_TYPE, 1, 0, make_block_id(b"\x02")))
        ev = ei.value.evidence
        assert isinstance(ev, DuplicateVoteEvidence)
        ev.verify(CHAIN_ID, pv.get_pub_key())

    def test_peer_maj23_allows_conflict_tracking(self):
        # types/vote_set_test.go TestConflicts spirit
        vset, pvs = rand_validator_set(4, power=1)
        vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vset)
        bid_a, bid_b = make_block_id(b"\x0a"), make_block_id(b"\x0b")
        vs.set_peer_maj23("peer1", bid_b)
        vs.add_vote(signed_vote(pvs[0], vset, PREVOTE_TYPE, 1, 0, bid_a))
        # conflicting vote for the peer-claimed block IS tracked (added)
        with pytest.raises(ErrVoteConflictingVotes):
            vs.add_vote(signed_vote(pvs[0], vset, PREVOTE_TYPE, 1, 0, bid_b))
        assert vs.bit_array_by_block_id(bid_b) is not None

    def test_make_commit(self):
        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 2, 1, bid)
        assert commit.height == 2 and commit.round == 1
        assert commit.block_id == bid
        assert len(commit.signatures) == 4
        vset.verify_commit(CHAIN_ID, bid, 2, commit)
        # round-trips through the codec
        d = Commit.from_dict(commit.to_dict())
        assert d.hash() == commit.hash()


class TestVoteSetScaleQueries:
    """The bitmap diff / selection queries the relay gossip pull path
    exercises at committee scale (128 validators): sparse sets (a few
    votes held, everything missing) and dense sets (one missing) are the
    two edges the summary → pull → batch exchange lives on."""

    N = 128

    def _set(self, held):
        vset, pvs = rand_validator_set(self.N, power=1)
        vs = VoteSet(CHAIN_ID, 1, 0, PREVOTE_TYPE, vset)
        bid = make_block_id()
        for pv in pvs[:held]:
            vs.add_vote(signed_vote(pv, vset, PREVOTE_TYPE, 1, 0, bid))
        return vs, pvs, bid

    def test_missing_votes_sparse(self):
        from tendermint_tpu.libs.bitarray import BitArray

        vs, pvs, _ = self._set(held=3)
        # peer holds nothing: every vote we hold is missing for it
        assert len(vs.missing_votes(BitArray(self.N))) == 3
        assert len(vs.missing_votes(None)) == 3
        # peer holds exactly what we hold: nothing to send
        assert vs.missing_votes(vs.bit_array()) == []

    def test_missing_votes_dense_one_lacking(self):
        from tendermint_tpu.libs.bitarray import BitArray

        vs, pvs, _ = self._set(held=self.N - 1)
        peer_bits = vs.bit_array()
        held_idx = peer_bits.true_indices()[7]
        peer_bits.set_index(held_idx, False)
        missing = vs.missing_votes(peer_bits)
        assert len(missing) == 1 and missing[0].validator_index == held_idx

    def test_bits_we_lack_clamps_and_diffs(self):
        from tendermint_tpu.libs.bitarray import BitArray

        vs, _, _ = self._set(held=3)
        theirs = BitArray.from_indices(self.N, range(self.N))
        lack = vs.bits_we_lack(theirs)
        assert lack.count() == self.N - 3
        assert not any(lack.get_index(i) for i in vs.bit_array().true_indices())
        # an attacker-sized bitmap is clamped to the validator set, and
        # None is an empty diff, not a crash
        oversized = BitArray.from_indices(self.N * 4, range(self.N * 4))
        assert vs.bits_we_lack(oversized).bits == self.N
        assert vs.bits_we_lack(None).count() == 0

    def test_select_votes_skips_unheld_and_clamps(self):
        from tendermint_tpu.libs.bitarray import BitArray

        vs, _, _ = self._set(held=3)
        held = vs.bit_array().true_indices()
        # want everything: only the 3 held votes come back
        want_all = BitArray.from_indices(self.N * 2, range(self.N * 2))
        got = vs.select_votes(want_all)
        assert sorted(v.validator_index for v in got) == held
        # want one held + one unheld: exactly the held one
        unheld = next(i for i in range(self.N) if i not in held)
        want = BitArray.from_indices(self.N, [held[0], unheld])
        got = vs.select_votes(want)
        assert [v.validator_index for v in got] == [held[0]]
        assert vs.select_votes(None) == []


# ---------------------------------------------------------------------------
# blocks, headers, part sets
# ---------------------------------------------------------------------------


def make_test_block(height=1, txs=(b"tx1", b"tx2")):
    vset, pvs = rand_validator_set(4)
    header = Header(
        chain_id=CHAIN_ID,
        height=height,
        time_ns=time.time_ns(),
        validators_hash=vset.hash(),
        next_validators_hash=vset.hash(),
        proposer_address=vset.get_proposer().address,
    )
    last_commit = None
    if height > 1:
        bid = make_block_id()
        last_commit = make_commit(vset, pvs, height - 1, 0, bid)
    return Block(header, list(txs), last_commit=last_commit), vset, pvs


class TestBlock:
    def test_header_hash_sensitive_to_fields(self):
        b, _, _ = make_test_block()
        h1 = b.hash()
        assert len(h1) == 32
        import dataclasses

        h2 = dataclasses.replace(b.header, height=99).hash()
        assert h1 != h2

    def test_block_validate_basic(self):
        b, _, _ = make_test_block(height=2)
        b.fill_header()
        b.validate_basic()

    def test_block_validate_rejects_bad(self):
        b, _, _ = make_test_block(height=2)
        b.fill_header()
        b.last_commit = None
        with pytest.raises(ValueError, match="LastCommit"):
            b.validate_basic()

    def test_block_validate_rejects_unfilled_hashes(self):
        # a received block with an omitted data_hash must NOT validate —
        # validation cannot fill fields in on the receiver's behalf
        import dataclasses

        b, _, _ = make_test_block(height=2)
        b.fill_header()
        b.header = dataclasses.replace(b.header, data_hash=b"")
        with pytest.raises(ValueError, match="DataHash"):
            b.validate_basic()

    def test_block_serialization_roundtrip(self):
        b, _, _ = make_test_block(height=2)
        data = b.serialize()
        b2 = Block.deserialize(data)
        assert b2.hash() == b.hash()
        assert b2.txs == b.txs
        assert b2.last_commit.hash() == b.last_commit.hash()

    def test_part_set_roundtrip(self):
        b, _, _ = make_test_block(height=2, txs=[b"x" * 5000 for _ in range(10)])
        data = b.serialize()
        ps = PartSet.from_data(data, part_size=1024)
        assert ps.is_complete()
        # rebuild from header + parts with proofs
        ps2 = PartSet.from_header(ps.header())
        for i in range(ps.total):
            assert ps2.add_part(ps.get_part(i))
        assert ps2.is_complete()
        assert ps2.assemble() == data
        assert Block.deserialize(ps2.assemble()).hash() == b.hash()

    def test_part_set_rejects_bad_proof(self):
        ps = PartSet.from_data(b"a" * 3000, part_size=1024)
        from tendermint_tpu.types.part_set import Part, PartSetError

        bad = Part(0, b"tampered", ps.get_part(0).proof)
        ps2 = PartSet.from_header(ps.header())
        with pytest.raises(PartSetError):
            ps2.add_part(bad)

    def test_txs_hash_and_proof(self):
        txs = [b"a", b"b", b"c", b"d", b"e"]
        root = txs_hash(txs)
        for i in range(len(txs)):
            p = tx_proof(txs, i)
            assert p.root_hash == root
            p.validate(root)
        with pytest.raises(ValueError):
            tx_proof(txs, 0).validate(b"\x00" * 32)


# ---------------------------------------------------------------------------
# genesis
# ---------------------------------------------------------------------------


class TestGenesis:
    def test_roundtrip(self, tmp_path):
        pvs = [MockPV() for _ in range(3)]
        doc = GenesisDoc(
            chain_id=CHAIN_ID,
            validators=[
                GenesisValidator(pv.address(), pv.get_pub_key(), 10, f"val{i}")
                for i, pv in enumerate(pvs)
            ],
        )
        doc.validate_and_complete()
        path = str(tmp_path / "genesis.json")
        doc.save_as(path)
        doc2 = GenesisDoc.from_file(path)
        assert doc2.chain_id == doc.chain_id
        assert doc2.validator_hash() == doc.validator_hash()
        assert doc2.validator_set().size() == 3

    def test_rejects_zero_power(self):
        pv = MockPV()
        doc = GenesisDoc(
            chain_id=CHAIN_ID, validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 0)]
        )
        with pytest.raises(ValueError, match="voting power"):
            doc.validate_and_complete()

    def test_rejects_empty_chain_id(self):
        with pytest.raises(ValueError, match="chain_id"):
            GenesisDoc(chain_id="").validate_and_complete()
