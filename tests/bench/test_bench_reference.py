"""The benchmark's plain reference against the program it judges, at toy
size on the CPU: sign-bytes, commit verdicts, the kvstore's app hash, and
the count of operations one plain verification needs (checked by hand).
"""

import dataclasses
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import harness, reference  # noqa: E402

from tendermint_tpu.crypto.keys import Ed25519PrivKey  # noqa: E402
from tendermint_tpu.types import (  # noqa: E402
    BlockID, Commit, CommitSig, PartSetHeader, Validator, ValidatorSet,
)

CHAIN = "bench-ref-test"


def committee(n):
    keys = [Ed25519PrivKey.from_secret(b"bench-ref-%d" % i) for i in range(n)]
    vset = ValidatorSet([Validator.new(k.pub_key(), 10 + i) for i, k in enumerate(keys)])
    by_address = {k.pub_key().address(): k for k in keys}
    return vset, [by_address[v.address] for v in vset.validators]


def signed_commit(vset, keys, absent=()):
    block_id = BlockID(b"\x05" * 32, PartSetHeader(3, b"\x06" * 32))
    sigs = [
        CommitSig.absent() if i in absent
        else CommitSig.for_block(bytes(64), v.address, 1_700_000_000_123_456_789 + i)
        for i, v in enumerate(vset.validators)
    ]
    commit = Commit(9, 1, block_id, sigs)
    for i, key in enumerate(keys):
        if i not in absent:
            sigs[i] = dataclasses.replace(
                sigs[i], signature=key.sign(commit.vote_sign_bytes(CHAIN, i))
            )
    return commit


def test_sign_bytes_equal_the_programs():
    vset, keys = committee(4)
    commit = signed_commit(vset, keys)
    view = harness.commit_view(commit)
    for i in range(4):
        ts, _ = view.slots[i]
        assert reference.vote_sign_bytes(
            CHAIN, view.height, view.round, view.block_hash, view.parts_total,
            view.parts_hash, ts,
        ) == commit.vote_sign_bytes(CHAIN, i)


def test_reference_accepts_what_the_program_accepts():
    vset, keys = committee(7)
    commit = signed_commit(vset, keys, absent={2})
    vset.verify_commit(CHAIN, commit.block_id, commit.height, commit)
    pubs = [v.pub_key.bytes() for v in vset.validators]
    powers = [v.voting_power for v in vset.validators]
    assert reference.commit_verdict(CHAIN, pubs, powers, harness.commit_view(commit)) == (None, True)


@pytest.mark.parametrize("kind", harness.TAMPERS)
def test_reference_and_program_name_the_same_bad_validator(kind):
    vset, keys = committee(7)
    commit = signed_commit(vset, keys, absent={2})
    secrets = [k.bytes()[:32] for k in keys]
    bad = harness.tamper(kind, CHAIN, commit, secrets, 4)
    pubs = [v.pub_key.bytes() for v in vset.validators]
    powers = [v.voting_power for v in vset.validators]
    first_bad, _ = reference.commit_verdict(CHAIN, pubs, powers, harness.commit_view(bad))
    assert first_bad == 4
    assert harness.engine_first_bad(CHAIN, vset, bad) == 4


def test_too_little_power_is_not_enough():
    vset, keys = committee(6)
    commit = signed_commit(vset, keys, absent={3, 4, 5})  # the three largest powers
    pubs = [v.pub_key.bytes() for v in vset.validators]
    powers = [v.voting_power for v in vset.validators]
    first_bad, enough = reference.commit_verdict(CHAIN, pubs, powers, harness.commit_view(commit))
    assert first_bad is None
    present = sum(p for i, p in enumerate(powers) if i not in {3, 4, 5})
    assert enough == (present > sum(powers) * 2 // 3)


def test_kvstore_app_hash_equals_the_programs():
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.examples import KVStoreApplication

    app = KVStoreApplication()
    delivered = 0
    for height in (1, 2, 3):
        for i in range(height + 1):
            app.deliver_tx(abci.RequestDeliverTx(tx=b"k%d.%d=v" % (height, i)))
            delivered += 1
        got = app.commit().data
        assert got == reference.kvstore_app_hash(delivered, height)


def test_operations_of_one_plain_verification_by_hand():
    """The hand count: a schoolbook 10-limb multiplication is 100 products,
    81 column additions, 9 folds of 2 operations and 10 carries of 3:
    229.  A doubling is 8 of them and 7 limb-wise additions (1,902), an
    addition 9 and 8 (2,141); 256 doublings, 192 additions and two
    265-multiplication exponentiations make one verification."""
    assert reference.field_mul_ops() == 100 + 81 + 18 + 30 == 229
    assert reference.point_double_ops() == 8 * 229 + 70 == 1902
    assert reference.point_add_ops() == 9 * 229 + 80 == 2141
    assert reference.field_pow_ops() == 265 * 229 == 60685
    assert reference.verify_ops_per_signature() == 256 * 1902 + 192 * 2141 + 2 * 60685 == 1019354
    assert reference.verify_bytes_per_signature() == 4 + 32 + 32 + 40 + 1 + 320 + 1
