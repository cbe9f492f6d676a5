"""`ValidatorSet.hash()` keeps its Merkle root on the set (as
`pubkeys_digest()` keeps its digest): carried by `copy()`, dropped where a
power or the membership changes, never shared between objects.  Every
mutator the class has is held against a root computed from scratch here.
"""

import pytest

from tendermint_tpu.crypto import merkle
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.encoding import codec
from tendermint_tpu.types import Validator, ValidatorSet

SIZES = (175, 1000)


def scratch_root(vset: ValidatorSet) -> bytes:
    return merkle.hash_from_byte_slices([v.bytes() for v in vset.validators])


@pytest.fixture(scope="module", params=SIZES)
def members(request):
    """One more key than the set holds: the last is the one a change adds."""
    keys = [Ed25519PrivKey.generate().pub_key() for _ in range(request.param + 1)]
    return [Validator.new(pk, 10 + i % 7) for i, pk in enumerate(keys)]


@pytest.fixture
def vset(members):
    return ValidatorSet(members[:-1])


def _changes(vset, newcomer):
    """The four kinds of change set, against the set as it stands."""
    first, last = vset.validators[0], vset.validators[-1]
    repower = Validator(first.address, first.pub_key, first.voting_power + 5)
    remove = Validator(last.address, last.pub_key, 0)
    return {
        "power": [repower],
        "add": [newcomer],
        "remove": [remove],
        "all_three": [repower, newcomer, remove],
    }


class TestTheRootIsTheOneFromScratch:
    def test_after_construction_and_again(self, vset):
        assert vset._root is None  # a new set is hashed before it is believed
        assert vset.hash() == scratch_root(vset)
        assert vset.hash() is vset._root  # the second call builds nothing

    def test_after_copy(self, vset):
        cold = vset.copy()
        assert cold._root is None and cold.hash() == scratch_root(vset)
        root = vset.hash()
        warm = vset.copy()
        assert warm._root is root and warm.hash() == scratch_root(warm)

    def test_after_the_proposer_rotates(self, vset):
        root = vset.hash()
        rotated = vset.copy_increment_proposer_priority(3)
        assert [v.proposer_priority for v in rotated.validators] != [
            v.proposer_priority for v in vset.validators]
        # priorities are not in Validator.bytes(): the root stands, and is right
        assert rotated._root is root and rotated.hash() == scratch_root(rotated)
        vset.increment_proposer_priority(2)
        vset.rescale_priorities(1)
        assert vset._root is root and vset.hash() == scratch_root(vset)

    @pytest.mark.parametrize("kind", ["power", "add", "remove", "all_three"])
    def test_after_a_change_set(self, vset, members, kind):
        old_root = vset.hash()
        before = vset.copy()  # taken before the update: keeps the old root
        vset.update_with_change_set(_changes(vset, members[-1])[kind])
        assert vset._root is None
        assert vset.hash() == scratch_root(vset) != old_root
        assert before.hash() == old_root == scratch_root(before)
        # and a copy of the updated set carries the new one
        assert vset.copy().hash() == scratch_root(vset)

    def test_an_empty_change_set_changes_nothing(self, vset):
        root = vset.hash()
        vset.update_with_change_set([])
        assert vset._root is root and vset.hash() == scratch_root(vset)

    def test_a_refused_change_set_leaves_the_set_and_its_root(self, vset):
        root = vset.hash()
        first = vset.validators[0]
        with pytest.raises(ValueError):
            vset.update_with_change_set([Validator(first.address, first.pub_key, -1)])
        assert vset.hash() == root == scratch_root(vset)

    @pytest.mark.parametrize("trip", ["dict", "codec"])
    def test_after_a_round_trip(self, vset, trip):
        root = vset.hash()
        if trip == "dict":
            back = ValidatorSet.from_dict(vset.to_dict())
        else:
            back = codec.loads(codec.dumps(vset))
        # what was read from the store or the wire is hashed once itself
        assert back._root is None
        assert back.hash() == root == scratch_root(back)

    def test_the_memo_is_per_object(self, vset, members):
        """A copy taken before an update keeps the old root while the
        updated set gets the new one, whichever is hashed first."""
        kept = vset.copy()
        vset.hash()
        shared = vset.copy()
        vset.update_with_change_set(_changes(vset, members[-1])["all_three"])
        new_root = vset.hash()
        assert kept.hash() == shared.hash() == scratch_root(kept) != new_root
        assert new_root == scratch_root(vset)
        assert len(vset) == len(kept)  # one in, one out


def test_an_empty_set_hashes_to_nothing_and_keeps_nothing():
    empty = ValidatorSet()
    assert empty.hash() == b"" and empty._root is None
    assert empty.copy().hash() == b""
