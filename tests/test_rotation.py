"""Dynamic validator-set rotation: the engine/consensus seams a set
change crosses.

Pins the pieces the rotation smoke exercises end-to-end, at unit scale:
ValidatorSet.update_with_change_set edge cases at N=100 (removing the
current proposer, priority re-centering), the TableCache rebuild pipeline
(recorder event + prometheus counter + a post-rotation commit verifying
through the engine's indexed path), fold_commit flipping aggregation on
and off as the set migrates, evidence from a validator already rotated
out of the set (unbonding-window semantics via historical sets), the
scenario-DSL valset clauses, and RotatingPV key activation.
"""

import time

import pytest

from tendermint_tpu.crypto import batch as batch_hook
from tendermint_tpu.crypto.batch_verifier import BatchVerifier, TableCache
from tendermint_tpu.libs.kvstore import MemDB
from tendermint_tpu.libs.tracing import FlightRecorder
from tendermint_tpu.state.state import State
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.state.validation import verify_evidence
from tendermint_tpu.types import (
    PRECOMMIT_TYPE,
    DuplicateVoteEvidence,
    MockPV,
    Validator,
    ValidatorSet,
    VoteSet,
)
from tendermint_tpu.types.params import ConsensusParams, EvidenceParams
from tests.test_types import (
    CHAIN_ID,
    make_block_id,
    make_commit,
    rand_validator_set,
    signed_vote,
)


# -- update_with_change_set at N=100 ----------------------------------------


class TestUpdateWithChangeSet:
    def test_removing_current_proposer_at_n100(self):
        vset, pvs = rand_validator_set(100)
        vset.increment_proposer_priority(1)
        proposer = vset.get_proposer()
        vset.update_with_change_set([Validator.new(proposer.pub_key, 0)])
        assert vset.size() == 99
        assert not vset.has_address(proposer.address)
        new_proposer = vset.get_proposer()
        assert new_proposer is not None
        assert new_proposer.address != proposer.address
        # the cached proposer pointer must be live (a member), not stale
        assert vset.has_address(new_proposer.address)

    def test_priorities_recentered_after_churn_at_n100(self):
        vset, pvs = rand_validator_set(100)
        vset.increment_proposer_priority(37)
        # remove 10, add 10, double 10
        changes = [Validator.new(pv.get_pub_key(), 0) for pv in pvs[:10]]
        changes += [Validator.new(MockPV().get_pub_key(), 10) for _ in range(10)]
        changes += [Validator.new(pv.get_pub_key(), 20) for pv in pvs[10:20]]
        vset.update_with_change_set(changes)
        assert vset.size() == 100
        # re-centering: average priority ~0 (Go-truncation rounding slack)
        prios = [v.proposer_priority for v in vset.validators]
        assert abs(sum(prios)) < len(prios)
        # rescaling: spread bounded by the priority window
        from tendermint_tpu.types.validator import PRIORITY_WINDOW_SIZE_FACTOR

        assert max(prios) - min(prios) <= (
            PRIORITY_WINDOW_SIZE_FACTOR * vset.total_voting_power()
        )
        # rotation still works after the churn
        seen = set()
        for _ in range(100):
            vset.increment_proposer_priority(1)
            seen.add(vset.get_proposer().address)
        assert len(seen) > 50  # every-ish validator gets turns, no wedge

    def test_updated_proposer_power_reflected_in_cached_pointer(self):
        vset, pvs = rand_validator_set(4)
        vset.increment_proposer_priority(1)
        proposer = vset.get_proposer()
        _, pv = next(
            (i, p) for i, p in enumerate(pvs) if p.address() == proposer.address
        )
        vset.update_with_change_set([Validator.new(pv.get_pub_key(), 99)])
        again = vset.get_proposer()
        if again.address == proposer.address:
            assert again.voting_power == 99  # not the stale pre-update object

    def test_membership_change_rotates_pubkeys_digest(self):
        vset, _ = rand_validator_set(4)
        before = vset.pubkeys_digest()
        vset.update_with_change_set([Validator.new(MockPV().get_pub_key(), 10)])
        assert vset.pubkeys_digest() != before


# -- TableCache rebuild pipeline --------------------------------------------


class TestTableRebuild:
    def _engine(self):
        rec = FlightRecorder(size=256)
        from prometheus_client import CollectorRegistry

        from tendermint_tpu.libs.metrics import VerifyMetrics

        reg = CollectorRegistry()
        verifier = BatchVerifier(
            min_device_batch=1 << 30,  # host tier: no device compiles in tests
            metrics=VerifyMetrics(reg, CHAIN_ID),
            recorder=rec,
        )
        return verifier, rec, reg

    def _wait_table(self, cache, key, budget=30.0):
        deadline = time.monotonic() + budget
        while time.monotonic() < deadline:
            if cache.has_table(key):
                return
            time.sleep(0.02)
        raise AssertionError("table rebuild never completed")

    def test_rebuild_fires_recorder_event_and_counter(self):
        verifier, rec, reg = self._engine()
        cache = TableCache(verifier)
        vset, _ = rand_validator_set(5)
        key = vset.pubkeys_digest()
        rows = [v.pub_key.bytes() for v in vset.validators]
        assert cache.rebuild(key, rows) is True
        self._wait_table(cache, key)
        events = [e for e in rec.events() if e["kind"] == "verify.table_rebuild"]
        assert len(events) == 1
        ev = events[0]
        assert ev["ok"] is True
        assert ev["validators"] == 5
        assert ev["set_key"] == key.hex()[:16]
        assert (
            reg.get_sample_value(
                "tendermint_verify_table_rebuilds_total", {"chain_id": CHAIN_ID}
            )
            == 1.0
        )
        # second rebuild for the same set is a no-op (already cached)
        assert cache.rebuild(key, rows) is False

    def test_post_rotation_commit_verifies_through_engine_path(self):
        """The acceptance pin: after a set change, a commit signed by the
        NEW set must verify through the rebuilt table (the engine's
        indexed hook), not the cold fallback."""
        verifier, rec, _ = self._engine()
        cache = TableCache(verifier)
        vset, pvs = rand_validator_set(4)
        bid = make_block_id()

        # rotate: drop one validator, add two — the set the next commit uses
        joiners = [MockPV() for _ in range(2)]
        vset.update_with_change_set(
            [Validator.new(pvs[0].get_pub_key(), 0)]
            + [Validator.new(pv.get_pub_key(), 10) for pv in joiners]
        )
        new_pvs = sorted(pvs[1:] + joiners, key=lambda pv: pv.address())
        new_key = vset.pubkeys_digest()
        assert cache.rebuild(
            new_key, [v.pub_key.bytes() for v in vset.validators]
        )
        self._wait_table(cache, new_key)

        commit = make_commit(vset, new_pvs, 7, 0, bid)
        hits_before = [
            e for e in rec.events() if e["kind"] == "verify.table" and e["hit"]
        ]
        try:
            batch_hook.set_indexed_verifier(cache.verify_indexed)
            vset.verify_commit(CHAIN_ID, bid, 7, commit)
        finally:
            batch_hook.set_indexed_verifier(None)
        hits_after = [
            e for e in rec.events() if e["kind"] == "verify.table" and e["hit"]
        ]
        assert len(hits_after) == len(hits_before) + 1  # served by the table


# -- BLS aggregation flipping with set composition --------------------------


class TestAggregationFlip:
    def _bls_set(self, n, power=10):
        pytest.importorskip("tendermint_tpu.crypto.bls.keys")
        from tendermint_tpu.crypto.bls.keys import BlsPrivKey

        pvs = [MockPV(BlsPrivKey.from_secret(bytes([i + 1]) * 32)) for i in range(n)]
        vset = ValidatorSet([Validator.new(pv.get_pub_key(), power) for pv in pvs])
        pvs.sort(key=lambda pv: pv.address())
        return vset, pvs

    def test_fold_engages_on_uniform_and_disengages_on_mixed(self):
        from tendermint_tpu.types.agg_commit import fold_commit, set_is_uniform_bls

        vset, pvs = self._bls_set(4)
        assert set_is_uniform_bls(vset)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 9, 0, bid)
        agg = fold_commit(commit, vset, CHAIN_ID)
        assert agg is not None
        assert len(agg.agg_sig) == 96
        # ONE pairing authenticates the folded commit against the set
        vset.verify_commit(CHAIN_ID, bid, 9, agg)

        # mid-chain flip: one member rotates back to ed25519 — the set is
        # no longer uniform and folding must disengage
        ed = MockPV()
        mixed = vset.copy()
        mixed.update_with_change_set(
            [Validator.new(pvs[0].get_pub_key(), 0), Validator.new(ed.get_pub_key(), 10)]
        )
        assert not set_is_uniform_bls(mixed)
        mixed_pvs = sorted(pvs[1:] + [ed], key=lambda pv: pv.address())
        mixed_commit = make_commit(mixed, mixed_pvs, 10, 0, bid)
        assert fold_commit(mixed_commit, mixed, CHAIN_ID) is None
        # the classic path still verifies the mixed-set commit
        mixed.verify_commit(CHAIN_ID, bid, 10, mixed_commit)

    def test_catchup_agg_commit_authenticated_against_historical_set(self):
        """A laggard replaying a folded height verifies the stored
        AggregateCommit against the set AT THAT HEIGHT (loaded through
        the state store), not whatever set is current."""
        from tendermint_tpu.types.agg_commit import fold_commit

        vset, pvs = self._bls_set(4)
        bid = make_block_id()
        commit = make_commit(vset, pvs, 9, 0, bid)
        agg = fold_commit(commit, vset, CHAIN_ID)

        store = StateStore(MemDB())
        sets = []
        store._stage_validators(sets, 9, 9, vset)
        store.db.write_batch(sets)
        historical = store.load_validators(9)
        assert historical is not None and historical.hash() == vset.hash()
        historical.verify_commit(CHAIN_ID, bid, 9, agg)

        # a DIFFERENT set (post-rotation) must reject the same aggregate
        other, _ = self._bls_set(4, power=7)
        other_members = ValidatorSet(
            [Validator.new(MockPV().get_pub_key(), 10) for _ in range(4)]
        )
        with pytest.raises(ValueError):
            other_members.verify_commit(CHAIN_ID, bid, 9, agg)


# -- evidence across set changes (unbonding window) --------------------------


class TestEvidenceAcrossRotation:
    UNBONDING_BLOCKS = 20

    def _setup(self, evidence_height, current_height):
        """Validator set A (with the byzantine validator) active at
        evidence_height; the validator has since rotated out — the CURRENT
        set does not contain it."""
        vset, pvs = rand_validator_set(4)
        culprit = pvs[0]
        now_ns = time.time_ns()

        store = StateStore(MemDB())
        sets = []
        store._stage_validators(sets, evidence_height, evidence_height, vset)
        store.db.write_batch(sets)

        current = vset.copy()
        current.update_with_change_set([Validator.new(culprit.get_pub_key(), 0)])
        state = State(
            chain_id=CHAIN_ID,
            last_block_height=current_height,
            last_block_time_ns=now_ns,
            validators=current,
            next_validators=current.copy(),
            last_validators=current.copy(),
            consensus_params=ConsensusParams(
                evidence=EvidenceParams(
                    max_age_num_blocks=self.UNBONDING_BLOCKS,
                    max_age_duration_ns=3600 * 1_000_000_000,
                )
            ),
        )
        va = signed_vote(
            culprit, vset, PRECOMMIT_TYPE, evidence_height, 0, make_block_id(b"\x01"),
            ts=now_ns,
        )
        vb = signed_vote(
            culprit, vset, PRECOMMIT_TYPE, evidence_height, 0, make_block_id(b"\x02"),
            ts=now_ns,
        )
        ev = DuplicateVoteEvidence.from_votes(culprit.get_pub_key(), va, vb)
        return state, store, ev

    def test_departed_validator_accepted_inside_unbonding_window(self):
        from tendermint_tpu.evidence import EvidencePool

        state, store, ev = self._setup(
            evidence_height=10, current_height=10 + self.UNBONDING_BLOCKS - 3
        )
        # the culprit is NOT in the current set — only the historical one
        assert not state.validators.has_address(ev.address())
        pool = EvidencePool(MemDB(), store, state)
        pool.add_evidence(ev)
        assert pool.is_pending(ev)
        assert pool.num_pending() == 1

    def test_departed_validator_rejected_beyond_unbonding_window(self):
        state, store, ev = self._setup(
            evidence_height=10, current_height=10 + self.UNBONDING_BLOCKS + 1
        )
        with pytest.raises(ValueError, match="too old"):
            verify_evidence(state, ev, store)

    def test_rejected_when_no_historical_set_stored(self):
        state, store, ev = self._setup(
            evidence_height=10, current_height=12
        )
        empty_store = StateStore(MemDB())
        with pytest.raises(ValueError, match="no validator set stored"):
            verify_evidence(state, ev, empty_store)

    def test_never_a_validator_rejected_even_inside_window(self):
        state, store, ev = self._setup(evidence_height=10, current_height=12)
        outsider = MockPV()
        stranger_set, s_pvs = rand_validator_set(2)
        va = signed_vote(
            s_pvs[0], stranger_set, PRECOMMIT_TYPE, 10, 0, make_block_id(b"\x01"),
            ts=state.last_block_time_ns,
        )
        vb = signed_vote(
            s_pvs[0], stranger_set, PRECOMMIT_TYPE, 10, 0, make_block_id(b"\x02"),
            ts=state.last_block_time_ns,
        )
        bogus = DuplicateVoteEvidence.from_votes(s_pvs[0].get_pub_key(), va, vb)
        with pytest.raises(ValueError, match="not a validator"):
            verify_evidence(state, bogus, store)


# -- scenario DSL valset clauses --------------------------------------------


class TestValsetDSL:
    def test_parse_all_ops(self):
        from tendermint_tpu.chaos.scenario import Scenario

        s = Scenario.parse(
            "valset join 4 power=20 @1\n"
            "valset leave 2 @2\n"
            "valset power 1=50 @3\n"
            "valset migrate 0 bls @4\n"
            "valset migrate 3 ed25519 @5",
            seed=1,
        )
        ops = [e.args for e in s.timeline() if e.action == "valset"]
        assert ops[0] == {"op": "join", "node": 4, "power": 20}
        assert ops[1] == {"op": "leave", "node": 2}
        assert ops[2] == {"op": "power", "node": 1, "power": 50}
        # "bls" normalizes to the canonical scheme name
        assert ops[3] == {"op": "migrate", "node": 0, "scheme": "bls12381"}
        assert ops[4] == {"op": "migrate", "node": 3, "scheme": "ed25519"}

    def test_join_defaults_power(self):
        from tendermint_tpu.chaos.scenario import Scenario

        s = Scenario.parse("valset join 1 @0", seed=1)
        assert s.timeline()[0].args["power"] == 10

    def test_parse_rejections(self):
        from tendermint_tpu.chaos.scenario import Scenario, ScenarioError

        for text in (
            "valset join 1 power=0 @0",       # non-positive power
            "valset join 1 speed=9 @0",       # unknown key
            "valset migrate 0 rsa @0",        # unknown scheme
            "valset bogus 1 @0",              # unknown op
            "valset @0",                      # missing op
        ):
            with pytest.raises(ScenarioError):
                Scenario.parse(text, seed=1)

    def test_fingerprint_covers_valset_clauses(self):
        from tendermint_tpu.chaos.scenario import Scenario

        a = Scenario.parse("valset join 1 power=10 @0", seed=1)
        b = Scenario.parse("valset join 1 power=20 @0", seed=1)
        assert a.fingerprint() != b.fingerprint()


# -- RotatingPV --------------------------------------------------------------


class TestRotatingPV:
    def test_activates_candidate_in_observed_set(self):
        from tendermint_tpu.types import RotatingPV

        ed, ed2 = MockPV(), MockPV()
        pv = RotatingPV(ed, ed2)
        assert pv.get_pub_key() == ed.get_pub_key()  # candidate 0 pre-rotation

        vset = ValidatorSet([Validator.new(ed2.get_pub_key(), 10)])
        pv.observe_validators(vset)
        assert pv.get_pub_key() == ed2.get_pub_key()

        # a set containing NEITHER key keeps the current signer
        other = ValidatorSet([Validator.new(MockPV().get_pub_key(), 10)])
        pv.observe_validators(other)
        assert pv.get_pub_key() == ed2.get_pub_key()

        # rotating back
        back = ValidatorSet([Validator.new(ed.get_pub_key(), 10)])
        pv.observe_validators(back)
        assert pv.get_pub_key() == ed.get_pub_key()

    def test_signs_with_active_candidate(self):
        from tendermint_tpu.types import RotatingPV

        ed, ed2 = MockPV(), MockPV()
        pv = RotatingPV(ed, ed2)
        vset = ValidatorSet([Validator.new(ed2.get_pub_key(), 10)])
        pv.observe_validators(vset)
        vote = signed_vote(pv, vset, PRECOMMIT_TYPE, 3, 0, make_block_id())
        vote.verify(CHAIN_ID, ed2.get_pub_key())  # raises on mismatch
        with pytest.raises(Exception):
            vote.verify(CHAIN_ID, ed.get_pub_key())

    def test_requires_a_candidate(self):
        from tendermint_tpu.types import RotatingPV

        with pytest.raises(ValueError):
            RotatingPV()
