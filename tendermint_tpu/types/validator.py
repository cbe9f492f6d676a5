"""Validator and ValidatorSet: proposer-priority math + batched commit
verification.

Reference parity: types/validator.go (Validator:16), types/validator_set.go
(ValidatorSet:42, IncrementProposerPriority:86, UpdateWithChangeSet:624,
VerifyCommit:629, VerifyCommitTrusting:754).  The priority arithmetic is
overflow-aware int64 math that must match the reference bit-for-bit across
nodes — Python ints are unbounded, so clipping is explicit here.

TPU inversion: VerifyCommit* gather (pubkey, msg, sig) triples for ALL
non-absent signatures and hand them to crypto.batch.get_verifier() as one
batch (vmapped ed25519 on TPU), then tally voting power from the boolean
mask.  The reference's early-exit-at-2/3 (validator_set.go:665) becomes
whole-batch verification — strictly stricter (a bad signature after the 2/3
mark fails the commit here) and deterministic across nodes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

from ..crypto import batch as crypto_batch
from ..crypto import merkle
from ..crypto.keys import PubKey, pubkey_from_dict
from ..encoding import codec
from ..encoding.proto import field_bytes, field_varint
from ..libs import tracing
from .block import BLOCK_ID_FLAG_ABSENT, BlockID, Commit, VoteBatch

INT64_MAX = (1 << 63) - 1
INT64_MIN = -(1 << 63)

# types/validator_set.go:25 — guards clipping/overflow in priority math
MAX_TOTAL_VOTING_POWER = INT64_MAX // 8
# types/validator_set.go:29
PRIORITY_WINDOW_SIZE_FACTOR = 2


def safe_add_clip(a: int, b: int) -> int:
    c = a + b
    return min(max(c, INT64_MIN), INT64_MAX)


def safe_sub_clip(a: int, b: int) -> int:
    c = a - b
    return min(max(c, INT64_MIN), INT64_MAX)


def mixed_batch_verify(
    pubkey_objs: Sequence[PubKey],
    msgs: Sequence[bytes],
    sigs: Sequence[bytes],
    batch_verify: Optional[Callable] = None,
    indexed: Optional[tuple] = None,
) -> List[bool]:
    """Verify a commit's signatures, routing by key type: ed25519 rides the
    installed device batch (crypto/batch.py); other key types (sr25519,
    secp256k1, threshold multisig) verify via their own PubKey.verify — the
    reference's per-key-type dispatch (crypto.PubKey interface), batched
    where the hardware pays off.

    `indexed=(set_key, set_pubkey_rows, row_idxs)` lets callers that know
    the validator-set identity and row indices (verify_commit*) route
    through the per-valset device table engine (crypto/batch.py indexed
    hook: HBM pubkey rows) — the steady-state path gathers pubkeys
    on-device instead of shipping them per call."""
    from ..crypto.keys import Ed25519PubKey

    n = len(msgs)
    out: List[bool] = [False] * n
    ed_idx = [i for i, pk in enumerate(pubkey_objs) if isinstance(pk, Ed25519PubKey)]
    if ed_idx and len(ed_idx) == n and indexed is not None and batch_verify is None:
        iv = crypto_batch.get_indexed_verifier()
        if iv is not None:
            set_key, set_rows, row_idxs = indexed
            res = iv(set_key, set_rows, row_idxs, msgs, sigs)
            if res is not None:
                return [bool(r) for r in res]
    if ed_idx:
        verify = batch_verify or crypto_batch.get_verifier()
        res = verify(
            [pubkey_objs[i].bytes() for i in ed_idx],
            [msgs[i] for i in ed_idx],
            [sigs[i] for i in ed_idx],
        )
        for i, r in zip(ed_idx, res):
            out[i] = bool(r)
    if len(ed_idx) != n:
        ed_set = set(ed_idx)
        for i, pk in enumerate(pubkey_objs):
            if i in ed_set:
                continue
            try:
                out[i] = bool(pk.verify(msgs[i], sigs[i]))
            except Exception:
                out[i] = False
    return out


class NotEnoughVotingPowerError(Exception):
    """types/validator_set.go:838 ErrNotEnoughVotingPowerSigned."""

    def __init__(self, got: int, needed: int):
        self.got = got
        self.needed = needed
        super().__init__(
            f"invalid commit -- insufficient voting power: got {got}, needed more than {needed}"
        )


@dataclass
class Validator:
    """types/validator.go:16.  ProposerPriority is volatile per-round state."""

    address: bytes
    pub_key: PubKey
    voting_power: int
    proposer_priority: int = 0

    @classmethod
    def new(cls, pub_key: PubKey, voting_power: int) -> "Validator":
        return cls(pub_key.address(), pub_key, voting_power, 0)

    def copy(self) -> "Validator":
        return Validator(self.address, self.pub_key, self.voting_power, self.proposer_priority)

    def compare_proposer_priority(self, other: "Validator") -> "Validator":
        """Higher priority wins; ties break toward the lower address
        (types/validator.go:41)."""
        if self.proposer_priority > other.proposer_priority:
            return self
        if self.proposer_priority < other.proposer_priority:
            return other
        if self.address < other.address:
            return self
        if self.address > other.address:
            return other
        raise ValueError("cannot compare identical validators")

    def bytes(self) -> bytes:
        """Hash input: pubkey + power, excluding address and priority
        (types/validator.go:83)."""
        pk = self.pub_key.to_dict()
        inner = field_bytes(1, pk["type"]) + field_bytes(2, pk["value"])
        return field_bytes(1, inner) + field_varint(2, self.voting_power)

    def to_dict(self) -> dict:
        return {
            "address": self.address,
            "pub_key": self.pub_key.to_dict(),
            "voting_power": self.voting_power,
            "proposer_priority": self.proposer_priority,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "Validator":
        return cls(
            d["address"], pubkey_from_dict(d["pub_key"]), d["voting_power"], d["proposer_priority"]
        )

    def __repr__(self) -> str:
        return f"Validator{{{self.address.hex()[:12]} VP:{self.voting_power} A:{self.proposer_priority}}}"


class ValidatorSet:
    """Validators sorted by address; proposer rotates by priority
    (types/validator_set.go:42)."""

    def __init__(self, validators: Optional[List[Validator]] = None):
        self.validators: List[Validator] = []
        self.proposer: Optional[Validator] = None
        self._total_voting_power = 0
        self._pk_digest: Optional[bytes] = None
        self._root: Optional[bytes] = None
        self._packed: Optional[bytes] = None
        if validators:
            self._update_with_change_set(validators, allow_deletes=False)
            self.increment_proposer_priority(1)

    def pubkeys_digest(self) -> bytes:
        """Cheap stable key for this set's pubkey rows (device table cache
        key) — sha256 over the concatenated raw pubkeys, cached until the
        membership changes.  Unlike hash() this ignores voting power and
        priorities, which don't affect the pubkey table."""
        if self._pk_digest is None:
            import hashlib

            h = hashlib.sha256()
            for v in self.validators:
                pk = v.pub_key.bytes()
                # length-prefix each key: mixed-size key types must not be
                # able to collide under different concatenation splits
                h.update(bytes([len(pk) & 0xFF]))
                h.update(pk)
            self._pk_digest = h.digest()
        return self._pk_digest

    # -- basic accessors ---------------------------------------------------
    def is_nil_or_empty(self) -> bool:
        return len(self.validators) == 0

    def size(self) -> int:
        return len(self.validators)

    def __len__(self) -> int:
        return len(self.validators)

    def has_address(self, address: bytes) -> bool:
        return self._index_of(address) is not None

    def _index_of(self, address: bytes) -> Optional[int]:
        lo, hi = 0, len(self.validators)
        while lo < hi:
            mid = (lo + hi) // 2
            if self.validators[mid].address < address:
                lo = mid + 1
            else:
                hi = mid
        if lo < len(self.validators) and self.validators[lo].address == address:
            return lo
        return None

    def get_by_address(self, address: bytes) -> Tuple[int, Optional[Validator]]:
        idx = self._index_of(address)
        if idx is None:
            return -1, None
        return idx, self.validators[idx].copy()

    def get_by_index(self, index: int) -> Tuple[Optional[bytes], Optional[Validator]]:
        if index < 0 or index >= len(self.validators):
            return None, None
        v = self.validators[index]
        return v.address, v.copy()

    def total_voting_power(self) -> int:
        if self._total_voting_power == 0:
            self._update_total_voting_power()
        return self._total_voting_power

    def _update_total_voting_power(self) -> None:
        total = 0
        for v in self.validators:
            total = safe_add_clip(total, v.voting_power)
            if total > MAX_TOTAL_VOTING_POWER:
                raise OverflowError(
                    f"total voting power must not exceed {MAX_TOTAL_VOTING_POWER}; got {total}"
                )
        self._total_voting_power = total

    def copy(self) -> "ValidatorSet":
        new = ValidatorSet()
        new.validators = [v.copy() for v in self.validators]
        new.proposer = self.proposer
        new._total_voting_power = self._total_voting_power
        new._pk_digest = self._pk_digest
        new._root = self._root
        new._packed = self._packed
        return new

    def hash(self) -> bytes:
        """Merkle root over validator bytes (types/validator_set.go:315),
        built once and kept until a pubkey or a power changes: priorities
        and addresses are not in `Validator.bytes()`, so a block's rotation
        of the proposer leaves it standing."""
        if not self.validators:
            return b""
        if self._root is None:
            self._root = merkle.hash_from_byte_slices([v.bytes() for v in self.validators])
        return self._root

    # -- proposer rotation -------------------------------------------------
    def get_proposer(self) -> Optional[Validator]:
        if not self.validators:
            return None
        if self.proposer is None:
            self.proposer = self._find_proposer()
        return self.proposer.copy()

    def _find_proposer(self) -> Validator:
        proposer = None
        for v in self.validators:
            if proposer is None:
                proposer = v
            elif v.address != proposer.address:
                proposer = proposer.compare_proposer_priority(v)
        return proposer

    def increment_proposer_priority(self, times: int) -> None:
        """types/validator_set.go:86."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if times <= 0:
            raise ValueError("cannot call increment_proposer_priority with non-positive times")
        diff_max = PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power()
        self.rescale_priorities(diff_max)
        self._shift_by_avg_proposer_priority()
        proposer = None
        for _ in range(times):
            proposer = self._increment_proposer_priority()
        self.proposer = proposer

    def _increment_proposer_priority(self) -> Validator:
        self._packed = None
        for v in self.validators:
            v.proposer_priority = safe_add_clip(v.proposer_priority, v.voting_power)
        # compare_proposer_priority returns one of its operands, so `mostest`
        # is the live list entry and the decrement below sticks.
        mostest = self._get_val_with_most_priority()
        mostest.proposer_priority = safe_sub_clip(
            mostest.proposer_priority, self.total_voting_power()
        )
        return mostest

    def _get_val_with_most_priority(self) -> Validator:
        res = None
        for v in self.validators:
            res = v if res is None else res.compare_proposer_priority(v)
        return res

    def _compute_avg_proposer_priority(self) -> int:
        n = len(self.validators)
        total = sum(v.proposer_priority for v in self.validators)
        # Python floor-division on negatives differs from Go integer division
        # (Go truncates toward zero); match Go for cross-impl determinism.
        avg = abs(total) // n
        return avg if total >= 0 else -avg

    def _shift_by_avg_proposer_priority(self) -> None:
        avg = self._compute_avg_proposer_priority()
        self._packed = None
        for v in self.validators:
            v.proposer_priority = safe_sub_clip(v.proposer_priority, avg)

    def _compute_max_min_priority_diff(self) -> int:
        prios = [v.proposer_priority for v in self.validators]
        return abs(max(prios) - min(prios))

    def rescale_priorities(self, diff_max: int) -> None:
        """types/validator_set.go:112."""
        if self.is_nil_or_empty():
            raise ValueError("empty validator set")
        if diff_max <= 0:
            return
        diff = self._compute_max_min_priority_diff()
        ratio = (diff + diff_max - 1) // diff_max
        if diff > diff_max:
            self._packed = None
            for v in self.validators:
                # Go truncates toward zero
                q = abs(v.proposer_priority) // ratio
                v.proposer_priority = q if v.proposer_priority >= 0 else -q

    def copy_increment_proposer_priority(self, times: int) -> "ValidatorSet":
        c = self.copy()
        c.increment_proposer_priority(times)
        return c

    # -- updates (ABCI validator-set changes) ------------------------------
    def update_with_change_set(self, changes: List[Validator]) -> None:
        self._update_with_change_set(changes, allow_deletes=True)

    def _update_with_change_set(self, changes: List[Validator], allow_deletes: bool) -> None:
        """types/validator_set.go:561 — validate, split into updates/deletes,
        compute priorities for new validators, merge, rescale, recenter."""
        if not changes:
            return
        updates, deletes = self._process_changes(changes)
        if not allow_deletes and deletes:
            raise ValueError(f"cannot process validators with voting power 0: {deletes}")
        num_new = sum(1 for u in updates if not self.has_address(u.address))
        if num_new == 0 and len(self.validators) == len(deletes):
            raise ValueError("applying the validator changes would result in empty set")
        removed_power = self._verify_removals(deletes)
        tvp_after_updates_before_removals = self._verify_updates(updates, removed_power)
        self._compute_new_priorities(updates, tvp_after_updates_before_removals)
        self._apply_updates(updates)
        self._apply_removals(deletes)
        self._pk_digest = None  # membership changed: table cache key rotates
        self._root = None  # and the one place a power changes: hash() builds anew
        self._packed = None
        self._update_total_voting_power()
        self.rescale_priorities(PRIORITY_WINDOW_SIZE_FACTOR * self.total_voting_power())
        self._shift_by_avg_proposer_priority()
        # The cached proposer may have been removed (stale pointer: a
        # validator no longer in the set) or replaced by _apply_updates (a
        # stale object: old power/priority).  Re-point it at the live entry,
        # or clear it so get_proposer() recomputes from the new priorities.
        if self.proposer is not None:
            _, live = self.get_by_address(self.proposer.address)
            self.proposer = live

    @staticmethod
    def _process_changes(orig_changes: List[Validator]) -> Tuple[List[Validator], List[Validator]]:
        changes = sorted([v.copy() for v in orig_changes], key=lambda v: v.address)
        updates, removals = [], []
        prev_addr = None
        for v in changes:
            if v.address == prev_addr:
                raise ValueError(f"duplicate entry {v} in changes")
            if v.voting_power < 0:
                raise ValueError(f"voting power can't be negative: {v.voting_power}")
            if v.voting_power > MAX_TOTAL_VOTING_POWER:
                raise ValueError(
                    f"voting power can't be higher than {MAX_TOTAL_VOTING_POWER}: {v.voting_power}"
                )
            (removals if v.voting_power == 0 else updates).append(v)
            prev_addr = v.address
        return updates, removals

    def _verify_removals(self, deletes: List[Validator]) -> int:
        removed_power = 0
        for v in deletes:
            _, val = self.get_by_address(v.address)
            if val is None:
                raise ValueError(f"failed to find validator {v.address.hex()} to remove")
            removed_power += val.voting_power
        if len(deletes) > len(self.validators):
            raise ValueError("more deletes than validators")
        return removed_power

    def _verify_updates(self, updates: List[Validator], removed_power: int) -> int:
        """types/validator_set.go:395 — ensure max total power is never
        exceeded, checking deltas smallest-first."""

        def delta(u: Validator) -> int:
            _, val = self.get_by_address(u.address)
            return u.voting_power - val.voting_power if val else u.voting_power

        tvp_after_removals = self.total_voting_power() - removed_power
        for u in sorted(updates, key=delta):
            tvp_after_removals += delta(u)
            if tvp_after_removals > MAX_TOTAL_VOTING_POWER:
                raise ValueError(
                    f"failed to add/update validator {u.address.hex()}: "
                    f"total voting power would exceed the max allowed {MAX_TOTAL_VOTING_POWER}"
                )
        return tvp_after_removals + removed_power

    def _compute_new_priorities(self, updates: List[Validator], updated_tvp: int) -> None:
        """New validators start at -1.125*tvp so they can't game rotation by
        re-bonding (types/validator_set.go:447)."""
        for u in updates:
            _, val = self.get_by_address(u.address)
            if val is None:
                u.proposer_priority = -(updated_tvp + (updated_tvp >> 3))
            else:
                u.proposer_priority = val.proposer_priority

    def _apply_updates(self, updates: List[Validator]) -> None:
        existing = self.validators
        merged: List[Validator] = []
        i = j = 0
        while i < len(existing) and j < len(updates):
            if existing[i].address < updates[j].address:
                merged.append(existing[i])
                i += 1
            else:
                merged.append(updates[j])
                if existing[i].address == updates[j].address:
                    i += 1
                j += 1
        merged.extend(existing[i:])
        merged.extend(updates[j:])
        self.validators = merged

    def _apply_removals(self, deletes: List[Validator]) -> None:
        delete_addrs = {v.address for v in deletes}
        self.validators = [v for v in self.validators if v.address not in delete_addrs]

    # -- aggregate (BLS) commit verification -------------------------------
    def verify_aggregate_commit(
        self,
        chain_id: str,
        block_id: BlockID,
        height: int,
        commit,
        needed: int,
        commit_vals: Optional["ValidatorSet"] = None,
    ) -> None:
        """ONE pairing check for an AggregateCommit: e(Σpk_bitmap, H(m)) ·
        e(-g1, σ) == 1, with power tallied against SELF.  `commit_vals` is
        the set the bitmap indexes (the commit's own set); when omitted it
        is this set (verify_commit).  The scheme memo means an async
        pre-verify lane (statesync/lite2/fastsync) that already paired
        this commit serves the check without re-pairing."""
        commit.validate_basic()
        if height != commit.height:
            raise ValueError(f"invalid commit height: want {height}, got {commit.height}")
        if block_id != commit.block_id:
            raise ValueError(
                f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
            )
        bitmap_vals = commit_vals if commit_vals is not None else self
        if commit.signers.bits != bitmap_vals.size():
            raise ValueError(
                f"invalid aggregate commit -- wrong bitmap size: "
                f"{commit.signers.bits} vs {bitmap_vals.size()}"
            )
        from .vote import is_bls_key

        idxs = commit.signers.true_indices()
        pks = []
        for i in idxs:
            pk = bitmap_vals.validators[i].pub_key
            if not is_bls_key(pk):
                raise ValueError(f"aggregate commit signer #{i} is not a BLS12-381 key")
            pks.append(pk.bytes())
        msg = commit.sign_message(chain_id)

        from ..crypto.bls import scheme

        ok = scheme.memo_get(pks, msg, commit.agg_sig)
        if ok is None:
            ok = scheme.fast_aggregate_verify(pks, msg, commit.agg_sig)
            scheme.memo_put(pks, msg, commit.agg_sig, ok)
        if not ok:
            raise ValueError("invalid aggregate commit signature")

        if bitmap_vals is self:
            tallied = sum(self.validators[i].voting_power for i in idxs)
        else:
            # trusting/future checks: the bitmap indexes the commit's set;
            # credit only signers that are also members of THIS set
            tallied = 0
            for i in idxs:
                _, val = self.get_by_address(bitmap_vals.validators[i].address)
                if val is not None:
                    tallied += val.voting_power
        if tallied <= needed:
            raise NotEnoughVotingPowerError(got=tallied, needed=needed)

    # -- batched commit verification (the TPU hot path) --------------------
    def verify_commit(
        self,
        chain_id: str,
        block_id: BlockID,
        height: int,
        commit: Commit,
        batch_verify: Optional[Callable] = None,
    ) -> None:
        """+2/3 of this set signed the commit (types/validator_set.go:629).

        Signatures and validators are index-aligned, so pubkeys gather by
        index straight into the batch — no address lookups.  Aggregate
        (BLS) commits route to the single-pairing check instead.
        """
        from .agg_commit import AggregateCommit

        if isinstance(commit, AggregateCommit):
            self.verify_aggregate_commit(
                chain_id, block_id, height, commit,
                needed=self.total_voting_power() * 2 // 3,
            )
            return
        if self.size() != len(commit.signatures):
            raise ValueError(
                f"invalid commit -- wrong set size: {self.size()} vs {len(commit.signatures)}"
            )
        _verify_commit_basic(commit, height, block_id)

        with tracing.child_span("verify.commit", height=height) as span:
            # signatures align with set rows: validator index IS the row
            batch = commit.vote_batch(chain_id, [v.pub_key for v in self.validators])
            span.set(n=len(batch.sigs), templated=batch.templated)
            span.lap("sign_bytes_ms")

            ok = self._verify_batch(batch, batch.idxs, batch_verify)
            span.lap("engine_ms")

            needed = self.total_voting_power() * 2 // 3
            tallied = _tally(batch, ok, (self.validators[i].voting_power for i in batch.idxs))
            span.lap("tally_ms")
        if tallied <= needed:
            raise NotEnoughVotingPowerError(got=tallied, needed=needed)

    def _verify_batch(self, batch, row_idxs, batch_verify) -> List[bool]:
        """mixed_batch_verify of a commit's batch whose position p signs
        with this set's row row_idxs[p]."""
        indexed = None
        if crypto_batch.get_indexed_verifier() is not None:
            # Rows are passed lazily — a table-cache hit (the steady state)
            # never materializes the V-sized list.
            indexed = (
                self.pubkeys_digest(),
                lambda: [v.pub_key.bytes() for v in self.validators],
                row_idxs,
            )
        return mixed_batch_verify(
            batch.pub_keys, batch.msgs, batch.sigs, batch_verify, indexed=indexed
        )

    def _batch_by_address(self, chain_id: str, commit: Commit, double_vote_raises: bool):
        """The commit's batch over the slots whose address is in THIS set
        (the commit may belong to another), with this set's row and power
        for each position.  A second slot of one validator raises or, for
        the future-commit check, is skipped."""
        seen_vals: dict = {}
        pub_keys: list = [None] * len(commit.signatures)
        rows: dict = {}
        for idx, cs in enumerate(commit.signatures):
            if cs.block_id_flag == BLOCK_ID_FLAG_ABSENT:
                continue
            val_idx, val = self.get_by_address(cs.validator_address)
            if val is None:
                continue
            if val_idx in seen_vals:
                if double_vote_raises:
                    raise ValueError(f"double vote from {val} ({seen_vals[val_idx]} and {idx})")
                continue
            seen_vals[val_idx] = idx
            pub_keys[idx] = val.pub_key
            rows[idx] = val_idx
        batch = commit.vote_batch(chain_id, pub_keys)
        row_idxs = [rows[i] for i in batch.idxs]
        return batch, row_idxs, [self.validators[r].voting_power for r in row_idxs]

    def ed25519_vote_triples(self, chain_id: str, commit: Commit) -> List[Tuple[bytes, bytes, bytes]]:
        """(pubkey, sign-bytes, signature) of every present ed25519 slot of
        an index-aligned commit: what the async pre-verify lanes (statesync,
        liteserve) hand the engine.  Other key types verify via their own
        PubKey path in mixed_batch_verify."""
        from ..crypto.keys import Ed25519PubKey

        batch = commit.vote_batch(
            chain_id,
            [v.pub_key if isinstance(v.pub_key, Ed25519PubKey) else None for v in self.validators],
        )
        return list(zip((pk.bytes() for pk in batch.pub_keys), batch.msgs, batch.sigs))

    def verify_future_commit(
        self,
        new_set: "ValidatorSet",
        chain_id: str,
        block_id: BlockID,
        height: int,
        commit: Commit,
        batch_verify: Optional[Callable] = None,
    ) -> None:
        """Old-set check for light clients (types/validator_set.go:703):
        commit must be valid for new_set AND >2/3 of the old set signed."""
        new_set.verify_commit(chain_id, block_id, height, commit, batch_verify)

        from .agg_commit import AggregateCommit

        if isinstance(commit, AggregateCommit):
            # signature already checked (and memoized) against new_set
            # above; this pass re-tallies the bitmap against the OLD set
            self.verify_aggregate_commit(
                chain_id, block_id, height, commit,
                needed=self.total_voting_power() * 2 // 3,
                commit_vals=new_set,
            )
            return

        batch, _, powers = self._batch_by_address(chain_id, commit, double_vote_raises=False)
        ok = mixed_batch_verify(batch.pub_keys, batch.msgs, batch.sigs, batch_verify)
        old_voting_power = _tally(batch, ok, powers)

        needed = self.total_voting_power() * 2 // 3
        if old_voting_power <= needed:
            raise NotEnoughVotingPowerError(got=old_voting_power, needed=needed)

    def verify_commit_trusting(
        self,
        chain_id: str,
        block_id: BlockID,
        height: int,
        commit: Commit,
        trust_numerator: int = 1,
        trust_denominator: int = 3,
        batch_verify: Optional[Callable] = None,
        commit_vals: Optional["ValidatorSet"] = None,
    ) -> None:
        """trustLevel of this (old, trusted) set signed the commit — the
        lite2 skipping-verification core (types/validator_set.go:754).
        Validators are matched by address since the commit may belong to a
        different validator set.  For an AggregateCommit the bitmap indexes
        the commit's OWN set, so callers must supply it as `commit_vals`
        (lite2 always holds it — it is the untrusted header's set)."""
        if trust_numerator * 3 < trust_denominator or trust_numerator > trust_denominator:
            raise ValueError(
                f"trustLevel must be within [1/3, 1], given {trust_numerator}/{trust_denominator}"
            )
        from .agg_commit import AggregateCommit

        if isinstance(commit, AggregateCommit):
            if commit_vals is None:
                raise ValueError(
                    "aggregate commit trusting-verify requires the commit's validator set"
                )
            self.verify_aggregate_commit(
                chain_id, block_id, height, commit,
                needed=self.total_voting_power() * trust_numerator // trust_denominator,
                commit_vals=commit_vals,
            )
            return
        _verify_commit_basic(commit, height, block_id)

        with tracing.child_span("verify.commit", height=height) as span:
            batch, row_idxs, powers = self._batch_by_address(
                chain_id, commit, double_vote_raises=True
            )
            span.set(n=len(batch.sigs), templated=batch.templated)
            span.lap("sign_bytes_ms")

            ok = self._verify_batch(batch, row_idxs, batch_verify)
            span.lap("engine_ms")

            needed = self.total_voting_power() * trust_numerator // trust_denominator
            tallied = _tally(batch, ok, powers)
            span.lap("tally_ms")
        if tallied <= needed:
            raise NotEnoughVotingPowerError(got=tallied, needed=needed)

    # -- TPU pubkey table --------------------------------------------------
    def pubkey_table(self):
        """[V, 32] uint8 array of raw ed25519 pubkeys, set order — the
        HBM-resident table the batch verifier gathers from by index."""
        import numpy as np

        table = np.zeros((len(self.validators), 32), dtype=np.uint8)
        for i, v in enumerate(self.validators):
            pk = v.pub_key.bytes()
            if len(pk) == 32:
                table[i] = np.frombuffer(pk, dtype=np.uint8)
        return table

    # -- serialization -----------------------------------------------------
    def packed(self) -> codec.Packed:
        """`codec.dumps(self.to_dict())` in pieces: the form `State.bytes()`
        nests.  The members' encoding is kept until a priority, a power or
        the membership changes (every method that writes one drops it;
        `copy()` carries it), so a set promoted unchanged from one state to
        the next is not encoded again.  The proposer is encoded on every
        call: `copy()` shares it between sets."""
        if self._packed is None:
            self._packed = codec.dumps([v.to_dict() for v in self.validators])
        return codec.packed_map({
            "validators": codec.Packed(self._packed),
            "proposer": self.proposer.to_dict() if self.proposer else None,
        })

    def to_dict(self) -> dict:
        return {
            "validators": [v.to_dict() for v in self.validators],
            "proposer": self.proposer.to_dict() if self.proposer else None,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "ValidatorSet":
        new = cls()
        new.validators = [Validator.from_dict(v) for v in d["validators"]]
        new.proposer = Validator.from_dict(d["proposer"]) if d["proposer"] else None
        return new

    def __repr__(self) -> str:
        return f"ValidatorSet(n={len(self.validators)} tvp={self.total_voting_power()})"


codec.register("tm/ValidatorSet")(ValidatorSet)


def _tally(batch: VoteBatch, ok: Sequence[bool], powers) -> int:
    """Voting power of the batch's votes for the block, `powers` aligned
    with the batch; the first bad signature raises.  Stray signatures
    (votes for nil) are valid but don't count toward the block's power
    (validator_set.go:656-662)."""
    tallied = 0
    for pos, (good, power, counts) in enumerate(zip(ok, powers, batch.for_block)):
        if not good:
            raise ValueError(f"wrong signature (#{batch.idxs[pos]}): {batch.sigs[pos].hex()}")
        if counts:
            tallied += power
    return tallied


def _verify_commit_basic(commit: Commit, height: int, block_id: BlockID) -> None:
    """types/validator_set.go:813."""
    commit.validate_basic()
    if height != commit.height:
        raise ValueError(f"invalid commit height: want {height}, got {commit.height}")
    if block_id != commit.block_id:
        raise ValueError(
            f"invalid commit -- wrong block ID: want {block_id}, got {commit.block_id}"
        )
