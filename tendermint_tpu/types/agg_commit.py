"""AggregateCommit: the O(1)-size BLS commit representation.

A classic Commit carries one CommitSig per validator — O(N) bytes and O(N)
signature verifies per consumer (lite2, statesync trust roots, fastsync
replay, block validation).  When a validator set is uniformly BLS12-381,
commit assembly folds the +2/3 precommits into

    (height, round, block_id, signer bitmap, ONE 96-byte aggregate
     signature, one BFT timestamp)

verified by a single pairing check: e(Σ_{i∈bitmap} pkᵢ, H(m)) = e(g1, σ)
with m the TIMESTAMP-FREE canonical precommit sign-bytes (every folded
precommit signed the identical message — types/canonical.py
canonical_vote_sign_bytes_no_ts).  At N=100 that is ~120 bytes instead of
~10 KB and one pairing instead of 100 verifies.

Soundness note: FastAggregateVerify is only safe against rogue-key attacks
for proof-of-possession-checked key sets; genesis validation enforces a
valid PoP for every BLS validator (types/genesis.py).

Semantics deltas vs the reference Commit, both deliberate:
  * only FOR-BLOCK precommits fold into the bitmap — a nil precommit signs
    a different message and cannot join the aggregate, so ABCI
    `signed_last_block` reports nil-voters as absent;
  * BFT time collapses to one power-weighted median timestamp computed at
    fold time (the per-slot timestamps it summarizes are discarded), so
    `median_time` returns `timestamp_ns` directly.  Because BLS votes sign
    timestamp-free bytes, that median is UNPROVABLE from signatures:
    verifiers accept the folder's word for it, and on all-BLS nets block
    time is proposer-attested — bounded by header-time monotonicity
    (state/validation.py) and the propose-side clock-drift prevote gate,
    not by the (now self-referential) median equality check.
"""

from __future__ import annotations

from typing import List, Optional

from ..encoding import codec
from ..encoding.proto import field_bytes, field_time, field_varint
from ..libs.bitarray import BitArray
from . import canonical
from .block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BlockID,
    Commit,
    CommitSig,
)

BLS_SIGNATURE_SIZE = 96


class AggregateCommit:
    """Duck-types the Commit surface consumers actually touch (height,
    round, block_id, size, bit_array, hash, validate_basic, signatures
    view) — get_vote returns None because per-vote signatures no longer
    exist; laggards catch up via fastsync, whose replay verifies this
    commit with the same single pairing."""

    def __init__(
        self,
        height: int,
        round_: int,
        block_id: BlockID,
        signers: BitArray,
        agg_sig: bytes,
        timestamp_ns: int,
    ):
        self.height = height
        self.round = round_
        self.block_id = block_id
        self.signers = signers
        self.agg_sig = bytes(agg_sig)
        self.timestamp_ns = timestamp_ns
        self._hash: Optional[bytes] = None
        self._sigs_view: Optional[List[CommitSig]] = None
        self._record: Optional[bytes] = None  # the block store's (store/block_store.py)

    # -- Commit surface ----------------------------------------------------
    def size(self) -> int:
        return self.signers.bits

    def is_commit(self) -> bool:
        return self.signers.bits > 0

    def bit_array(self) -> BitArray:
        return self.signers.copy()

    def get_vote(self, val_idx: int):
        """Per-vote signatures are folded away — None, always.  Callers
        (reactor catchup) already tolerate None and fall back to block
        transfer."""
        return None

    @property
    def signatures(self) -> List[CommitSig]:
        """Read-only per-slot VIEW for consumers that only inspect
        presence (ABCI LastCommitInfo's signed_last_block).  The entries
        carry no address/signature — code that needs either must route on
        the commit type, which every verification path does."""
        if self._sigs_view is None:
            self._sigs_view = [
                CommitSig(
                    block_id_flag=(
                        BLOCK_ID_FLAG_COMMIT
                        if self.signers.get_index(i)
                        else BLOCK_ID_FLAG_ABSENT
                    ),
                    validator_address=b"",
                    timestamp_ns=0,
                    signature=b"",
                )
                for i in range(self.signers.bits)
            ]
        return self._sigs_view

    def sign_message(self, chain_id: str) -> bytes:
        """THE aggregated message: timestamp-free canonical precommit
        sign-bytes for (chain_id, height, round, block_id)."""
        return canonical.canonical_vote_sign_bytes_no_ts(
            chain_id,
            canonical.PRECOMMIT_TYPE,
            self.height,
            self.round,
            self.block_id.hash,
            self.block_id.parts_header.total,
            self.block_id.parts_header.hash,
        )

    def validate_basic(self) -> None:
        if self.height < 0:
            raise ValueError("negative Height")
        if self.round < 0:
            raise ValueError("negative Round")
        if self.block_id.is_zero():
            raise ValueError("commit cannot be for nil block")
        if self.signers.bits <= 0:
            raise ValueError("empty signer bitmap")
        if self.signers.count() == 0:
            raise ValueError("no signers in aggregate commit")
        if len(self.agg_sig) != BLS_SIGNATURE_SIZE:
            raise ValueError(
                f"aggregate signature must be {BLS_SIGNATURE_SIZE} bytes, got {len(self.agg_sig)}"
            )
        if self.timestamp_ns <= 0:
            raise ValueError("aggregate commit missing timestamp")

    def encode(self) -> bytes:
        """Canonical byte layout (hash input + the wire/bench size)."""
        return (
            field_varint(1, self.height)
            + field_varint(2, self.round)
            + field_bytes(3, self.block_id.encode())
            + field_bytes(4, self.signers.to_bytes())
            + field_bytes(5, self.agg_sig)
            + field_time(6, self.timestamp_ns)
        )

    def hash(self) -> bytes:
        if self._hash is None:
            from ..crypto import merkle

            self._hash = merkle.hash_from_byte_slices([self.encode()])
        return self._hash

    # -- serialization -----------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "height": self.height,
            "round": self.round,
            "block_id": self.block_id.to_dict(),
            "signers": self.signers.to_bytes(),
            "agg_sig": self.agg_sig,
            "timestamp_ns": self.timestamp_ns,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "AggregateCommit":
        return cls(
            d["height"],
            d["round"],
            BlockID.from_dict(d["block_id"]),
            BitArray.from_bytes(d["signers"]),
            d["agg_sig"],
            d["timestamp_ns"],
        )

    def __repr__(self) -> str:
        return (
            f"AggregateCommit(H={self.height} R={self.round} "
            f"signers={self.signers.count()}/{self.signers.bits})"
        )


codec.register("tm/AggCommit")(AggregateCommit)


def commit_from_dict(d: Optional[dict]):
    """Decode either commit representation (storage/wire dicts)."""
    if d is None:
        return None
    if "agg_sig" in d:
        return AggregateCommit.from_dict(d)
    return Commit.from_dict(d)


def weighted_median_timestamp(commit: Commit, validators) -> int:
    """Power-weighted median of a classic commit's non-absent timestamps —
    the exact BFT-time rule of state.median_time, applied at FOLD time so
    the aggregate carries the same block time the full commit would have
    produced."""
    weighted = []
    total_power = 0
    for cs in commit.signatures:
        if cs.is_absent():
            continue
        _, val = validators.get_by_address(cs.validator_address)
        if val is not None:
            total_power += val.voting_power
            weighted.append((cs.timestamp_ns, val.voting_power))
    if total_power == 0:
        raise ValueError("weighted_median_timestamp: no commit signatures match the validator set")
    weighted.sort()
    median = total_power // 2
    acc = 0
    for ts, power in weighted:
        if acc + power > median:
            return ts
        acc += power
    raise AssertionError("unreachable: weighted median not found")


def set_is_uniform_bls(val_set) -> bool:
    """True iff EVERY validator key is BLS12-381 — the aggregation gate.
    Mixed sets keep per-vote commits and per-scheme verify routing."""
    from .vote import is_bls_key

    vals = val_set.validators
    return bool(vals) and all(is_bls_key(v.pub_key) for v in vals)


def fold_commit(commit: Commit, val_set, chain_id: str) -> Optional["AggregateCommit"]:
    """Fold a classic +2/3 commit into an AggregateCommit, or None when
    ineligible (non-uniform key set, nothing to fold, or a malformed
    signature blob — the caller keeps the per-vote commit in every None
    case, so aggregation DISABLES itself cleanly on mixed nets)."""
    if not isinstance(commit, Commit) or not commit.signatures:
        return None
    if val_set.size() != len(commit.signatures):
        return None
    if not set_is_uniform_bls(val_set):
        return None
    signers = BitArray(val_set.size())
    sigs = []
    for idx, cs in enumerate(commit.signatures):
        if not cs.is_for_block():
            continue  # nil precommits sign a different message; absent is absent
        signers.set_index(idx, True)
        sigs.append(cs.signature)
    if not sigs:
        return None
    try:
        ts = weighted_median_timestamp(commit, val_set)
    except ValueError:
        return None
    from ..crypto.bls import scheme

    agg = scheme.aggregate_signatures(sigs)
    if agg is None:
        return None
    return AggregateCommit(commit.height, commit.round, commit.block_id, signers, agg, ts)


class AggregateLastCommit:
    """Restart adapter: consensus reconstructs rs.last_commit from the
    stored SeenCommit, but an aggregate seen-commit has no per-vote
    signatures to rebuild a VoteSet from.  This stand-in satisfies the
    narrow surface ConsensusState/reactor touch on rs.last_commit —
    proposal assembly reuses the aggregate directly; straggler precommits
    for the folded height are ignored (the commit is already +2/3 by
    construction, verified against the stored validator set on load)."""

    def __init__(self, commit: AggregateCommit):
        self.commit = commit
        self.height = commit.height
        self.round = commit.round
        self.signed_msg_type = canonical.PRECOMMIT_TYPE

    def has_two_thirds_majority(self) -> bool:
        return True

    def two_thirds_majority(self):
        return self.commit.block_id, True

    def make_commit(self) -> AggregateCommit:
        return self.commit

    def add_vote(self, vote, verify: bool = True) -> bool:
        return False  # nothing to add a straggler to; duplicate-safe

    def has_all(self) -> bool:
        return self.commit.signers.is_full()

    def get_by_index(self, val_idx: int):
        return None

    def bit_array(self) -> BitArray:
        return self.commit.bit_array()

    def size(self) -> int:
        return self.commit.size()

    def missing_votes(self, peer_bits):
        return []

    def select_votes(self, bits):
        return []

    def bits_we_lack(self, their_bits) -> BitArray:
        return BitArray(0)

    def __repr__(self) -> str:
        return f"AggregateLastCommit({self.commit!r})"
