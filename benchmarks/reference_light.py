"""Light verification, the plain reference: the rules of Tendermint v0.33's
lite2/verifier.go (HeaderExpired, verifyNewHeaderAndVals, VerifyAdjacent,
VerifyNonAdjacent) and the two commit checks of types/validator_set.go they
call (VerifyCommit: more than 2/3 of the header's own set, slot by slot;
VerifyCommitTrusting: more than the trust level of the trusted set, matched
by address), written out plainly, with the client's two ways of getting from
a trusted header to another (lite2/client.go sequence and bisection).

Nothing here imports the program.  A header, its commit and a validator set
are plain fields (HeaderView, reference.CommitView with the slots' addresses,
SetView); a set's hash is worked out here from its members; signatures go
through reference.verify_one and reference.commit_verdict (OpenSSL, one at a
time).  Each check answers a verdict: OK, or the name of the first rule the
header breaks, in upstream's order.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Callable, Dict, List, Sequence, Tuple

from benchmarks import reference

ED25519_TYPE = "tendermint/PubKeyEd25519"
DEFAULT_TRUST_LEVEL = (1, 3)  # lite2/trust_options.go DefaultTrustLevel

# verdicts
OK = "ok"
MALFORMED = "malformed"  # another chain, or a commit that is not for this header
EXPIRED = "expired"  # the trusted header is past its trusting period
NOT_NEWER = "not_newer"  # the new header's height is not above the trusted one's
NOT_LATER = "not_later"  # the new header's time is not after the trusted one's
FROM_THE_FUTURE = "from_the_future"  # past now plus the clock drift allowed
WRONG_SET = "wrong_set"  # the set supplied is not the one the header names
BROKEN_LINK = "broken_link"  # adjacent: not the set the trusted header named next
BAD_SIGNATURE = "bad_signature"  # a signature of a validator the check counts is invalid
CANNOT_BE_TRUSTED = "cannot_be_trusted"  # the trusted set's signers hold no more than the trust level
NOT_ENOUGH_POWER = "not_enough_power"  # the header's own set's signers hold no more than 2/3


def _varint_field(field: int, v: int) -> bytes:
    return b"" if v == 0 else reference.uvarint(field << 3) + reference.uvarint(v)


def _bytes_field(field: int, v: bytes) -> bytes:
    return b"" if not v else reference.uvarint((field << 3) | 2) + reference.uvarint(len(v)) + v


def merkle_root(items: Sequence[bytes]) -> bytes:
    """Tendermint's simple Merkle tree over raw items: leaf sha256(0x00 |
    item), inner sha256(0x01 | left | right), split at the largest power of
    two below the count."""
    def root(leaves: Sequence[bytes]) -> bytes:
        if not leaves:
            return hashlib.sha256(b"").digest()
        if len(leaves) == 1:
            return leaves[0]
        k = 1 << ((len(leaves) - 1).bit_length() - 1)
        return hashlib.sha256(b"\x01" + root(leaves[:k]) + root(leaves[k:])).digest()

    return root([hashlib.sha256(b"\x00" + item).digest() for item in items])


@dataclass
class SetView:
    """A validator set: ed25519 public keys and their powers, in the set's
    order (by address)."""

    pubkeys: List[bytes]
    powers: List[int]

    def hash(self) -> bytes:
        """The root over each validator's pubkey and power (types/validator.go
        Bytes: address and priority left out)."""
        return merkle_root([
            _bytes_field(1, _bytes_field(1, ED25519_TYPE.encode()) + _bytes_field(2, pub))
            + _varint_field(2, power)
            for pub, power in zip(self.pubkeys, self.powers)
        ])

    def total(self) -> int:
        return sum(self.powers)


@dataclass
class HeaderView:
    """The fields of a header the rules read.  `hash` is the header's hash as
    its source states it (the block id its commit must name); hashing a
    header is the chain's business, not a rule of light verification."""

    chain_id: str
    height: int
    time_ns: int
    hash: bytes
    validators_hash: bytes
    next_validators_hash: bytes


@dataclass
class Signed:
    """A header with its commit: the commit's slots index the header's own
    set, and `addresses[i]` is slot i's validator address (what the trusting
    check matches by)."""

    header: HeaderView
    commit: reference.CommitView
    addresses: List[bytes]


def header_expired(header: HeaderView, trusting_period_ns: int, now_ns: int) -> bool:
    """lite2/verifier.go HeaderExpired: expiry not after now."""
    return header.time_ns + trusting_period_ns <= now_ns


def verify_commit(chain_id: str, vals: SetView, signed: Signed) -> str:
    """types/validator_set.go VerifyCommit: one slot per member, every
    present signature valid, and more than 2/3 of the power behind them."""
    if len(vals.pubkeys) != len(signed.commit.slots):
        return MALFORMED
    first_bad, enough = reference.commit_verdict(chain_id, vals.pubkeys, vals.powers, signed.commit)
    if first_bad is not None:
        return BAD_SIGNATURE
    return OK if enough else NOT_ENOUGH_POWER


def verify_commit_trusting(chain_id: str, trusted: SetView, signed: Signed,
                           trust_level: Tuple[int, int] = DEFAULT_TRUST_LEVEL) -> str:
    """types/validator_set.go VerifyCommitTrusting: each present slot whose
    address the trusted set holds is checked with the trusted set's key and
    counts the trusted set's power; a slot of an address it does not hold is
    passed over.  More than `trust_level` of the trusted set's power."""
    commit = signed.commit
    by_address: Dict[bytes, int] = {reference.address(p): i for i, p in enumerate(trusted.pubkeys)}
    head, tail = reference.vote_sign_parts(
        chain_id, commit.height, commit.round, commit.block_hash, commit.parts_total,
        commit.parts_hash,
    )
    seen, tallied = set(), 0
    for slot, address in zip(commit.slots, signed.addresses):
        if slot is None or address not in by_address:
            continue
        i = by_address[address]
        if i in seen:  # one validator twice
            return MALFORMED
        seen.add(i)
        ts, sig = slot
        if not reference.verify_one(trusted.pubkeys[i], head + ts.to_bytes(8, "little") + tail, sig):
            return BAD_SIGNATURE
        tallied += trusted.powers[i]
    num, den = trust_level
    return OK if tallied > trusted.total() * num // den else CANNOT_BE_TRUSTED


def _new_header_and_set(chain_id: str, new: Signed, vals: SetView, trusted: HeaderView,
                        now_ns: int, max_clock_drift_ns: int) -> str:
    """lite2/verifier.go verifyNewHeaderAndVals, its ValidateBasic included."""
    header, commit = new.header, new.commit
    if header.chain_id != chain_id or commit.height != header.height or commit.block_hash != header.hash:
        return MALFORMED
    if header.height <= trusted.height:
        return NOT_NEWER
    if header.time_ns <= trusted.time_ns:
        return NOT_LATER
    if header.time_ns >= now_ns + max_clock_drift_ns:
        return FROM_THE_FUTURE
    if header.validators_hash != vals.hash():
        return WRONG_SET
    return OK


def verify_adjacent(chain_id: str, trusted: HeaderView, new: Signed, vals: SetView,
                    trusting_period_ns: int, now_ns: int, max_clock_drift_ns: int) -> str:
    """lite2/verifier.go VerifyAdjacent: the next height, signed by the set
    the trusted header named next."""
    if new.header.height != trusted.height + 1:
        return MALFORMED
    if header_expired(trusted, trusting_period_ns, now_ns):
        return EXPIRED
    verdict = _new_header_and_set(chain_id, new, vals, trusted, now_ns, max_clock_drift_ns)
    if verdict != OK:
        return verdict
    if new.header.validators_hash != trusted.next_validators_hash:
        return BROKEN_LINK
    return verify_commit(chain_id, vals, new)


def verify_non_adjacent(chain_id: str, trusted: HeaderView, trusted_vals: SetView, new: Signed,
                        vals: SetView, trusting_period_ns: int, now_ns: int,
                        max_clock_drift_ns: int,
                        trust_level: Tuple[int, int] = DEFAULT_TRUST_LEVEL) -> str:
    """lite2/verifier.go VerifyNonAdjacent: a later height, vouched for by
    more than the trust level of the trusted set and signed by more than 2/3
    of its own."""
    if new.header.height == trusted.height + 1:
        return MALFORMED
    if header_expired(trusted, trusting_period_ns, now_ns):
        return EXPIRED
    verdict = _new_header_and_set(chain_id, new, vals, trusted, now_ns, max_clock_drift_ns)
    if verdict != OK:
        return verdict
    verdict = verify_commit_trusting(chain_id, trusted_vals, new, trust_level)
    if verdict != OK:
        return verdict
    return verify_commit(chain_id, vals, new)


Fetch = Callable[[int], Tuple[Signed, SetView]]


def sequence(chain_id: str, trusted: HeaderView, target: int, fetch: Fetch,
             trusting_period_ns: int, now_ns: int, max_clock_drift_ns: int) -> str:
    """lite2/client.go sequence: every height up to `target`, each adjacent
    to the last; the first verdict that is not OK, else OK."""
    for h in range(trusted.height + 1, target + 1):
        new, vals = fetch(h)
        verdict = verify_adjacent(chain_id, trusted, new, vals, trusting_period_ns, now_ns,
                                  max_clock_drift_ns)
        if verdict != OK:
            return verdict
        trusted = new.header
    return OK


def bisection(chain_id: str, trusted: HeaderView, trusted_vals: SetView, target: int,
              fetch: Fetch, trusting_period_ns: int, now_ns: int, max_clock_drift_ns: int,
              trust_level: Tuple[int, int] = DEFAULT_TRUST_LEVEL) -> str:
    """lite2/client.go bisection: jump to `target`; where the trusted set
    cannot vouch for it, make the height halfway one's own first, then jump
    from there."""
    new, vals = fetch(target)
    if target == trusted.height + 1:
        return verify_adjacent(chain_id, trusted, new, vals, trusting_period_ns, now_ns,
                               max_clock_drift_ns)
    verdict = verify_non_adjacent(chain_id, trusted, trusted_vals, new, vals, trusting_period_ns,
                                  now_ns, max_clock_drift_ns, trust_level)
    if verdict != CANNOT_BE_TRUSTED:
        return verdict
    pivot = (trusted.height + target) // 2
    args = (fetch, trusting_period_ns, now_ns, max_clock_drift_ns, trust_level)
    verdict = bisection(chain_id, trusted, trusted_vals, pivot, *args)
    if verdict != OK:
        return verdict
    pivot_signed, pivot_vals = fetch(pivot)
    return bisection(chain_id, pivot_signed.header, pivot_vals, target, *args)

