"""Catch-up by consensus, the plain reference: what a node behind its peers
makes of the precommits they send it for one height.

Nothing here imports the program.  Votes are plain fields (`Precommit`), the
set is a list of `Member`s (address, power, raw ed25519 key) in the set's
order, sign-bytes are reference.vote_sign_bytes (this directory's own copy
of the canonical vote layout) and every signature is checked on its own by
OpenSSL (reference.verify_one).

`tally` takes the precommits in the order the node received them, from
whichever peer, and keeps upstream's rule for counting them
(types/vote_set.go AddVote, Tendermint v0.33): a vote counts once, for the
validator its index names, signed by that validator's key over this
height's and round's sign-bytes; a block id is committed when the votes for
it carry more than two thirds of the set's power.  What it returns: the
valid votes counted, each dropped vote with its reason, the position in the
arrival order at which a block id first held more than two thirds, and that
block id.

Where it departs from upstream's AddVote, on purpose:

- One height, one round, precommits only.  A vote of another height, round
  or type is dropped (`wrong_step`), where upstream's HeightVoteSet would
  file it under its own round.
- A vote whose index and address disagree is dropped (`address_mismatch`);
  upstream returns an error, on which the program's reactor stops the peer.
  The same for a bad signature (`invalid_signature`): dropped here, an error
  there.
- A second vote of a validator already counted for the same block id is
  dropped (`duplicate`) whether its signature is the same (upstream: no-op)
  or another valid one (upstream: an error, non-deterministic signature).
  The signature is checked first here, so a bad copy reads
  `invalid_signature`.
- A valid vote of a validator already counted for another block id is
  dropped (`conflict`) and the first one stands.  Upstream keeps the
  conflict as evidence and, where a peer has claimed +2/3 for the second
  block, counts it there too; no peer claims anything here.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

from benchmarks import reference

BlockKey = Tuple[bytes, int, bytes]  # (block hash, parts total, parts hash)


@dataclass(frozen=True)
class Member:
    """One validator of the set, in the set's order."""

    address: bytes
    power: int
    pubkey: bytes


@dataclass(frozen=True)
class Precommit:
    """A precommit as received: the fields its sign-bytes and its slot take."""

    index: int
    address: bytes
    height: int
    round: int
    block_hash: bytes
    parts_total: int
    parts_hash: bytes
    timestamp_ns: int
    signature: bytes
    type: int = reference.PRECOMMIT_TYPE

    @property
    def block(self) -> BlockKey:
        return (self.block_hash, self.parts_total, self.parts_hash)


@dataclass
class Tally:
    valid: List[Precommit] = field(default_factory=list)  # counted, in arrival order
    dropped: List[Tuple[int, str]] = field(default_factory=list)  # (position, reason)
    maj23_at: Optional[int] = None  # the position whose vote brought a block over 2/3
    block: Optional[BlockKey] = None  # that block's id

    def reasons(self) -> List[str]:
        return [reason for _, reason in self.dropped]


def members(addresses: Sequence[bytes], powers: Sequence[int],
            pubkeys: Sequence[bytes]) -> List[Member]:
    return [Member(a, w, p) for a, w, p in zip(addresses, powers, pubkeys)]


def tally(chain_id: str, members: Sequence[Member], height: int, round_: int,
          arrivals: Sequence[Precommit]) -> Tally:
    """Count `arrivals` (in the order received) for (`height`, `round_`) by
    the set `members`; see the module's docstring."""
    total = sum(m.power for m in members)
    out = Tally()
    counted = {}  # validator index -> the block key its vote counted for
    power_for = {}  # block key -> power counted
    for pos, vote in enumerate(arrivals):
        if (vote.type, vote.height, vote.round) != (reference.PRECOMMIT_TYPE, height, round_):
            out.dropped.append((pos, "wrong_step"))
            continue
        if not 0 <= vote.index < len(members) or members[vote.index].address != vote.address:
            out.dropped.append((pos, "address_mismatch"))
            continue
        member = members[vote.index]
        msg = reference.vote_sign_bytes(
            chain_id, vote.height, vote.round, vote.block_hash, vote.parts_total,
            vote.parts_hash, vote.timestamp_ns,
        )
        if not reference.verify_one(member.pubkey, msg, vote.signature):
            out.dropped.append((pos, "invalid_signature"))
            continue
        if vote.index in counted:
            same = counted[vote.index] == vote.block
            out.dropped.append((pos, "duplicate" if same else "conflict"))
            continue
        counted[vote.index] = vote.block
        out.valid.append(vote)
        power_for[vote.block] = power_for.get(vote.block, 0) + member.power
        if out.maj23_at is None and power_for[vote.block] * 3 > total * 2:
            out.maj23_at, out.block = pos, vote.block
    return out
