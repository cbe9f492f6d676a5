"""The plain reference the benchmark compares the node against.

Nothing here imports the program.  Signatures are checked one at a time by
OpenSSL (through `cryptography`), sign-bytes are formed by this file's own
copy of the canonical vote layout, the kvstore's app hash is worked out
from the transaction count, and a validator set that changes is kept as a
plain {pubkey: power} under the ABCI's update rule.  The program's engine
(JAX kernels, host prep, table cache) and its `ValidatorSet` share no code
with any of it.

Also here, because it is part of the yardstick: the count of integer
operations and bytes one plain ed25519 verification needs, which the
kernels' roofline share divides by.
"""

from __future__ import annotations

import bisect
import functools
import hashlib
import random
import struct
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

PRECOMMIT_TYPE = 0x02
ED25519_L = 2**252 + 27742317777372353535851937790883648493  # group order


# ---------------------------------------------------------------------------
# canonical vote sign-bytes (Tendermint v0.33 CanonicalVote, as this chain
# lays it out: fixed64 height/round/timestamp, length-prefixed)
# ---------------------------------------------------------------------------


def uvarint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _tag(field: int, wire: int) -> bytes:
    return uvarint((field << 3) | wire)


def _f_varint(field: int, v: int) -> bytes:
    return b"" if v == 0 else _tag(field, 0) + uvarint(v)


def _f_fixed64(field: int, v: int, emit_zero: bool = False) -> bytes:
    if v == 0 and not emit_zero:
        return b""
    return _tag(field, 1) + struct.pack("<Q", v)


def _f_bytes(field: int, v: bytes) -> bytes:
    return b"" if not v else _tag(field, 2) + uvarint(len(v)) + v


def vote_sign_parts(
    chain_id: str, height: int, round_: int, block_hash: bytes, parts_total: int, parts_hash: bytes
) -> Tuple[bytes, bytes]:
    """(head, tail) of a precommit's sign-bytes: the full message is
    head + <timestamp as 8 little-endian bytes> + tail.  Within one commit
    only the timestamp differs between validators."""
    payload = _f_varint(1, PRECOMMIT_TYPE) + _f_fixed64(2, height) + _f_fixed64(3, round_)
    psh = _f_bytes(1, parts_hash) + _f_varint(2, parts_total)
    bid = _f_bytes(1, block_hash) + _f_bytes(2, psh)
    payload += _f_bytes(4, bid) + _tag(5, 1)
    tail = _f_bytes(6, chain_id.encode())
    return uvarint(len(payload) + 8 + len(tail)) + payload, tail


def vote_sign_bytes(
    chain_id: str, height: int, round_: int, block_hash: bytes, parts_total: int,
    parts_hash: bytes, timestamp_ns: int,
) -> bytes:
    head, tail = vote_sign_parts(chain_id, height, round_, block_hash, parts_total, parts_hash)
    return head + struct.pack("<Q", timestamp_ns) + tail


# ---------------------------------------------------------------------------
# commits
# ---------------------------------------------------------------------------


@dataclass
class CommitView:
    """A commit as the reference sees it: plain fields, no program types.
    `slots[i]` is None for an absent validator, else (timestamp_ns,
    signature) of validator i's precommit for the block."""

    height: int
    round: int
    block_hash: bytes
    parts_total: int
    parts_hash: bytes
    slots: List[Optional[Tuple[int, bytes]]]


def verify_one(pubkey: bytes, msg: bytes, sig: bytes) -> bool:
    """One ed25519 verification by OpenSSL: RFC 8032, canonical S only."""
    if len(sig) != 64 or len(pubkey) != 32:
        return False
    try:
        Ed25519PublicKey.from_public_bytes(pubkey).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True


def commit_verdict(
    chain_id: str, pubkeys: Sequence[bytes], powers: Sequence[int], commit: CommitView
) -> Tuple[Optional[int], bool]:
    """Serial verification of a whole commit, no early exit: (index of the
    first validator whose signature is rejected or None, whether the valid
    signatures carry more than two thirds of the set's power)."""
    head, tail = vote_sign_parts(
        chain_id, commit.height, commit.round, commit.block_hash,
        commit.parts_total, commit.parts_hash,
    )
    first_bad = None
    tallied = 0
    for i, slot in enumerate(commit.slots):
        if slot is None:
            continue
        ts, sig = slot
        if verify_one(pubkeys[i], head + struct.pack("<Q", ts) + tail, sig):
            tallied += powers[i]
        elif first_bad is None:
            first_bad = i
    return first_bad, tallied > sum(powers) * 2 // 3


# ---------------------------------------------------------------------------
# the kvstore application
# ---------------------------------------------------------------------------


def kvstore_app_hash(tx_count: int, height: int) -> bytes:
    """App hash of the builtin kvstore after `height` commits that
    delivered `tx_count` key=value transactions in all."""
    return hashlib.sha256(struct.pack("<QQ", tx_count, height)).digest()


# ---------------------------------------------------------------------------
# validator sets that change (Tendermint v0.33: ABCI validator updates
# delivered at height H change the set that signs H + 2)
# ---------------------------------------------------------------------------

Update = Tuple[bytes, int]  # (pubkey, new power); power 0 removes the validator


@functools.lru_cache(maxsize=4096)
def address(pubkey: bytes) -> bytes:
    return hashlib.sha256(pubkey).digest()[:20]


def apply_updates(members: Dict[bytes, int], updates: Sequence[Update]) -> Dict[bytes, int]:
    """A set held as {pubkey: power} after one block's validator updates."""
    out = dict(members)
    for pubkey, power in updates:
        if power < 0 or (power == 0 and pubkey not in out):
            raise ValueError(f"update ({pubkey.hex()[:16]}, {power}) fits no validator set")
        if power == 0:
            del out[pubkey]
        else:
            out[pubkey] = power
    return out


def in_address_order(members: Dict[bytes, int]) -> Tuple[List[bytes], List[int]]:
    pubkeys = sorted(members, key=address)
    return pubkeys, [members[p] for p in pubkeys]


def draw_updates(
    changes: dict, seed: int, height: int, members: Dict[bytes, int], standby: List[bytes],
) -> List[Update]:
    """The validator updates block `height` delivers, drawn from the seed
    under a configuration's `validator_set_changes`, against the newest set
    there is (the one the last updates made).  A swap takes the next standby
    key and puts the leaver at the queue's end: `standby` is changed."""
    rng = random.Random(f"bench-valset-{seed}-{height}")
    updates: List[Update] = []
    swapped = set()
    rule = changes.get("membership")
    if rule and height % rule["every_heights"] == 0:  # one validator swapped
        lowest = sorted(members, key=lambda p: (members[p], address(p)))
        leaver, joiner = rng.choice(lowest[: rule["leaver_among_lowest"]]), standby.pop(0)
        standby.append(leaver)
        updates += [(leaver, 0), (joiner, members[leaver])]
        swapped = {leaver, joiner}
    rule = changes.get("power")
    if rule and rng.random() < rule["share_of_blocks"]:
        few, many = (int(x) for x in rule["validators"].split("-"))
        staying = sorted((p for p in members if p not in swapped), key=address)
        for pubkey in rng.sample(staying, rng.randint(few, many)):
            power = members[pubkey]
            step = max(1, round(rng.random() * rule["delta_share"] * power))
            up = rng.random() < 0.5 or power - step < 1
            updates.append((pubkey, power + step if up else power - step))
    return updates


class SetHistory:
    """The validator set at every height of a chain: the genesis set and the
    updates each block delivered, applied two heights later."""

    def __init__(self, genesis: Sequence[Update], updates: Dict[int, Sequence[Update]]):
        members = dict(genesis)
        self._first = [1]  # the height each set signs first, ascending
        self.sets = [in_address_order(members)]
        self.membership_heights: List[int] = []  # where a new membership signs first
        for height in sorted(updates):
            if not updates[height]:
                continue
            changed = apply_updates(members, updates[height])
            if changed.keys() != members.keys():
                self.membership_heights.append(height + 2)
                pubkeys, powers = in_address_order(changed)
            else:  # powers only: the order stands
                pubkeys = self.sets[-1][0]
                powers = [changed[p] for p in pubkeys]
            members = changed
            self._first.append(height + 2)
            self.sets.append((pubkeys, powers))

    def index(self, height: int) -> int:
        """Which of `sets` signs `height`."""
        return bisect.bisect_right(self._first, height) - 1

    def at(self, height: int) -> Tuple[List[bytes], List[int]]:
        """(pubkeys, powers) in address order of the set that signs `height`."""
        return self.sets[self.index(height)]


def validator_sets(
    changes: dict, seed: int, heights: int, genesis: Sequence[Update], standby: Sequence[bytes],
) -> Tuple[SetHistory, Dict[int, List[Update]]]:
    """The seeded schedule of a chain of `heights` blocks: the sets by
    height, and the updates by the height that delivers them."""
    members, queue = dict(genesis), list(standby)
    updates: Dict[int, List[Update]] = {}
    for height in range(1, heights + 1):
        drawn = draw_updates(changes, seed, height, members, queue)
        if drawn:
            updates[height] = drawn
            members = apply_updates(members, drawn)
    return SetHistory(genesis, updates), updates


# ---------------------------------------------------------------------------
# work of one plain verification (for the kernels' roofline share)
# ---------------------------------------------------------------------------

LIMBS = 10  # radix 2^25.5 limbs of a field element, as ref10 holds them


def field_mul_ops() -> int:
    """Integer operations of one schoolbook multiplication mod 2^255-19 on
    10 limbs: 100 products, 81 additions into the 19 columns, 9 folds of the
    upper columns (a multiply by 19 and an addition each), then a carry
    chain of a shift, a mask and an addition per limb."""
    products = LIMBS * LIMBS
    column_adds = products - (2 * LIMBS - 1)
    folds = 2 * (LIMBS - 1)
    carries = 3 * LIMBS
    return products + column_adds + folds + carries


def field_add_ops() -> int:
    return LIMBS


def point_double_ops() -> int:
    """Extended twisted Edwards doubling (dbl-2008-hwcd): 4 squarings, 4
    multiplications, 7 additions or subtractions.  A plain implementation
    squares with the multiplier."""
    return 8 * field_mul_ops() + 7 * field_add_ops()


def point_add_ops() -> int:
    """Extended addition (add-2008-hwcd-3): 9 multiplications (one by 2d),
    8 additions or subtractions."""
    return 9 * field_mul_ops() + 8 * field_add_ops()


def field_pow_ops() -> int:
    """One exponentiation by a ~255-bit fixed exponent (a square root or an
    inversion): 254 squarings and 11 multiplications."""
    return 265 * field_mul_ops()


def verify_ops_per_signature() -> int:
    """A plain double-scalar verification of one signature: decompress A,
    then 256 steps of Shamir's trick over the bits of (S, h) — a doubling
    every step and an addition whenever either bit is set, three steps in
    four — and one inversion to compare the result with R.  SHA-512 runs
    on the host and is not counted."""
    ladder = 256 * point_double_ops() + 192 * point_add_ops()
    return field_pow_ops() + ladder + field_pow_ops()


def verify_bytes_per_signature() -> int:
    """Bytes one signature moves through HBM on the indexed path: its row
    index, packed h and S, R's y limbs and sign, the gathered table row
    (4 coordinates of 20 int32 limbs... as the table holds them), and the
    verdict."""
    return 4 + 32 + 32 + 2 * 20 + 1 + 4 * 20 * 4 + 1
