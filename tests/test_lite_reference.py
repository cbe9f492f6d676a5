"""The light client against the plain reference (benchmarks/reference_light.py):
the verifier's two rules and the client in both modes answer as the
reference does, on a few small chains of seeded keys and uneven powers,
each with one thing wrong at the header verified last.  Every case runs
twice: on the serial host path, and with the table path installed (a
stand-in for the engine's `TableCache` that verifies on the host by the rows
the program hands it, so the row indices of the by-address batch are what
is checked).  Verdicts are compared, not timings.
"""

from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Optional

import pytest

from benchmarks import reference
from benchmarks import reference_light as ref
from tendermint_tpu.crypto import batch as crypto_batch
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.lite2 import (
    BISECTION,
    SEQUENCE,
    Client,
    ErrNewValSetCantBeTrusted,
    InvalidHeaderError,
    MemStore,
    MockProvider,
    TrustOptions,
    verify_adjacent,
    verify_non_adjacent,
)
from tendermint_tpu.types import (
    BlockID,
    Commit,
    CommitSig,
    Header,
    PartSetHeader,
    SignedHeader,
    Validator,
    ValidatorSet,
)
from tendermint_tpu.types.validator import NotEnoughVotingPowerError

CHAIN = "lite-reference"
SEC = 1_000_000_000
T0 = 1_700_000_000 * SEC
PERIOD = 3600 * SEC
DRIFT = 10 * SEC  # the client's default max_clock_drift
NOW = T0 + 10 * SEC

KEYS = [Ed25519PrivKey.from_secret(b"lite-reference-%d" % i) for i in range(16)]
# eight uneven powers that sum to 90: 2/3 is 60 and 1/3 is 30, exactly
POWERS = [30, 20, 15, 10, 6, 5, 3, 1]
BASE = {i: p for i, p in enumerate(POWERS)}  # key index -> power
NEWCOMER = 8  # a key the base set does not hold
WITH_NEWCOMER = {**BASE, NEWCOMER: 100}  # 190: more than the base set's whole power
ALL = frozenset(range(len(KEYS)))


def by_power(*powers):
    """The key indices of the base set's members with these powers."""
    return frozenset(i for i, p in BASE.items() if p in powers)


@dataclass
class Case:
    """A chain of three heights (1 the trust root, 3 the target of a jump)
    and what is wrong at `at` (2 for a step, 3 for a jump)."""

    sets: Dict[int, Dict[int, int]] = field(default_factory=lambda: {1: BASE, 2: BASE, 3: BASE})
    at: int = 3
    signers: FrozenSet[int] = ALL  # of the set at `at`
    flip: Optional[int] = None  # the key whose signature at `at` has a bit flipped
    wrong_set: bool = False  # the primary hands another set for `at` than its header names
    time_ns: Optional[int] = None  # the header time at `at`
    now: int = NOW


CASES = {
    "honest jump": Case(),
    "honest step": Case(at=2),
    "flipped signature, jump": Case(flip=2),
    "flipped signature, step": Case(at=2, flip=0),
    "two thirds exactly, step": Case(at=2, signers=by_power(30, 20, 10)),
    "over two thirds, step": Case(at=2, signers=by_power(30, 20, 10, 1)),
    "two thirds exactly, jump": Case(signers=by_power(30, 20, 10)),
    # the newcomer holds more than the trusted set: the new set's two thirds
    # stand whatever the trusted set's share
    "one third of the trusted set exactly": Case(
        sets={1: BASE, 2: BASE, 3: WITH_NEWCOMER}, signers=by_power(30) | {NEWCOMER}),
    "over one third of the trusted set": Case(
        sets={1: BASE, 2: BASE, 3: WITH_NEWCOMER}, signers=by_power(30, 1) | {NEWCOMER}),
    "a signer the trusted set does not hold": Case(
        sets={1: BASE, 2: BASE, 3: WITH_NEWCOMER}, signers=by_power(30, 20) | {NEWCOMER}),
    "wrong validators hash, jump": Case(wrong_set=True),
    "wrong validators hash, step": Case(at=2, wrong_set=True),
    "expired trusted header": Case(now=T0 + SEC + PERIOD),
    "expired trusted header, step": Case(at=2, now=T0 + SEC + PERIOD),
    "a header past the drift": Case(time_ns=NOW + DRIFT),
    "a header within the drift": Case(time_ns=NOW + DRIFT - 1),
}


def program_set(members: Dict[int, int]) -> ValidatorSet:
    return ValidatorSet([Validator.new(KEYS[i].pub_key(), p) for i, p in members.items()])


def build(case: Case):
    """{height: SignedHeader} and {height: the ValidatorSet the primary hands}."""
    headers, vals = {}, {}
    last_id = BlockID()
    for h in (1, 2, 3):
        vset, next_vset = program_set(case.sets[h]), program_set(case.sets.get(h + 1, case.sets[h]))
        header = Header(
            chain_id=CHAIN, height=h, time_ns=case.time_ns if h == case.at and case.time_ns else T0 + h * SEC,
            last_block_id=last_id, validators_hash=vset.hash(),
            next_validators_hash=next_vset.hash(), proposer_address=vset.validators[0].address,
        )
        bid = BlockID(header.hash(), PartSetHeader(1, header.hash()))
        signers = case.signers if h == case.at else ALL
        key_of = {KEYS[i].pub_key().address(): KEYS[i] for i in case.sets[h]}
        slots = []
        for v in vset.validators:
            key = key_of[v.address]
            if KEYS.index(key) not in signers:
                slots.append(CommitSig.absent())
                continue
            ts = header.time_ns - SEC // 2
            sig = key.sign(reference.vote_sign_bytes(CHAIN, h, 0, bid.hash, 1, bid.hash, ts))
            if h == case.at and KEYS.index(key) == case.flip:
                sig = bytes([sig[0] ^ 1]) + sig[1:]
            slots.append(CommitSig.for_block(sig, v.address, ts))
        headers[h] = SignedHeader(header, Commit(h, 0, bid, slots))
        if h == case.at and case.wrong_set:
            first = next(iter(case.sets[h]))
            vset = program_set({**case.sets[h], first: case.sets[h][first] + 1})
        vals[h] = vset
        last_id = bid
    return headers, vals


# -- the program's objects as the reference sees them --------------------------------


def set_view(vset: ValidatorSet) -> ref.SetView:
    return ref.SetView([v.pub_key.bytes() for v in vset.validators],
                       [v.voting_power for v in vset.validators])


def signed_view(sh: SignedHeader) -> ref.Signed:
    h, c = sh.header, sh.commit
    return ref.Signed(
        ref.HeaderView(h.chain_id, h.height, h.time_ns, h.hash(), h.validators_hash,
                       h.next_validators_hash),
        reference.CommitView(
            c.height, c.round, c.block_id.hash, c.block_id.parts_header.total,
            c.block_id.parts_header.hash,
            [None if cs.is_absent() else (cs.timestamp_ns, cs.signature) for cs in c.signatures],
        ),
        [cs.validator_address for cs in c.signatures],
    )


def program_verdict(exc: Optional[BaseException]) -> str:
    """The reference's name for what the program raised."""
    if exc is None:
        return ref.OK
    if isinstance(exc, ErrNewValSetCantBeTrusted):
        return ref.CANNOT_BE_TRUSTED
    if isinstance(exc, NotEnoughVotingPowerError):
        return ref.NOT_ENOUGH_POWER
    if isinstance(exc, InvalidHeaderError):
        text = str(exc)
        for words, verdict in (
            ("trusted header expired", ref.EXPIRED), ("from the future", ref.FROM_THE_FUTURE),
            ("to match those supplied", ref.WRONG_SET), ("next validators", ref.BROKEN_LINK),
            ("to be greater than", ref.NOT_NEWER), ("to be after", ref.NOT_LATER),
        ):
            if words in text:
                return verdict
    if isinstance(exc, ValueError):
        return ref.BAD_SIGNATURE if "wrong signature" in str(exc) else ref.MALFORMED
    raise exc


def outcome(fn) -> str:
    try:
        fn()
    except Exception as exc:  # noqa: BLE001 — named by program_verdict, or raised again
        return program_verdict(exc)
    return ref.OK


@pytest.fixture(params=["host", "table"])
def path(request):
    """The serial host path, or the table path installed: a stand-in for
    TableCache.verify_indexed that verifies on the host by the rows handed."""
    if request.param == "table":
        def indexed(set_key, pubkeys, idxs, msgs, sigs):
            rows = pubkeys() if callable(pubkeys) else pubkeys
            indexed.calls += 1
            return crypto_batch.host_batch_verify([rows[i] for i in idxs], msgs, sigs)

        indexed.calls = 0
        crypto_batch.set_indexed_verifier(indexed)
        yield indexed
        crypto_batch.set_indexed_verifier(None)
    else:
        yield None


def reference_step(case: Case, headers, vals) -> str:
    trusted = signed_view(headers[1]).header
    new, new_vals = signed_view(headers[case.at]), set_view(vals[case.at])
    if case.at == 2:
        return ref.verify_adjacent(CHAIN, trusted, new, new_vals, PERIOD, case.now, DRIFT)
    return ref.verify_non_adjacent(CHAIN, trusted, set_view(vals[1]), new, new_vals, PERIOD,
                                   case.now, DRIFT)


@pytest.mark.parametrize("name", list(CASES))
def test_the_verifier_answers_as_the_reference(name, path):
    case = CASES[name]
    headers, vals = build(case)
    want = reference_step(case, headers, vals)
    if case.at == 2:
        got = outcome(lambda: verify_adjacent(
            CHAIN, headers[1], headers[2], vals[2], PERIOD, case.now, DRIFT))
    else:
        got = outcome(lambda: verify_non_adjacent(
            CHAIN, headers[1], vals[1], headers[3], vals[3], PERIOD, case.now, DRIFT))
    assert got == want
    if path is not None and want in (ref.OK, ref.BAD_SIGNATURE, ref.NOT_ENOUGH_POWER,
                                     ref.CANNOT_BE_TRUSTED):
        assert path.calls >= 1  # the commit checks took the table path


def test_the_cases_cover_every_verdict_they_name():
    """The boundaries land where the cases say, by the reference alone."""
    got = {}
    for name, case in CASES.items():
        headers, vals = build(case)
        got[name] = reference_step(case, headers, vals)
    assert got["honest jump"] == got["honest step"] == ref.OK
    assert got["flipped signature, jump"] == got["flipped signature, step"] == ref.BAD_SIGNATURE
    assert got["two thirds exactly, step"] == got["two thirds exactly, jump"] == ref.NOT_ENOUGH_POWER
    assert got["over two thirds, step"] == ref.OK
    assert got["one third of the trusted set exactly"] == ref.CANNOT_BE_TRUSTED
    assert got["over one third of the trusted set"] == ref.OK
    assert got["a signer the trusted set does not hold"] == ref.OK
    assert got["wrong validators hash, jump"] == got["wrong validators hash, step"] == ref.WRONG_SET
    assert got["expired trusted header"] == got["expired trusted header, step"] == ref.EXPIRED
    assert got["a header past the drift"] == ref.FROM_THE_FUTURE
    assert got["a header within the drift"] == ref.OK


def test_the_reference_hashes_a_set_as_the_program_does():
    for members in (BASE, WITH_NEWCOMER, {3: 7}):
        vset = program_set(members)
        assert set_view(vset).hash() == vset.hash()


@pytest.mark.parametrize("mode", [SEQUENCE, BISECTION])
@pytest.mark.parametrize("name", list(CASES))
async def test_the_client_answers_as_the_reference(name, mode, path):
    """The client from the trust root to the case's height in either mode,
    against the reference's walk of the same mode (a jump the trusted set
    cannot vouch for makes the bisection descend)."""
    case = CASES[name]
    headers, vals = build(case)
    fetch = lambda h: (signed_view(headers[h]), set_view(vals[h]))  # noqa: E731
    trusted = signed_view(headers[1]).header
    if mode == SEQUENCE:
        want = ref.sequence(CHAIN, trusted, case.at, fetch, PERIOD, case.now, DRIFT)
    else:
        want = ref.bisection(CHAIN, trusted, set_view(vals[1]), case.at, fetch, PERIOD, case.now,
                             DRIFT)
    client = Client(
        CHAIN, TrustOptions(PERIOD, 1, headers[1].header.hash()),
        MockProvider(CHAIN, headers, vals), store=MemStore(), mode=mode, now_fn=lambda: case.now,
    )
    try:
        sh = await client.verify_header_at_height(case.at)
    except Exception as exc:  # noqa: BLE001
        got = program_verdict(exc)
        assert client.store.signed_header(case.at) is None
    else:
        got = ref.OK
        assert sh.header.hash() == headers[case.at].header.hash()
        assert client.store.signed_header(case.at) is not None
    assert got == want
