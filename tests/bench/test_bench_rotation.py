"""A chain whose validator set changes (PR 30): the seeded schedule keeps to
what a configuration's `validator_set_changes` says, the generator carries
it as the kvstore's `val:` transactions and signs every height with the set
that holds there, the plain reference's sets agree with the program's
`update_with_change_set` (order and roots), a commit signed by another
height's set is named, the window counts the new memberships whose table
build could land in it, and the set root's lap is read from the node's ring.
"""

import importlib
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import chain, harness, reference  # noqa: E402

from tendermint_tpu.crypto.keys import Ed25519PubKey  # noqa: E402
from tendermint_tpu.types import Block, Validator, ValidatorSet  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

CHANGES = {
    "power": {"share_of_blocks": 0.5, "validators": "1-3", "delta_share": 0.005},
    "membership": {"every_heights": 10, "leaver_among_lowest": 4, "standby": 3},
}
CONFIG = {
    "name": "toy-16r", "validators": 16, "absent_share": 0.1,
    "power": {"kind": "zipf", "top": 100000, "s": 0.8},
    "app": "kvstore", "node": {"db_backend": "memdb"}, "source_peers": 2,
    "validator_set_changes": CHANGES,
}
TRAFFIC = {"name": "replay", "txs_per_block": 3, "tx_bytes": 40, "warm_in_blocks": 2}
HEIGHTS = 64
SEED = 2_147_483_659


@pytest.fixture(scope="module")
def rotating_chain(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("chain") / "r")
    meta = chain.generate(CONFIG, TRAFFIC, SEED, HEIGHTS, out, workers=2)
    with open(os.path.join(out, "blocks.bin"), "rb") as f:
        raw = f.read()
    blocks = [
        Block.deserialize(raw[meta["offsets"][h - 1]: meta["offsets"][h]])
        for h in range(1, HEIGHTS + 1)
    ]
    return out, meta, blocks


def history_of(meta):
    _, pubs, pows = chain.committee(SEED, CONFIG)
    return chain.set_history(zip(pubs, pows), meta)


def program_set(pubs, pows):
    return ValidatorSet([Validator.new(Ed25519PubKey(p), w) for p, w in zip(pubs, pows)])


# -- the schedule ----------------------------------------------------------------


def test_the_schedule_keeps_to_the_configurations_rule():
    _, pubs, pows = chain.committee(SEED, CONFIG)
    waiting = chain.standby(SEED, CONFIG)[1]
    assert len(waiting) == 3 and not set(waiting) & set(pubs)
    history, updates = reference.validator_sets(CHANGES, SEED, 400, list(zip(pubs, pows)), waiting)
    again, same = reference.validator_sets(CHANGES, SEED, 400, list(zip(pubs, pows)), waiting)
    assert same == updates and again.sets == history.sets
    other = reference.validator_sets(CHANGES, SEED + 1, 400, list(zip(pubs, pows)), waiting)[1]
    assert other != updates
    # a swap every 10 heights signs first two heights later; nothing else moves the membership
    assert history.membership_heights == [h + 2 for h in range(10, 401, 10)]
    members = dict(zip(pubs, pows))
    power_blocks = 0
    for h in range(1, 401):
        ups = updates.get(h, [])
        assert len({p for p, _ in ups}) == len(ups)  # a change set names a validator once
        moved = [(p, w) for p, w in ups if w and p in members]
        power_blocks += bool(moved)
        assert len(moved) <= 3
        for p, w in moved:
            assert w >= 1 and 1 <= abs(w - members[p]) <= max(1, round(0.005 * members[p]))
        if h % 10 == 0:
            (leaver, zero), (joiner, power) = ups[:2]
            assert zero == 0 and power == members[leaver] and joiner not in members
            assert members[leaver] <= sorted(members.values())[3]  # one of the four lowest
        else:
            assert len(moved) == len(ups)
        members = reference.apply_updates(members, ups)
        assert len(members) == 16
        assert history.at(h + 2) == reference.in_address_order(members)
    assert 160 <= power_blocks <= 240  # about half the blocks
    assert history.at(1) == history.at(2) == (pubs, pows)
    # a leaver joins the queue's end: after the three spares, the first leavers come back
    assert len({p for s in history.sets for p in s[0]}) == 19


def test_an_update_that_fits_no_set_is_refused():
    with pytest.raises(ValueError):
        reference.apply_updates({b"a": 1}, [(b"b", 0)])
    with pytest.raises(ValueError):
        reference.apply_updates({b"a": 1}, [(b"a", -1)])
    assert reference.apply_updates({b"a": 1}, [(b"a", 0), (b"b", 7)]) == {b"b": 7}
    static = reference.SetHistory([(b"a", 1)], {})
    assert static.at(1) == static.at(10**6) == ([b"a"], [1]) and static.membership_heights == []


def test_the_references_sets_agree_with_the_programs_change_sets():
    """Order and roots, over the seeded schedule: the program applies each
    block's updates with `update_with_change_set`, the reference with a dict."""
    _, pubs, pows = chain.committee(SEED, CONFIG)
    history, updates = reference.validator_sets(
        CHANGES, SEED, 120, list(zip(pubs, pows)), chain.standby(SEED, CONFIG)[1]
    )
    vset = program_set(pubs, pows)
    for h in range(1, 121):
        if h in updates:
            vset.update_with_change_set(
                [Validator.new(Ed25519PubKey(p), w) for p, w in updates[h]]
            )
        want_pubs, want_pows = history.at(h + 2)
        assert [v.pub_key.bytes() for v in vset.validators] == want_pubs
        assert [v.voting_power for v in vset.validators] == want_pows
        assert vset.hash() == program_set(want_pubs, want_pows).hash()
        assert [v.address for v in vset.validators] == [reference.address(p) for p in want_pubs]


# -- the generator ---------------------------------------------------------------


def test_the_same_seed_gives_the_same_rotating_chain(rotating_chain, tmp_path):
    out, meta, _ = rotating_chain
    again = chain.generate(CONFIG, TRAFFIC, SEED, HEIGHTS, str(tmp_path / "b"), workers=1)
    with open(os.path.join(out, "blocks.bin"), "rb") as a, open(tmp_path / "b" / "blocks.bin", "rb") as b:
        assert a.read() == b.read()
    assert again["set_updates"] == meta["set_updates"]
    assert again["membership_heights"] == meta["membership_heights"] == [12, 22, 32, 42, 52, 62]
    key = chain.cache_key(CONFIG, TRAFFIC, SEED, HEIGHTS)
    assert key != chain.cache_key(dict(CONFIG, validator_set_changes="none"), TRAFFIC, SEED, HEIGHTS)


def test_the_meta_holds_the_seeded_schedule(rotating_chain):
    _, meta, _ = rotating_chain
    _, pubs, pows = chain.committee(SEED, CONFIG)
    history, updates = reference.validator_sets(
        CHANGES, SEED, HEIGHTS, list(zip(pubs, pows)), chain.standby(SEED, CONFIG)[1]
    )
    assert {int(h): [(bytes.fromhex(p), w) for p, w in ups]
            for h, ups in meta["set_updates"].items()} == updates
    assert history_of(meta).sets == history.sets


def test_every_block_is_made_by_the_set_that_holds_at_its_height(rotating_chain):
    _, meta, blocks = rotating_chain
    history = history_of(meta)
    chain_id = chain.chain_id(CONFIG["name"], SEED)
    for h, block in enumerate(blocks, start=1):
        header = block.header
        pubs, pows = history.at(h)
        assert header.validators_hash == program_set(pubs, pows).hash()
        assert header.next_validators_hash == program_set(*history.at(h + 1)).hash()
        assert header.proposer_address in [reference.address(p) for p in pubs]
        # the updates ride after the traffic's transactions, as the kvstore's own
        ups = meta["set_updates"].get(str(h), [])
        assert block.txs[:3] == chain.block_txs(SEED, h, TRAFFIC)
        assert block.txs[3:] == [chain.val_tx(bytes.fromhex(p), w) for p, w in ups]
        if h > 1:  # the block's LastCommit is the commit for h - 1
            commit = block.last_commit
            assert commit.height == h - 1 and commit.size() == 16
            assert not harness.accepted_wrongly(chain_id, history, commit, meta["hashes"][h - 2])
            assert header.app_hash == reference.kvstore_app_hash(3 * (h - 1), h - 1)


def test_a_val_tx_is_the_kvstores_own():
    from tendermint_tpu.abci import types as abci
    from tendermint_tpu.abci.examples import KVStoreApplication

    app = KVStoreApplication()
    pub = chain.public_key(b"\x07" * 32)
    res = app.deliver_tx(abci.RequestDeliverTx(tx=chain.val_tx(pub, 12345)))
    assert res.code == abci.CODE_TYPE_OK and res.data == b""
    (update,) = app.end_block(abci.RequestEndBlock(height=1)).validator_updates
    assert (update.pub_key, update.power) == (pub, 12345)
    app.deliver_tx(abci.RequestDeliverTx(tx=b"k=v"))
    assert app.commit().data == reference.kvstore_app_hash(1, 1)  # a val: tx does not count


def test_a_commit_signed_by_another_heights_set_is_named(rotating_chain):
    """`commits_accepted_wrongly` holds a commit to the reference's set for
    its own height: the same signatures under the set of a height across a
    swap (another key in one slot) or under moved powers alone are told apart
    from the right one."""
    _, meta, blocks = rotating_chain
    history = history_of(meta)
    chain_id = chain.chain_id(CONFIG["name"], SEED)
    # every update ten heights late: the set of the height one swap before
    late = reference.SetHistory(list(zip(*history.at(1))), {
        int(height) + 10: [(bytes.fromhex(p), w) for p, w in ups]
        for height, ups in meta["set_updates"].items()
    })
    for h in (12, 22, 32):  # the heights a new membership signs first
        commit = blocks[h].last_commit  # block h + 1 carries the commit for h
        assert commit.height == h
        assert not harness.accepted_wrongly(chain_id, history, commit, meta["hashes"][h - 1])
        assert harness.accepted_wrongly(chain_id, history, commit, meta["hashes"][h])
        shifted = late
        assert shifted.at(h)[0] != history.at(h)[0]
        assert harness.accepted_wrongly(chain_id, shifted, commit, meta["hashes"][h - 1])


# -- the set root's lap, and the memberships a window met -------------------------

T_OPEN, T_CLOSE = 10**9, 41 * 10**9


def window(events=(), membership_changes=(), blocks=4):
    cell = harness.Cell("toy.replay", 1, {}, {}, 0, end_to_end=[], per_layer=[])
    return harness.Window(
        cell=cell, seconds=40.0, t_open_ns=T_OPEN, t_close_ns=T_CLOSE,
        block_times=[T_OPEN / 1e9 + i for i in range(1, blocks + 1)],
        block_heights=list(range(1, blocks + 1)), events=list(events), deliver_spans=[],
        buffered=[], membership_changes=list(membership_changes),
    )


def test_the_set_roots_lap_has_its_file_and_its_entry_in_every_cell():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == "set_hash_ms_per_block")
    assert entry == {"name": "set_hash_ms_per_block", "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "consensus + replay loop",
                     "moves": "replay_blocks_per_s"}  # no `workloads`: a later cell gets it too
    with open(os.path.join(REPO, "benchmarks", "metrics", "set_hash_ms_per_block.json")) as f:
        spec = json.load(f)
    assert {k: spec[k] for k in entry} == entry
    assert spec["params"] == {"kind": "fastsync.block", "fields": ["set_hash_ms"]}
    assert entry["layer"] in {m["layer"] for m in BENCH["per_layer"] if m is not entry}
    for cell in BENCH["workloads"]:
        assert entry in harness.load_cell(cell["name"]).per_layer


def test_set_hash_ms_is_read_from_the_nodes_ring(monkeypatch):
    from tendermint_tpu.libs import tracing

    class Ring:
        enabled = True

        def events(self, since=0, kinds=None):
            return [
                {"kind": "fastsync.block", "seq": i, "t_ns": (2 + i) * 10**9, "id": 5 + i,
                 "dur_ns": 10**7, "set_hash_ms": ms}
                for i, ms in enumerate((1.25, 0.0, 0.75, 0.0))
            ]

    monkeypatch.setattr(tracing, "live_recorders", lambda: [Ring()])
    with open(os.path.join(REPO, "benchmarks", "metrics", "set_hash_ms_per_block.json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"benchmarks.reducers.{spec['reducer']}")
    assert reader.read(window(), spec["params"]) == pytest.approx(0.5)
    monkeypatch.setattr(tracing, "live_recorders", lambda: [])
    assert reader.read(window(), spec["params"]) is None


def test_the_window_counts_the_memberships_whose_build_could_land_in_it():
    """From two heights before the first block inside (a build begun as the
    update landed, three to four blocks long) to one past the last (the watch
    builds ahead of the first commit)."""

    class Stamps:
        times_ns = [10**9 * i for i in range(1, 13)]
        heights = list(range(100, 112))
        buffered = [0] * 12

    cell = harness.Cell("toy.replay", 1, {}, {}, 0, end_to_end=[], per_layer=[])
    # opens at the arrival of height 101: the blocks inside are 102..111
    w = harness.cut_window(
        cell, Stamps, 1, 60.0, [], [], [92, 99, 100, 101, 102, 111, 112, 113, 122]
    )
    assert w.block_heights[0] == 102 and w.block_heights[-1] == 111
    assert w.membership_changes == [100, 101, 102, 111, 112]
    assert harness.cut_window(cell, Stamps, 1, 60.0, [], []).membership_changes == []
