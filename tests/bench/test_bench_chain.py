"""The seeded chain generator: the same seed gives the same bytes, another
seed another chain, and a CPU node replays the chain at toy size (8
validators, 12 heights) through its own fast-sync loop to the generator's
block hashes and app hash.
"""

import asyncio
import hashlib
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import chain, harness, reference  # noqa: E402

CONFIG = {
    "name": "toy-8", "validators": 8, "absent_share": 0.1,
    "power": {"kind": "zipf", "top": 1000, "s": 0.8},
    "app": "kvstore", "node": {"db_backend": "memdb"}, "source_peers": 2,
}
TRAFFIC = {"name": "replay", "txs_per_block": 3, "tx_bytes": 40, "warm_in_blocks": 2}
HEIGHTS = 12
SEED = 2_147_483_659  # past 32 signed bits, as the driver's seeds are


def digest(chain_dir):
    with open(os.path.join(chain_dir, "blocks.bin"), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.fixture(scope="module")
def toy_chain(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("chain") / "a")
    return out, chain.generate(CONFIG, TRAFFIC, SEED, HEIGHTS, out, workers=1)


def test_a_static_sets_bytes_are_the_ones_the_generator_always_wrote(toy_chain):
    """Pinned on the generator as PR 29 left it (chain.FORMAT 3): teaching it
    sets that change moved no byte of a chain whose set stands still, so no
    cached chain's name had to."""
    out, meta = toy_chain
    assert chain.FORMAT == 3
    assert digest(out) == "5bcfd89f5b1d5ac1989d9cf7f31711dcf2be9dd62f1f5db1bc75ff5f65751258"
    assert meta["offsets"][-1] == 23664
    assert "set_updates" not in meta and "membership_heights" not in meta
    assert chain.cache_key(CONFIG, TRAFFIC, SEED, HEIGHTS) == "toy-8.replay.s2147483659.h12.4cd42b0868"
    assert chain.set_changes(CONFIG) == chain.set_changes({"validator_set_changes": "none"}) == {}


def test_the_same_seed_gives_the_same_chain(toy_chain, tmp_path):
    out, meta = toy_chain
    again = chain.generate(CONFIG, TRAFFIC, SEED, HEIGHTS, str(tmp_path / "b"), workers=2)
    assert digest(str(tmp_path / "b")) == digest(out)
    assert again["hashes"] == meta["hashes"] and again["offsets"] == meta["offsets"]


def test_another_seed_gives_another_chain(toy_chain, tmp_path):
    out, meta = toy_chain
    other = chain.generate(CONFIG, TRAFFIC, SEED + 1, HEIGHTS, str(tmp_path / "c"), workers=1)
    assert other["hashes"][0] != meta["hashes"][0]
    assert other["chain_id"] != meta["chain_id"]


def test_cache_key_names_everything_the_bytes_depend_on():
    base = chain.cache_key(CONFIG, TRAFFIC, SEED, HEIGHTS)
    assert base == chain.cache_key(dict(CONFIG), dict(TRAFFIC), SEED, HEIGHTS)
    assert base != chain.cache_key({**CONFIG, "absent_share": 0.2}, TRAFFIC, SEED, HEIGHTS)
    assert base != chain.cache_key(CONFIG, {**TRAFFIC, "tx_bytes": 41}, SEED, HEIGHTS)
    assert base != chain.cache_key(CONFIG, TRAFFIC, SEED + 1, HEIGHTS)
    assert base != chain.cache_key(CONFIG, TRAFFIC, SEED, HEIGHTS + 1)
    assert all(c.isalnum() or c in "._-" for c in base)


def test_an_unfinished_chain_is_not_loaded(toy_chain, tmp_path):
    out, _ = toy_chain
    assert chain.load_meta(out) is not None
    assert chain.load_meta(str(tmp_path / "nothing")) is None
    cut = tmp_path / "cut"
    cut.mkdir()
    (cut / "meta.json").write_bytes(open(os.path.join(out, "meta.json"), "rb").read())
    (cut / "blocks.bin").write_bytes(open(os.path.join(out, "blocks.bin"), "rb").read()[:-1])
    assert chain.load_meta(str(cut)) is None


def test_the_old_chains_go_first_when_the_cache_is_pruned(tmp_path):
    for i, name in enumerate(("old", "mid", "new")):
        d = tmp_path / name
        d.mkdir()
        (d / "blocks.bin").write_bytes(b"x" * 100)
        os.utime(d, (1000 + i, 1000 + i))
    chain.prune_cache(str(tmp_path), keep_bytes=250)
    assert sorted(os.listdir(tmp_path)) == ["mid", "new"]


def test_powers_follow_the_configurations_rule():
    hub = {"validators": 175, "power": {"kind": "zipf", "top": 1000000, "s": 0.8}}
    powers = chain.powers(hub)
    assert len(powers) == 175 and powers == sorted(powers, reverse=True)
    # the ten largest hold 37.1% of the power, as the configuration's file says
    assert round(100 * sum(powers[:10]) / sum(powers), 1) == 37.1
    assert chain.powers({"validators": 3, "power": {"kind": "equal", "each": 10}}) == [10] * 3
    with pytest.raises(ValueError):
        chain.powers({"validators": 3, "power": {"kind": "nope"}})


def test_every_transaction_is_a_distinct_write_of_the_stated_size():
    txs = [tx for h in (1, 2, 3) for tx in chain.block_txs(SEED, h, TRAFFIC)]
    assert all(len(tx) == TRAFFIC["tx_bytes"] for tx in txs)
    keys = [chain.tx_key_value(tx)[0] for tx in txs]
    assert len(set(keys)) == len(keys) == 9
    assert txs == [tx for h in (1, 2, 3) for tx in chain.block_txs(SEED, h, TRAFFIC)]


async def test_a_cpu_node_replays_the_chain_to_the_generators_app_hash(toy_chain, tmp_path):
    """The node's own fast-sync loop (BlockchainReactor._try_sync: commit
    verification, validation, ABCI delivery, stores) over the generated
    blocks, fed to its processor as a peer's would be."""
    from tendermint_tpu.node import Node
    from tendermint_tpu.types import Block

    out, meta = toy_chain
    cell = harness.Cell("toy-8.replay", 1, CONFIG, TRAFFIC, HEIGHTS, [], [])
    _, pubs, powers = chain.committee(SEED, CONFIG)

    def configure(cfg):
        cfg.tpu.enabled = False  # 8 signatures ride the host tier anyway
        cfg.rpc.laddr = ""

    cfg = harness.node_config(cell, str(tmp_path / "home"), configure)
    node = Node(cfg, harness.genesis(cell, SEED, pubs, powers), priv_validator=None)
    await node.start()
    try:
        reactor = node.blockchain_reactor
        with open(os.path.join(out, "blocks.bin"), "rb") as f:
            raw = f.read()
        for h in range(1, HEIGHTS + 1):
            block = Block.deserialize(raw[meta["offsets"][h - 1]: meta["offsets"][h]])
            reactor.processor.add_block(h, block, "generator")
        reactor._wake_pool()
        for _ in range(400):
            if node.block_store.height() >= HEIGHTS - 1:
                break
            await asyncio.sleep(0.05)
        # the last block waits for a successor's commit, as in any fast sync
        assert node.block_store.height() == HEIGHTS - 1
        for h in range(1, HEIGHTS):
            assert node.block_store.load_block_meta(h).block_id.hash.hex() == meta["hashes"][h - 1]
        applied = HEIGHTS - 1
        assert reactor.state.app_hash == reference.kvstore_app_hash(
            TRAFFIC["txs_per_block"] * applied, applied
        )
        assert reactor.state.last_block_height == applied
    finally:
        await node.stop()
