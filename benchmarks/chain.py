"""Seeded chain generator: the history a joining node replays.

`python benchmarks/chain.py --config <file> --traffic <file> --seed <n>
--heights <h> --out <dir>` writes `<dir>/blocks.bin` (every block as the
wire carries it, back to back) and `<dir>/meta.json` (offsets, block ids,
what the reference needs).  The same seed gives the same bytes.

The chain is real: every header links to the last, every commit is signed
by the set that holds at its height over that block's id, block time is the
commit's weighted median.  It is built without a node: every header field
is a function of the height, the transaction count, the last commit and
the validator set, and the set is a function of the seed (static, or moved
by the configuration's `validator_set_changes`: reference.validator_sets),
so nothing is executed and nothing verified here — the node under test does
both.  Where the set changes, the changes ride in the blocks as the
kvstore's own `val:` transactions, after the traffic mix's, and the set they
make signs two heights later.  Signing runs in worker processes, a slice of
the key pool (committee and standby) each; a height cannot start before the
last one's signatures are in, so the workers run in step.

This process and its workers never import JAX.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import multiprocessing
import os
import shutil
import sys
import time
from typing import List, Optional, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reference  # noqa: E402

GENESIS_TIME_NS = 1_700_000_000_000_000_000
BLOCK_INTERVAL_NS = 1_000_000_000  # timeout_commit's default 1 s between heights
VOTE_JITTER_NS = 200_000_000  # validators' precommit clocks within 200 ms
FORMAT = 3  # part of the cache key: bump when the generated bytes change


# ---------------------------------------------------------------------------
# what follows from the seed alone (the harness and the workers recompute it)
# ---------------------------------------------------------------------------


def chain_id(config_name: str, seed: int) -> str:
    return f"bench-{config_name}-{seed}"[:50]


def secrets(seed: int, n: int) -> List[bytes]:
    """32-byte ed25519 seeds of the committee, by rank (rank 0 holds the
    most power)."""
    return [hashlib.sha256(b"bench-validator-%d-%d" % (seed, i)).digest() for i in range(n)]


def powers(config: dict) -> List[int]:
    """Voting power by rank, from the configuration's `power` rule."""
    rule = config["power"]
    n = config["validators"]
    if rule["kind"] == "equal":
        return [rule["each"]] * n
    if rule["kind"] == "zipf":
        return [max(1, round(rule["top"] / (rank + 1) ** rule["s"])) for rank in range(n)]
    raise ValueError(f"unknown power rule {rule['kind']!r}")


def public_key(secret: bytes) -> bytes:
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    return Ed25519PrivateKey.from_private_bytes(secret).public_key().public_bytes(
        serialization.Encoding.Raw, serialization.PublicFormat.Raw
    )


def committee(seed: int, config: dict) -> Tuple[List[bytes], List[bytes], List[int]]:
    """(secrets, public keys, powers) of the genesis set in validator-set
    order, which is by address: sha256(pubkey)[:20]."""
    members = []
    for secret, power in zip(secrets(seed, config["validators"]), powers(config)):
        pub = public_key(secret)
        members.append((hashlib.sha256(pub).digest()[:20], secret, pub, power))
    members.sort()
    return [m[1] for m in members], [m[2] for m in members], [m[3] for m in members]


def set_changes(config: dict) -> dict:
    """The configuration's `validator_set_changes`; empty for a static set
    (the key absent, or "none")."""
    changes = config.get("validator_set_changes")
    return changes if isinstance(changes, dict) else {}


def standby(seed: int, config: dict) -> Tuple[List[bytes], List[bytes]]:
    """(secrets, public keys) of the keys waiting to join, in the order they
    will: the ranks after the committee's."""
    n = config["validators"]
    spare = set_changes(config).get("membership", {}).get("standby", 0)
    waiting = secrets(seed, n + spare)[n:]
    return waiting, [public_key(s) for s in waiting]


def set_history(genesis, meta: dict) -> "reference.SetHistory":
    """The sets by height of a generated chain, from its genesis set
    [(pubkey, power)] and the updates its meta.json holds (none: static)."""
    return reference.SetHistory(list(genesis), {
        int(h): [(bytes.fromhex(pub), power) for pub, power in updates]
        for h, updates in meta.get("set_updates", {}).items()
    })


def val_tx(pubkey: bytes, power: int) -> bytes:
    """The kvstore's validator-update transaction (persistent_kvstore.go)."""
    return b"val:" + base64.b64encode(pubkey) + b"!%d" % power


def block_txs(seed: int, height: int, traffic: dict) -> List[bytes]:
    """The key=value transactions of one block, `tx_bytes` long each.  Keys
    are unique over the chain, so every write can be read back."""
    n, size = traffic["txs_per_block"], traffic["tx_bytes"]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, height, 1])
    txs = []
    for i in range(n):
        key = b"k%d.%d" % (height, i)
        pad = size - len(key) - 1
        txs.append(key + b"=" + rng.bytes((pad + 1) // 2).hex().encode()[:pad])
    return txs


def tx_key_value(tx: bytes) -> Tuple[bytes, bytes]:
    key, value = tx.split(b"=", 1)
    return key, value


def draw_votes(seed: int, height: int, set_powers: np.ndarray, absent_share: float):
    """Which validators' precommits the commit for `height` holds, and
    their timestamps.  Redrawn (from the same stream) in the rare case that
    the absent ones hold a third of the power."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, height, 2])
    total = int(set_powers.sum())
    while True:
        present = rng.random(len(set_powers)) >= absent_share
        if int(set_powers[present].sum()) * 3 > total * 2 + 3:
            break
    base = GENESIS_TIME_NS + height * BLOCK_INTERVAL_NS
    stamps = base + rng.integers(0, VOTE_JITTER_NS, len(set_powers), dtype=np.int64)
    return present, stamps


def weighted_median(stamps: np.ndarray, weights: np.ndarray) -> int:
    """BFT time: the power-weighted median of the commit's timestamps."""
    order = np.lexsort((weights, stamps))
    acc = np.cumsum(weights[order])
    return int(stamps[order][np.searchsorted(acc, int(acc[-1]) // 2, side="right")])


def merkle_root(leaf_hashes: List[bytes]) -> bytes:
    """Root of Tendermint's simple Merkle tree over already-hashed leaves
    (inner = sha256(0x01 | left | right), split at the largest power of two
    below the count)."""
    n = len(leaf_hashes)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return leaf_hashes[0]
    k = 1 << ((n - 1).bit_length() - 1)
    return hashlib.sha256(
        b"\x01" + merkle_root(leaf_hashes[:k]) + merkle_root(leaf_hashes[k:])
    ).digest()


_SECS_FIELD: dict = {}  # seconds -> its encoded field; one entry per height


def _time_field(unix_ns: int) -> bytes:
    """Field 3 of a commit slot: a protobuf Timestamp {1: seconds, 2: nanos}."""
    secs, nanos = divmod(unix_ns, 1_000_000_000)
    inner = _SECS_FIELD.get(secs)
    if inner is None:
        _SECS_FIELD.clear()
        inner = _SECS_FIELD[secs] = b"\x08" + reference.uvarint(secs)
    if nanos:
        inner += b"\x10" + reference.uvarint(nanos)
    return b"\x1a" + bytes((len(inner),)) + inner


# ---------------------------------------------------------------------------
# signing workers
# ---------------------------------------------------------------------------


def _sign_worker(conn, slice_secrets: List[bytes]) -> None:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    keys = [Ed25519PrivateKey.from_private_bytes(s) for s in slice_secrets]
    while True:
        job = conn.recv()
        if job is None:
            return
        head, tail, who, stamps = job
        conn.send_bytes(b"".join(
            keys[j].sign(head + stamps[8 * k: 8 * k + 8] + tail)
            for k, j in enumerate(np.frombuffer(who, dtype="<u4"))
        ))


class Signers:
    """The signing keys of the pool (committee and standby), spread over
    worker processes: a key stays with its worker wherever its validator
    stands in the set."""

    def __init__(self, pool_secrets: List[bytes], workers: int):
        ctx = multiprocessing.get_context("spawn")
        n = len(pool_secrets)
        workers = max(1, min(workers, n // 16 or 1))
        bounds = [n * w // workers for w in range(workers + 1)]
        self.slices = list(zip(bounds[:-1], bounds[1:]))
        self.worker_of = np.repeat(np.arange(workers), np.diff(bounds))
        self.procs, self.conns = [], []
        for a, b in self.slices:
            parent, child = ctx.Pipe()
            proc = ctx.Process(target=_sign_worker, args=(child, pool_secrets[a:b]), daemon=True)
            proc.start()
            child.close()
            self.procs.append(proc)
            self.conns.append(parent)

    def sign(self, head: bytes, tail: bytes, members: np.ndarray, present: np.ndarray,
             stamps: np.ndarray) -> bytes:
        """Signatures of the present validators, in set order, 64 bytes
        each; `members` is each set position's place in the pool."""
        who, when = members[present], stamps[present].astype("<i8")
        worker = self.worker_of[who]
        theirs = [np.flatnonzero(worker == w) for w in range(len(self.conns))]
        for (a, _), conn, mine in zip(self.slices, self.conns, theirs):
            conn.send((head, tail, (who[mine] - a).astype("<u4").tobytes(), when[mine].tobytes()))
        sigs = np.empty((len(who), 64), dtype=np.uint8)
        for conn, mine in zip(self.conns, theirs):
            sigs[mine] = np.frombuffer(conn.recv_bytes(), dtype=np.uint8).reshape(-1, 64)
        return sigs.tobytes()

    def close(self) -> None:
        for conn in self.conns:
            try:
                conn.send(None)
            except OSError:  # the worker is gone already
                pass
            conn.close()
        for proc in self.procs:
            proc.join(10)
            if proc.is_alive():
                proc.kill()
                proc.join()


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------


def generate(config: dict, traffic: dict, seed: int, heights: int, out_dir: str,
             workers: Optional[int] = None) -> dict:
    """Write blocks.bin and meta.json for `heights` blocks; returns meta."""
    from tendermint_tpu.crypto.keys import Ed25519PubKey
    from tendermint_tpu.types import (
        Block, BlockID, Commit, CommitSig, ConsensusParams, Header, Validator, ValidatorSet,
    )
    from tendermint_tpu.types.block import BLOCK_ID_FLAG_COMMIT
    from tendermint_tpu.types.params import BLOCK_PART_SIZE_BYTES
    from tendermint_tpu.types.part_set import PartSet
    from tendermint_tpu.types.tx import ABCIResult, results_hash
    from tendermint_tpu.version import BLOCK_PROTOCOL

    t0 = time.monotonic()
    cid = chain_id(config["name"], seed)
    set_secrets, pubs, pows = committee(seed, config)
    vset = ValidatorSet([Validator.new(Ed25519PubKey(p), w) for p, w in zip(pubs, pows)])
    if [v.pub_key.bytes() for v in vset.validators] != pubs:
        raise RuntimeError("the program orders the validator set otherwise than by address")
    spare_secrets, spare_pubs = standby(seed, config)
    pool_pubs = pubs + spare_pubs
    pool_place = {p: i for i, p in enumerate(pool_pubs)}
    pool_addresses = [Ed25519PubKey(p).address() for p in pool_pubs]
    history, updates = reference.validator_sets(
        set_changes(config), seed, heights, list(zip(pubs, pows)), spare_pubs
    )
    cons_hash = ConsensusParams().hash()
    if workers is None:
        workers = max(1, min(8, (os.cpu_count() or 2) - 2))
    signers = Signers(set_secrets + spare_secrets, workers)

    # A set's hash is a Merkle root over one encoded validator per leaf; a
    # change moves a few leaves, so the leaves are kept and the root folded
    # here, over the program's own encoding of a validator.
    leaf_of, root_of = {}, {}

    def set_root(index: int) -> bytes:
        if index not in root_of:
            leaves = []
            for member in zip(*history.sets[index]):
                if member not in leaf_of:
                    pub, power = member
                    leaf_of[member] = hashlib.sha256(
                        b"\x00" + Validator.new(Ed25519PubKey(pub), power).bytes()
                    ).digest()
                leaves.append(leaf_of[member])
            root_of[index] = merkle_root(leaves)
        return root_of[index]

    if set_root(0) != vset.hash():
        raise RuntimeError("the program hashes a validator set otherwise than this generator")

    # A commit's hash is a Merkle root over one encoded slot per validator.
    # Flag and address never change, so they are encoded once, by the
    # program's own encoder; time and signature are spliced in per height.
    absent_leaf = hashlib.sha256(b"\x00" + CommitSig.absent().encode()).digest()
    probe = CommitSig.for_block(bytes(64), pool_addresses[0], GENESIS_TIME_NS + 7).encode()
    # (an empty signature and a zero time leave just the 2-byte empty time field behind)
    pool_heads = [b"\x00" + CommitSig.for_block(b"", a, 0).encode()[:-2] for a in pool_addresses]
    if pool_heads[0][1:] + _time_field(GENESIS_TIME_NS + 7) + b"\x22\x40" + bytes(64) != probe:
        raise RuntimeError("the program encodes a commit's slots otherwise than this generator")

    os.makedirs(out_dir, exist_ok=True)
    offsets, hashes, parts = [0], [], []
    n_txs = traffic["txs_per_block"]
    results = {}  # by how many transactions the block before delivered, all accepted
    last_id, last_commit = BlockID(), Commit(0, 0, BlockID(), [])
    last_commit_hash = merkle_root([])
    time_ns = GENESIS_TIME_NS
    held = -1  # which of history's sets the per-set values below belong to
    try:
        with open(os.path.join(out_dir, "blocks.bin"), "wb") as out:
            for h in range(1, heights + 1):
                if history.index(h) != held:
                    held = history.index(h)
                    set_pubs, set_pows = history.sets[held]
                    members = np.asarray([pool_place[p] for p in set_pubs])
                    set_powers = np.asarray(set_pows, dtype=np.int64)
                    addresses = [pool_addresses[i] for i in members]
                    slot_heads = [pool_heads[i] for i in members]
                delivered = n_txs + len(updates.get(h - 1, ()))
                if delivered not in results:
                    results[delivered] = results_hash([ABCIResult(0, b"")] * delivered)
                header = Header(
                    version_block=BLOCK_PROTOCOL, version_app=0, chain_id=cid, height=h,
                    time_ns=time_ns, last_block_id=last_id,
                    validators_hash=set_root(held),
                    next_validators_hash=set_root(history.index(h + 1)),
                    consensus_hash=cons_hash,
                    app_hash=b"" if h == 1 else reference.kvstore_app_hash(n_txs * (h - 1), h - 1),
                    last_results_hash=b"" if h == 1 else results[delivered],
                    last_commit_hash=last_commit_hash,
                    proposer_address=addresses[h % len(addresses)],
                )
                txs = block_txs(seed, h, traffic) + [val_tx(*u) for u in updates.get(h, ())]
                block = Block(header, txs, last_commit=last_commit)
                block.fill_header()
                raw = block.serialize()
                psh = PartSet.from_data(raw, BLOCK_PART_SIZE_BYTES).header()
                out.write(raw)
                offsets.append(offsets[-1] + len(raw))
                hashes.append(block.hash().hex())
                parts.append([psh.total, psh.hash.hex()])

                present, stamps = draw_votes(seed, h, set_powers, config["absent_share"])
                head, tail = reference.vote_sign_parts(
                    cid, h, 0, block.hash(), psh.total, psh.hash
                )
                sigs = signers.sign(head, tail, members, present, stamps)
                slots, leaves, k = [], [], 0
                sha256 = hashlib.sha256
                for i, (here, ts) in enumerate(zip(present.tolist(), stamps.tolist())):
                    if here:
                        sig = sigs[64 * k: 64 * k + 64]
                        slots.append(CommitSig(BLOCK_ID_FLAG_COMMIT, addresses[i], ts, sig))
                        leaves.append(sha256(
                            slot_heads[i] + _time_field(ts) + b"\x22\x40" + sig
                        ).digest())
                        k += 1
                    else:
                        slots.append(CommitSig.absent())
                        leaves.append(absent_leaf)
                last_id = BlockID(block.hash(), psh)
                last_commit = Commit(h, 0, last_id, slots)
                last_commit_hash = merkle_root(leaves)
                time_ns = weighted_median(stamps[present], set_powers[present])
    finally:
        signers.close()
    meta = {
        "format": FORMAT, "chain_id": cid, "config": config["name"], "seed": seed,
        "heights": heights, "genesis_time_ns": GENESIS_TIME_NS,
        "txs_per_block": n_txs, "offsets": offsets, "hashes": hashes, "parts": parts,
        "generate_s": round(time.monotonic() - t0, 3),
    }
    if updates:  # what the reference needs of a set that changes
        meta["set_updates"] = {
            str(h): [[pub.hex(), power] for pub, power in ups] for h, ups in updates.items()
        }
        meta["membership_heights"] = history.membership_heights
    with open(os.path.join(out_dir, "meta.json"), "w") as f:
        json.dump(meta, f)
    return meta


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------


def cache_key(config: dict, traffic: dict, seed: int, heights: int) -> str:
    """Names a generated chain by everything its bytes depend on."""
    body = json.dumps([FORMAT, config, traffic], sort_keys=True).encode()
    digest = hashlib.sha256(body).hexdigest()[:10]
    return f"{config['name']}.{traffic['name']}.s{seed}.h{heights}.{digest}"


def load_meta(chain_dir: str) -> Optional[dict]:
    """The meta of a finished chain directory, or None."""
    try:
        with open(os.path.join(chain_dir, "meta.json")) as f:
            meta = json.load(f)
    except (OSError, ValueError):
        return None
    size = os.path.getsize(os.path.join(chain_dir, "blocks.bin"))
    return meta if meta.get("format") == FORMAT and size == meta["offsets"][-1] else None


def prune_cache(cache_dir: str, keep_bytes: int) -> None:
    """Delete the chains used longest ago until the cache holds at most
    keep_bytes (the host keeps every block once written; reuse beats
    regrowth)."""
    entries = []
    for name in os.listdir(cache_dir):
        path = os.path.join(cache_dir, name)
        if os.path.isdir(path):
            size = sum(os.path.getsize(os.path.join(path, f)) for f in os.listdir(path))
            entries.append((os.path.getmtime(path), size, path))
    entries.sort(reverse=True)
    total = 0
    for _, size, path in entries:
        total += size
        if total > keep_bytes:
            shutil.rmtree(path, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="generate one seeded chain")
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--heights", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    tmp = f"{args.out}.partial"
    meta = generate(config, traffic, args.seed, args.heights, tmp)
    os.replace(tmp, args.out)
    print(f"[chain] {meta['heights']} heights, {meta['offsets'][-1]} bytes, "
          f"{meta['generate_s']} s", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
