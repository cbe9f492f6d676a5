"""The Merkle root as one fold over leaf hashes (crypto/merkle.py).

The recursive definition (crypto/merkle/simple_tree.go:9, with its slices)
lives here, as the reference the module's fold is held to; the digests
pinned below were taken from the module before it folded by index.
"""

import hashlib

import pytest

from tendermint_tpu.crypto import merkle
from tendermint_tpu.types.part_set import PartSet
from tendermint_tpu.types.tx import ABCIResult, results_hash, tx_proof, txs_hash


def _leaf(item: bytes) -> bytes:
    return hashlib.sha256(b"\x00" + item).digest()


def root_by_definition(items) -> bytes:
    """SimpleHashFromByteSlices as simple_tree.go writes it."""
    n = len(items)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return _leaf(items[0])
    k = 1
    while k * 2 < n:
        k *= 2
    return hashlib.sha256(
        b"\x01" + root_by_definition(items[:k]) + root_by_definition(items[k:])
    ).digest()


def _items(n: int):
    return [hashlib.sha256(n.to_bytes(4, "big") + i.to_bytes(4, "big")).digest()[: i % 33]
            for i in range(n)]


@pytest.mark.parametrize("n", [*range(71), 1000, 9500])
def test_the_fold_equals_the_recursive_definition(n):
    items = _items(n)
    want = root_by_definition(items)
    assert merkle.hash_from_leaf_hashes([_leaf(it) for it in items]) == want
    assert merkle.hash_from_byte_slices(items) == want


@pytest.mark.parametrize("items, root", [
    ([], "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"),
    ([b""], "6e340b9cffb37a989ca544e6bb780a2c78901d3fb33738768511a30617afa01d"),
    ([b"a", b"b", b"c", b"d", b"e"],
     "fe14a5426fbd70c0fa73f52342afed0da0bd23c4838662ccf6b88a3070ead97b"),
    ([bytes([i]) * (i % 7) for i in range(100)],
     "8f3839361bd739ba8367c45310c8b9f92acbed6bd9b0fdd2a1014ed62ebe8188"),
    ([hashlib.sha256(bytes([i & 255, i >> 8])).digest() for i in range(1000)],
     "38e69bcb6e6a49c9d855580ed78e58237bcfc9b03cedae191d75a9a2d2e74084"),
], ids=["none", "one-empty", "five", "hundred", "thousand"])
def test_roots_taken_before_the_fold_still_stand(items, root):
    assert merkle.hash_from_byte_slices(items).hex() == root


def test_the_fold_copies_no_slice_and_leaves_its_input_alone():
    class NoSlices(list):
        def __getitem__(self, i):
            assert not isinstance(i, slice), "the fold took a slice"
            return list.__getitem__(self, i)

    leaves = NoSlices(_leaf(it) for it in _items(300))
    before = list(leaves)
    assert merkle.hash_from_leaf_hashes(leaves) == root_by_definition(_items(300))
    assert list(leaves) == before


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 13, 100, 257])
def test_proofs_from_the_untouched_builders_verify_against_the_new_root(n):
    items = _items(n)
    root = merkle.hash_from_byte_slices(items)
    built_root, proofs = merkle.proofs_from_byte_slices(items)
    assert built_root == root
    for i, proof in enumerate(proofs):
        assert proof.verify(root, items[i])
        assert proof.compute_root() == root
        assert not proof.verify(root, items[i] + b"x")


def test_the_roots_other_callers_take_are_the_same_tree():
    txs = [b"k%d=v" % i for i in range(37)]
    root = txs_hash(txs)
    assert root == root_by_definition([hashlib.sha256(t).digest() for t in txs])
    assert tx_proof(txs, 5).proof.verify(root, hashlib.sha256(txs[5]).digest())
    results = [ABCIResult(i % 3, b"r" * i) for i in range(11)]
    assert results_hash(results) == root_by_definition([r.bytes() for r in results])
    # the part-set root comes from the proof builders, another walk of the same tree
    parts = PartSet.from_data(b"\x07" * 1000, 64)
    chunks = [parts.get_part(i).bytes for i in range(parts.total)]
    assert parts.hash() == merkle.hash_from_byte_slices(chunks) == root_by_definition(chunks)
