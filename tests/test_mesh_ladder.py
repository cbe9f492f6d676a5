"""The ladder under the engine's mesh, on four virtual CPU devices at a
small tile.  Every case's verdicts are held to serial host verification of
the same seeded keys and signatures, and to the engine without a mesh on
the same batch.

The ladder in interpret mode costs the CPU's XLA 200 s of compile for every
shape (sandbox CPU, PR 26), so the cases that count run a stand-in with the
ladder's contract — rows in whole tiles, or it raises — and XLA Straus's
arithmetic; they exercise what the mesh adds: buckets, routing, shard_map's
specs, the transfers, the span's fields.  One `slow` case runs the real
ladder under the Pallas interpreter, and tests/test_tpu_aot_compile.py has
Mosaic compile it under shard_map for the four chips at the real size.
"""

import numpy as np
import pytest

from tendermint_tpu.crypto import batch as batch_hook
from tendermint_tpu.crypto import batch_verifier as bv
from tendermint_tpu.crypto.keys import Ed25519PrivKey
from tendermint_tpu.libs.tracing import FlightRecorder

TILE = 8
SHARDS = 4
N_KEYS = 12
BAD_KEY = 5  # a table row whose pubkey does not decompress


@pytest.fixture(scope="module")
def signed():
    keys = [Ed25519PrivKey.from_secret(b"mesh-ladder-%d" % i) for i in range(N_KEYS)]
    pubkeys = [k.pub_key().bytes() for k in keys]
    pubkeys[BAD_KEY] = b"\x02" + bytes(31)  # y = 2 is on no curve point
    assert bv._neg_a_limbs(pubkeys[BAD_KEY]) is None
    msgs = [b"vote-%d" % i for i in range(N_KEYS)]
    return pubkeys, msgs, [k.sign(m) for k, m in zip(keys, msgs)]


def _mesh4():
    import jax
    from jax.sharding import Mesh

    devs = jax.devices("cpu")
    if len(devs) < SHARDS:
        pytest.skip("needs 4 virtual CPU devices (conftest XLA_FLAGS)")
    return Mesh(np.array(devs[:SHARDS]), ("batch",))


_stand_ins = {}


def _tiled_stand_in(tile: int, interpret: bool = False):
    """In `_shared_pallas_fn`'s place: process-wide as that is, and as strict
    about its rows as `verify_prepared_pallas`."""
    if tile not in _stand_ins:
        import jax

        from tendermint_tpu.ops import ed25519_kernel

        def verify_prepared_tiled(neg_a, h_digits, s_digits, r_y_raw, r_sign):
            assert neg_a.shape[0] and neg_a.shape[0] % tile == 0, (neg_a.shape, tile)
            return ed25519_kernel.verify_prepared(neg_a, h_digits, s_digits, r_y_raw, r_sign)

        _stand_ins[tile] = jax.jit(verify_prepared_tiled)
    return _stand_ins[tile]


@pytest.fixture(scope="module")
def engines(signed):
    """(mesh table, one-device table, the mesh engine's recorder): engines
    that take themselves for ladder engines, tile 8, chunks of two tiles a
    chip (the shapes the other cases compile)."""
    mesh = _mesh4()
    patch = pytest.MonkeyPatch()
    patch.setattr(bv, "_PALLAS_TILE", TILE)
    patch.setattr(bv, "_CHUNK", 2 * TILE)
    patch.setattr(bv, "_shared_pallas_fn", _tiled_stand_in)
    rec = FlightRecorder(size=256)
    tables = []
    for m in (mesh, None):
        engine = bv.BatchVerifier(mesh=m, recorder=rec if m is not None else None)
        engine._pallas = True
        tables.append(bv.PubkeyTable(signed[0], engine))
    yield tables[0], tables[1], rec
    patch.undo()


def _flip(sig: bytes, at: int = 3) -> bytes:
    return sig[:at] + bytes([sig[at] ^ 1]) + sig[at + 1:]


def _batch(signed, n, tampered=(), idx_at=None):
    """n signatures over the good keys in turn; rows in `tampered` get a
    flipped bit; `idx_at` maps a row to another table index."""
    pubkeys, msgs, sigs = signed
    good = [i for i in range(N_KEYS) if i != BAD_KEY]
    idxs = [good[i % len(good)] for i in range(n)]
    ms, ss = [msgs[i] for i in idxs], [sigs[i] for i in idxs]
    for row in tampered:
        ss[row] = _flip(ss[row])
    for row, idx in (idx_at or {}).items():
        idxs[row] = idx
    return idxs, ms, ss


# (n, tampered rows, rows given another index, chunked, want: path, bucket, shards, shard_n)
CASES = {
    "divisible": (64, (), None, False, ("indexed", 64, 4, [16, 16, 16, 16])),
    "not_divisible": (45, (44,), None, False, ("indexed", 64, 4, [16, 16, 13, 0])),
    "first_and_last_row_of_every_shard": (
        64, (0, 15, 16, 31, 32, 47, 48, 63), None, False, ("indexed", 64, 4, [16] * 4)),
    "out_of_range_and_invalid_row_index": (
        40, (7,), {3: 999, 21: BAD_KEY, 39: -1}, False, ("indexed", 64, 4, [16, 16, 8, 0])),
    "under_shards_x_tile": (10, (9,), {2: BAD_KEY}, False, ("indexed", 16, 1, [10])),
    # three chunks of two tiles a chip; every other case is monolithic
    "chunked": (130, (0, 63, 64, 129), {100: 999}, True, ("chunked", 64, 4, [34, 32, 32, 32])),
}


@pytest.mark.parametrize("case", list(CASES))
def test_sharded_ladder_equals_serial_host_verification(engines, signed, case):
    n, tampered, idx_at, chunked, (path, bucket, shards, shard_n) = CASES[case]
    mesh_tab, solo_tab, rec = engines
    idxs, ms, ss = _batch(signed, n, tampered, idx_at)
    # serial host verification; a row with no valid table index is invalid
    pubkeys = signed[0]
    keyed = [pubkeys[i] if 0 <= i < N_KEYS else b"" for i in idxs]
    want = [bool(ok) for ok in batch_hook.host_batch_verify(keyed, ms, ss)]
    bad = set(tampered) | set(idx_at or {})
    assert want == [row not in bad for row in range(n)]

    mesh_tab.chunked_single_shot = solo_tab.chunked_single_shot = chunked
    seq = rec.snapshot()["next_seq"]
    got = [bool(ok) for ok in mesh_tab.verify_indexed(idxs, ms, ss)]
    assert got == want
    # without a mesh the same batch gives the same verdicts
    assert [bool(ok) for ok in solo_tab.verify_indexed(idxs, ms, ss)] == want

    (ev,) = rec.events(since=seq, kinds=["verify.dispatch"])
    assert (ev["path"], ev["bucket"], ev["shards"], ev["shard_n"]) == (
        path, bucket, shards, shard_n)
    assert ev["kernel"] == "ladder" and sum(ev["shard_n"]) == ev["n"] == n
    # routed to one device of the mesh: the event names it, and nothing is put
    assert ("device" in ev) == (shards == 1)
    assert ("put_ms" in ev) == (shards > 1)
    if shards > 1:
        assert 0 < ev["put_ms"] < ev["launch_ms"]


def test_flat_path_routes_by_bucket_too(engines, signed):
    """The flat dispatch (vote ingress, a set with no table yet): a bucket
    of a tile per chip or more is split over the mesh, a smaller one runs on
    one device; verdicts equal serial verification on both."""
    mesh_tab, _, rec = engines
    engine = mesh_tab.verifier
    for n, shards in ((40, SHARDS), (5, 1)):
        idxs, ms, ss = _batch(signed, n, tampered=(n - 1,))
        pubkeys = [signed[0][i] for i in idxs]
        seq = rec.snapshot()["next_seq"]
        assert engine.verify(pubkeys, ms, ss) == batch_hook.host_batch_verify(pubkeys, ms, ss)
        (ev,) = rec.events(since=seq, kinds=["verify.dispatch"])
        assert (ev["path"], ev["shards"], ev["kernel"]) == ("device", shards, "ladder")


def test_buckets_give_every_shard_whole_tiles(monkeypatch):
    """On a four-chip host (tile 512): a hub commit stays in one chip's
    bucket, a 10k commit's rows split into whole tiles, and a chunk is a
    per-chip quantity."""

    class FourChips:
        shape = {"batch": 4}

    monkeypatch.setattr(bv, "_PALLAS_TILE", 512)
    monkeypatch.setattr(bv, "_CHUNK", 2048)
    engine = bv.BatchVerifier(mesh=FourChips())
    engine._pallas = True
    assert engine.shards == 4
    for n, bucket, shards in (
        (166, 512, 1), (1024, 1024, 1), (1025, 2048, 4), (2048, 2048, 4), (2049, 4096, 4),
        (9500, 10240, 4), (10000, 10240, 4),
    ):
        assert (engine._bucket(n), engine._shards_for(engine._bucket(n))) == (bucket, shards), n
        assert bucket % (shards * 512) == 0
    assert engine.effective_chunk() == 4 * 2048
    assert engine.shard_fill(9500, 10240, 4) == [2560, 2560, 2560, 1820]
    # one chip, and the XLA kernel on a mesh, keep the buckets they had
    solo = bv.BatchVerifier()
    solo._pallas = True
    assert [solo._bucket(n) for n in (1, 166, 513, 2049, 9500)] == [512, 512, 1024, 3072, 10240]
    assert solo.effective_chunk() == 2048
    straus = bv.BatchVerifier(mesh=FourChips())
    straus._pallas = False
    assert [straus._bucket(n) for n in (3, 17, 9500)] == [16, 32, 10240]
    assert straus._shards_for(16) == 4


def test_a_fresh_one_chip_table_dispatches_the_ladder_and_nothing_else(engines, signed):
    """On one chip a table's first `verify_indexed` is one ladder dispatch:
    no other kernel is built, timed or reported before it.  (`engines` is
    asked for its stand-in for the ladder, not for its tables.)"""
    rec = FlightRecorder(size=64)
    engine = bv.BatchVerifier(recorder=rec)
    engine._pallas = True
    table = bv.PubkeyTable(signed[0], engine)
    idxs, ms, ss = _batch(signed, 20, tampered=(3, 11))
    want = batch_hook.host_batch_verify([signed[0][i] for i in idxs], ms, ss)
    assert [bool(ok) for ok in table.verify_indexed(idxs, ms, ss)] == [bool(ok) for ok in want]
    events = [e for e in rec.events() if e["kind"].startswith("verify.")]
    assert [e["kind"] for e in events] == ["verify.dispatch"]
    assert events[0]["path"] == "indexed" and events[0]["kernel"] == "ladder"
    assert events[0].get("ok", True) is True


@pytest.mark.slow
def test_the_real_ladder_under_the_interpreter_on_the_mesh(signed, monkeypatch):
    """`verify_prepared_pallas(interpret=True)` per shard under shard_map:
    the verdicts of a batch with a bad row in every shard equal serial host
    verification.  Minutes of compile on a CPU."""
    monkeypatch.setattr(bv, "_PALLAS_TILE", TILE)
    engine = bv.BatchVerifier(mesh=_mesh4())
    engine._pallas, engine._interpret = True, True
    table = bv.PubkeyTable(signed[0], engine)
    idxs, ms, ss = _batch(signed, 30, tampered=(0, 7, 8, 15, 16, 23, 24, 29))
    want = batch_hook.host_batch_verify([signed[0][i] for i in idxs], ms, ss)
    assert [bool(ok) for ok in table.verify_indexed(idxs, ms, ss)] == [bool(ok) for ok in want]
