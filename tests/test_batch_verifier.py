"""Differential tests: JAX batched ed25519 vs host C backend and the
pure-Python oracle (crypto/ed25519_math.py).

Coverage model: the reference's crypto tests + golden edge cases
(crypto/ed25519/ed25519_test.go, x/crypto semantics: non-canonical S,
corrupted R, wrong pubkey, truncated sigs).
"""

import numpy as np
import pytest

from tendermint_tpu.crypto import batch as batch_hook
from tendermint_tpu.crypto import ed25519_math as em
from tendermint_tpu.crypto.batch_verifier import (
    AsyncBatchVerifier,
    BatchVerifier,
    PubkeyTable,
    prepare_batch,
)
from tendermint_tpu.crypto.keys import Ed25519PrivKey


@pytest.fixture(scope="module")
def verifier():
    return BatchVerifier()


def make_sigs(n, msg_fn=lambda i: f"message-{i}".encode()):
    keys = [Ed25519PrivKey.from_secret(f"key-{i}".encode()) for i in range(n)]
    pubkeys = [k.pub_key().bytes() for k in keys]
    msgs = [msg_fn(i) for i in range(n)]
    sigs = [k.sign(m) for k, m in zip(keys, msgs)]
    return pubkeys, msgs, sigs


# ---------------------------------------------------------------------------
# field arithmetic vs python ints
# ---------------------------------------------------------------------------


class TestFieldOps:
    def test_mul_matches_python(self):
        from tendermint_tpu.ops import fe

        rng = np.random.default_rng(0)
        for _ in range(20):
            a = int(rng.integers(0, 2**63)) * int(rng.integers(0, 2**63)) % em.P
            b = int(rng.integers(0, 2**63)) ** 4 % em.P
            got = fe.to_int(fe.canonical(fe.mul(fe.from_int(a), fe.from_int(b))))
            assert got == a * b % em.P

    def test_sub_and_canonical(self):
        from tendermint_tpu.ops import fe

        a, b = 5, em.P - 3
        got = fe.to_int(fe.canonical(fe.sub(fe.from_int(a), fe.from_int(b))))
        assert got == (a - b) % em.P

    def test_invert(self):
        from tendermint_tpu.ops import fe

        for v in (2, 12345678901234567890, em.P - 2):
            inv = fe.to_int(fe.canonical(fe.invert(fe.from_int(v))))
            assert v * inv % em.P == 1

    def test_point_add_matches_oracle(self):
        import jax.numpy as jnp

        from tendermint_tpu.ops import ed25519_kernel as ek
        from tendermint_tpu.ops import fe

        def to_ext(pt):  # batch of 1 lane
            return tuple(jnp.asarray(fe.from_int(c)) for c in pt)

        def from_ext(p):
            return tuple(fe.to_int(fe.canonical(c)) for c in p)

        b2 = em.point_double(em.BASE)
        b3 = em.point_add(b2, em.BASE)
        got = from_ext(ek.point_add(to_ext(b2), to_ext(em.BASE)))
        assert em.to_affine(got[:2] + got[2:]) == em.to_affine(b3)
        got_d = from_ext(ek.point_double(to_ext(em.BASE)))
        assert em.to_affine(got_d[:2] + got_d[2:]) == em.to_affine(b2)

    def test_field_torture_int32_bounds(self):
        """Randomized + adversarial values (all-ones limbs, p-1, 2p-ish)
        exercising the int32 magnitude analysis in ops/fe.py."""
        import jax.numpy as jnp

        from tendermint_tpu.ops import fe

        rng = np.random.default_rng(7)
        specials = [0, 1, 19, em.P - 1, em.P - 19, 2**255 - 20, 2**252 + 27742317777372353535851937790883648493]
        vals = specials + [int(rng.integers(0, 2**63)) ** 4 % em.P for _ in range(9)]

        def lanes(ints):  # [20, n] with one lane per value
            arr = np.zeros((fe.N_LIMBS, len(ints)), np.int32)
            for lane, v in enumerate(ints):
                arr[:, lane] = fe.from_int(v)[:, 0]
            return jnp.asarray(arr)

        def to_ints(arr):
            arr = np.asarray(arr)
            return [fe.to_int(arr, lane) for lane in range(arr.shape[1])]

        a = lanes(vals)
        b = lanes(list(reversed(vals)))
        got_mul = to_ints(fe.canonical(fe.mul(a, b)))
        got_sq = to_ints(fe.canonical(fe.square(a)))
        got_add = to_ints(fe.canonical(fe.add(a, b)))
        got_sub = to_ints(fe.canonical(fe.sub(a, b)))
        rv = list(reversed(vals))
        for i, (x, y) in enumerate(zip(vals, rv)):
            assert got_mul[i] == x * y % em.P
            assert got_sq[i] == x * x % em.P
            assert got_add[i] == (x + y) % em.P
            assert got_sub[i] == (x - y) % em.P


# ---------------------------------------------------------------------------
# end-to-end batch verification
# ---------------------------------------------------------------------------


class TestBatchVerifier:
    def test_valid_batch(self, verifier):
        pubkeys, msgs, sigs = make_sigs(5)
        assert verifier.verify(pubkeys, msgs, sigs) == [True] * 5

    def test_mixed_batch(self, verifier):
        pubkeys, msgs, sigs = make_sigs(8)
        bad = list(sigs)
        bad[2] = bad[2][:32] + bytes(32)  # S=0 -> wrong
        bad[5] = bytes(64)  # garbage
        expected = [True, True, False, True, True, False, True, True]
        assert verifier.verify(pubkeys, msgs, bad) == expected

    def test_wrong_message(self, verifier):
        pubkeys, msgs, sigs = make_sigs(3)
        msgs[1] = b"tampered"
        assert verifier.verify(pubkeys, msgs, sigs) == [True, False, True]

    def test_wrong_pubkey(self, verifier):
        pubkeys, msgs, sigs = make_sigs(3)
        pubkeys[0], pubkeys[2] = pubkeys[2], pubkeys[0]
        assert verifier.verify(pubkeys, msgs, sigs) == [False, True, False]

    def test_noncanonical_s_rejected(self, verifier):
        pubkeys, msgs, sigs = make_sigs(1)
        s = int.from_bytes(sigs[0][32:], "little")
        bumped = (s + em.L).to_bytes(32, "little")
        assert verifier.verify(pubkeys, msgs, [sigs[0][:32] + bumped]) == [False]

    def test_corrupted_r_rejected(self, verifier):
        pubkeys, msgs, sigs = make_sigs(1)
        r = bytearray(sigs[0][:32])
        r[0] ^= 1
        assert verifier.verify(pubkeys, msgs, [bytes(r) + sigs[0][32:]]) == [False]

    def test_truncated_sig_and_bad_pubkey(self, verifier):
        pubkeys, msgs, sigs = make_sigs(2)
        assert verifier.verify(pubkeys, msgs, [sigs[0][:63], sigs[1]]) == [False, True]
        assert verifier.verify([b"\xff" * 32, pubkeys[1]], msgs, sigs) == [False, True]

    def test_differential_vs_oracle_random_corruptions(self, verifier):
        rng = np.random.default_rng(42)
        pubkeys, msgs, sigs = make_sigs(32)
        mutated = []
        for i, sig in enumerate(sigs):
            if rng.random() < 0.5:
                b = bytearray(sig)
                b[rng.integers(0, 64)] ^= 1 << rng.integers(0, 8)
                mutated.append(bytes(b))
            else:
                mutated.append(sig)
        got = verifier.verify(pubkeys, msgs, mutated)
        want = [em.verify(pk, m, s) for pk, m, s in zip(pubkeys, msgs, mutated)]
        assert got == want

    def test_batch_padding_shapes(self, verifier):
        # different batch sizes hit the same bucket; larger sizes re-jit once
        for n in (1, 2, 15, 16, 17):
            pubkeys, msgs, sigs = make_sigs(n)
            assert verifier.verify(pubkeys, msgs, sigs) == [True] * n

    def test_empty_batch(self, verifier):
        assert verifier.verify([], [], []) == []


class TestPallasKernel:
    # Interpret-mode runs dispatch every kernel op individually on the CPU —
    # minutes per ladder pass on a small host, so these differential tests
    # are tier-2 (`-m slow`); the quick gate covers the same math through
    # the portable XLA kernel.

    @pytest.mark.slow
    def test_differential_vs_oracle_interpret(self):
        """The Pallas kernel is the default verify path on TPU backends;
        cover its exact code on CPU via the Pallas interpreter."""
        import numpy as np

        from tendermint_tpu.crypto.batch_verifier import prepare_batch
        from tendermint_tpu.ops.ed25519_pallas import verify_prepared_pallas

        rng = np.random.default_rng(11)
        pubkeys, msgs, sigs = make_sigs(8)
        mutated = []
        for sig in sigs:
            if rng.random() < 0.5:
                b = bytearray(sig)
                b[rng.integers(0, 64)] ^= 1 << rng.integers(0, 8)
                mutated.append(bytes(b))
            else:
                mutated.append(sig)
        neg_a, h, s, ry, rs, valid = prepare_batch(pubkeys, msgs, mutated)
        ok = np.asarray(
            verify_prepared_pallas(neg_a, h, s, ry, rs, tile=8, interpret=True)
        )
        got = list(np.logical_and(ok, valid))
        want = [em.verify(pk, m, sg) for pk, m, sg in zip(pubkeys, msgs, mutated)]
        assert got == want

    @pytest.mark.slow
    def test_multi_tile_grid_interpret(self):
        """tile < batch exercises the BlockSpec index maps with grid > 1 —
        a multi-tile indexing bug must surface off-TPU, not only on real
        hardware."""
        import numpy as np

        from tendermint_tpu.crypto.batch_verifier import prepare_batch
        from tendermint_tpu.ops.ed25519_pallas import verify_prepared_pallas

        pubkeys, msgs, sigs = make_sigs(8)
        bad = bytearray(sigs[5])
        bad[3] ^= 0x40  # corrupt one sig so tiles differ in outcome
        sigs = sigs[:5] + [bytes(bad)] + sigs[6:]
        neg_a, h, s, ry, rs, valid = prepare_batch(pubkeys, msgs, sigs)
        ok = np.asarray(
            verify_prepared_pallas(neg_a, h, s, ry, rs, tile=4, interpret=True)
        )
        got = list(np.logical_and(ok, valid))
        want = [em.verify(pk, m, sg) for pk, m, sg in zip(pubkeys, msgs, sigs)]
        assert got == want
        assert got[5] is np.False_ or got[5] == False  # noqa: E712


class TestPubkeyTable:
    def test_verify_indexed(self, verifier):
        pubkeys, msgs, sigs = make_sigs(6)
        table = PubkeyTable(pubkeys, verifier)
        idxs = [3, 1, 5, 0]
        got = table.verify_indexed(
            idxs, [msgs[i] for i in idxs], [sigs[i] for i in idxs]
        )
        assert got == [True] * 4
        # wrong index -> wrong pubkey -> False
        assert table.verify_indexed([0], [msgs[1]], [sigs[1]]) == [False]
        # out-of-range index
        assert table.verify_indexed([99], [msgs[0]], [sigs[0]]) == [False]

    def test_commit_via_hook(self, verifier):
        # ValidatorSet.verify_commit routed through the installed TPU hook
        import time

        from tendermint_tpu.types import PRECOMMIT_TYPE, ValidatorSet, Validator, MockPV, VoteSet
        from tests.test_types import CHAIN_ID, make_block_id, rand_validator_set, signed_vote

        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        vs = VoteSet(CHAIN_ID, 5, 0, PRECOMMIT_TYPE, vset)
        for pv in pvs:
            vs.add_vote(signed_vote(pv, vset, PRECOMMIT_TYPE, 5, 0, bid))
        commit = vs.make_commit()
        try:
            verifier.install()
            vset.verify_commit(CHAIN_ID, bid, 5, commit)
        finally:
            batch_hook.set_verifier(None)


class TestTableCacheCommits:
    """ValidatorSet.verify_commit through the installed indexed hook."""

    def test_table_cache_routes_verify_commit(self, verifier):
        """verify_commit uses the installed indexed hook (device-resident
        pubkey rows) and falls back cleanly when the cache declines."""
        from tendermint_tpu.crypto.batch_verifier import TableCache
        from tendermint_tpu.types import PRECOMMIT_TYPE, VoteSet
        from tests.test_types import CHAIN_ID, make_block_id, rand_validator_set, signed_vote

        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        vs = VoteSet(CHAIN_ID, 5, 0, PRECOMMIT_TYPE, vset)
        for pv in pvs:
            vs.add_vote(signed_vote(pv, vset, PRECOMMIT_TYPE, 5, 0, bid))
        commit = vs.make_commit()
        cache = TableCache(verifier)
        calls = {"n": 0}
        orig = cache.verify_indexed

        def counting(*a):
            calls["n"] += 1
            return orig(*a)

        cache.verify_indexed = counting
        try:
            batch_hook.set_indexed_verifier(cache.verify_indexed)
            vset.verify_commit(CHAIN_ID, bid, 5, commit)
            assert calls["n"] == 1
            assert vset.pubkeys_digest() in cache._tables
            # second commit at the same set reuses the cached table
            vset.verify_commit(CHAIN_ID, bid, 5, commit)
            assert len(cache._tables) == 1
        finally:
            batch_hook.set_indexed_verifier(None)

    def test_bad_sig_still_raises_through_indexed_path(self, verifier):
        from tendermint_tpu.crypto.batch_verifier import TableCache
        from tendermint_tpu.types import PRECOMMIT_TYPE, VoteSet
        from tests.test_types import CHAIN_ID, make_block_id, rand_validator_set, signed_vote

        vset, pvs = rand_validator_set(4)
        bid = make_block_id()
        vs = VoteSet(CHAIN_ID, 5, 0, PRECOMMIT_TYPE, vset)
        for pv in pvs:
            vs.add_vote(signed_vote(pv, vset, PRECOMMIT_TYPE, 5, 0, bid))
        commit = vs.make_commit()
        import dataclasses

        commit.signatures[0] = dataclasses.replace(commit.signatures[0], signature=bytes(64))
        cache = TableCache(verifier)
        try:
            batch_hook.set_indexed_verifier(cache.verify_indexed)
            with pytest.raises(ValueError, match="wrong signature"):
                vset.verify_commit(CHAIN_ID, bid, 5, commit)
        finally:
            batch_hook.set_indexed_verifier(None)


class TestAsyncBatchVerifier:
    async def test_futures_resolve(self):
        pubkeys, msgs, sigs = make_sigs(4)
        svc = AsyncBatchVerifier(BatchVerifier(), flush_interval=0.01)
        await svc.start()
        try:
            futs = [svc.verify_one(pk, m, s) for pk, m, s in zip(pubkeys, msgs, sigs)]
            bad = svc.verify_one(pubkeys[0], b"other", sigs[0])
            import asyncio

            results = await asyncio.gather(*futs, bad)
            assert results == [True, True, True, True, False]
        finally:
            await svc.stop()


class TestChunkedIndexed:
    def test_double_buffered_chunks_match(self, verifier, monkeypatch):
        """Large indexed batches split into pipelined chunks; results must
        be identical to the one-shot path, incl. padding + invalid rows."""
        from tendermint_tpu.crypto import batch_verifier as bv

        monkeypatch.setattr(bv, "_CHUNK", 32)
        pubkeys, msgs, sigs = make_sigs(12)
        chunk_verifier = BatchVerifier()
        chunk_verifier._pallas = False  # XLA kernel: any chunk shape allowed
        table = PubkeyTable(pubkeys, chunk_verifier)
        table.chunked_single_shot = True
        n = 70
        idxs = [i % 12 for i in range(n)]
        ms = [msgs[i] for i in idxs]
        ss = [sigs[i] for i in idxs]
        ss[40] = ss[40][:3] + bytes([ss[40][3] ^ 1]) + ss[40][4:]  # corrupt
        idxs[65] = 999  # out-of-range row
        expect = [True] * n
        expect[40] = False
        expect[65] = False
        assert table.verify_indexed(idxs, ms, ss) == expect


class TestWarmup:
    def test_cold_bucket_serves_host_path_then_device(self, verifier):
        """With warmup mode on, an uncompiled bucket shape must answer
        correctly (host path) immediately, and flip to the device path once
        the background compile lands — a cold node never stalls consensus."""
        import time

        pubkeys, msgs, sigs = make_sigs(3)
        bv = BatchVerifier()
        bv._warmup_mode = True  # no pre-compile: every bucket starts cold
        assert bv.verify(pubkeys, msgs, sigs) == [True, True, True]
        # a wrong signature is caught on the fallback path too
        assert bv.verify([pubkeys[0]], [b"other"], [sigs[0]]) == [False]
        deadline = time.time() + 60
        while time.time() < deadline:
            if bv._bucket(3) in bv._ready_buckets:
                break
            time.sleep(0.1)
        assert bv._bucket(3) in bv._ready_buckets
        assert bv.verify(pubkeys, msgs, sigs) == [True, True, True]

    async def test_overflow_falls_back_inline(self):
        pubkeys, msgs, sigs = make_sigs(2)
        svc = AsyncBatchVerifier(BatchVerifier(), flush_interval=0.01, max_pending=1)
        await svc.start()
        try:
            f1 = svc.verify_one(pubkeys[0], msgs[0], sigs[0])
            f2 = svc.verify_one(pubkeys[1], msgs[1], sigs[1])  # over cap: inline host
            assert f2.done() and f2.result() is True
            import asyncio

            assert await asyncio.wait_for(f1, 30) is True
        finally:
            await svc.stop()


def _mesh8():
    import jax
    from jax.sharding import Mesh

    devs = jax.devices("cpu")
    if len(devs) < 8:
        pytest.skip("needs 8 virtual CPU devices (conftest XLA_FLAGS)")
    return Mesh(np.array(devs[:8]), ("batch",))


class TestSharded:
    def test_mesh_sharded_verify(self):
        v = BatchVerifier(mesh=_mesh8())
        pubkeys, msgs, sigs = make_sigs(10)
        sigs[7] = bytes(64)
        want = [True] * 10
        want[7] = False
        assert v.verify(pubkeys, msgs, sigs) == want

    def test_sharded_indexed_differential_vs_single_device(self):
        """The sharded fused dispatch must be BIT-IDENTICAL to the
        single-device engine on a mixed valid/invalid indexed batch."""
        pubkeys, msgs, sigs = make_sigs(16)
        n = 96
        idxs = [i % 16 for i in range(n)]
        ms = [msgs[i] for i in idxs]
        ss = [sigs[i] for i in idxs]
        ss[5] = bytes(64)  # garbage
        ss[33] = ss[33][:10] + bytes([ss[33][10] ^ 0x40]) + ss[33][11:]
        ms[70] = b"forged"  # wrong message
        idxs[90] = 999  # out-of-range validator row

        mesh_tab = PubkeyTable(pubkeys, BatchVerifier(mesh=_mesh8()))
        solo_tab = PubkeyTable(pubkeys, BatchVerifier())
        got_mesh = mesh_tab.verify_indexed(idxs, ms, ss)
        got_solo = solo_tab.verify_indexed(idxs, ms, ss)
        assert got_mesh == got_solo
        expect = [True] * n
        for j in (5, 33, 70, 90):
            expect[j] = False
        assert got_mesh == expect

    def test_liar_attribution_on_every_shard(self):
        """One invalid signature placed at each shard's slice of the batch:
        the verdict vector must point at exactly those rows — a liar on
        shard k must never be blamed on a row owned by shard j."""
        pubkeys, msgs, sigs = make_sigs(16)
        n = 64  # 8 rows per shard on the 8-device mesh
        idxs = [i % 16 for i in range(n)]
        ms = [msgs[i] for i in idxs]
        ss = [sigs[i] for i in idxs]
        liars = [shard * 8 + 3 for shard in range(8)]  # one per shard
        for j in liars:
            ss[j] = bytes(64)
        expect = [i not in liars for i in range(n)]
        tab = PubkeyTable(pubkeys, BatchVerifier(mesh=_mesh8()))
        assert tab.verify_indexed(idxs, ms, ss) == expect

    def test_ragged_batches_no_verdict_leakage(self):
        """Sizes not divisible by the shard count pad up to the bucket;
        padding rows must never leak into (or flip) real verdicts."""
        pubkeys, msgs, sigs = make_sigs(16)
        tab = PubkeyTable(pubkeys, BatchVerifier(mesh=_mesh8()))
        for n in (13, 27, 67):
            idxs = [i % 16 for i in range(n)]
            ms = [msgs[i] for i in idxs]
            ss = [sigs[i] for i in idxs]
            expect = [True] * n
            ss[n - 1] = bytes(64)
            expect[n - 1] = False
            assert tab.verify_indexed(idxs, ms, ss) == expect, n

    def test_sharded_chunked_matches(self, monkeypatch):
        from tendermint_tpu.crypto import batch_verifier as bv_mod

        monkeypatch.setattr(bv_mod, "_CHUNK", 16)
        pubkeys, msgs, sigs = make_sigs(16)
        tab = PubkeyTable(pubkeys, BatchVerifier(mesh=_mesh8()))
        tab.chunked_single_shot = True
        n = 48
        idxs = [i % 16 for i in range(n)]
        ms = [msgs[i] for i in idxs]
        ss = [sigs[i] for i in idxs]
        ss[20] = bytes(64)
        expect = [True] * n
        expect[20] = False
        assert tab.verify_indexed(idxs, ms, ss) == expect

    def test_pack_expand_round_trip(self):
        """Host-side packed 32-byte scalars must expand on-device to the
        exact window digits the unpacked wire format would have carried."""
        import jax.numpy as jnp

        from tendermint_tpu.crypto.batch_verifier import _pack_digits, _scalar_rows
        from tendermint_tpu.ops import ed25519_kernel

        pubkeys, msgs, sigs = make_sigs(5)
        items = list(zip(pubkeys, msgs, sigs))
        h_digits, s_digits, _, _, _ = _scalar_rows(items)
        for digits in (h_digits, s_digits):
            packed = _pack_digits(digits)
            assert packed.shape == (len(items), 32)
            expanded = np.asarray(ed25519_kernel.expand_digits(jnp.asarray(packed)))
            np.testing.assert_array_equal(expanded, digits)


class TestResolveMesh:
    def test_off_never_shards(self):
        from tendermint_tpu.crypto.backend import resolve_mesh

        mesh, shards, reason = resolve_mesh("off", 8)
        assert mesh is None and shards == 1 and "off" in reason

    def test_auto_ignores_virtual_cpu_devices(self):
        from tendermint_tpu.crypto.backend import resolve_mesh

        mesh, shards, reason = resolve_mesh("auto", 0)
        assert mesh is None and shards == 1
        assert "virtual cpu" in reason

    def test_auto_with_explicit_device_cap_opts_in(self):
        from tendermint_tpu.crypto.backend import resolve_mesh

        mesh, shards, reason = resolve_mesh("auto", 4)
        assert mesh is not None and shards == 4

    def test_on_shards_any_platform(self):
        from tendermint_tpu.crypto.backend import resolve_mesh

        mesh, shards, reason = resolve_mesh("on", 8)
        assert mesh is not None and shards == 8
        assert "sharded over 8" in reason

    def test_probe_failure_degrades_to_single_device(self, monkeypatch):
        import jax

        from tendermint_tpu.crypto.backend import resolve_mesh

        def boom(*a, **k):
            raise RuntimeError("device plane down")

        monkeypatch.setattr(jax, "devices", boom)
        mesh, shards, reason = resolve_mesh("on", 8)
        assert mesh is None and shards == 1
        assert "mesh probe failed" in reason


class TestShardedWarmup:
    def test_no_compile_after_warmup_on_mesh(self):
        """start_warmup on a mesh engine must compile the SHARDED bucket
        executable — the first live dispatch after warmup lands must not
        trigger any new XLA compilation."""
        import time

        v = BatchVerifier(mesh=_mesh8())
        v.start_warmup()
        b = v._bucket(max(1, v.min_device_batch))
        deadline = time.time() + 120
        while time.time() < deadline and b not in v._ready_buckets:
            time.sleep(0.05)
        assert b in v._ready_buckets, "warmup compile never landed"
        fn = v._jitted()
        compiled = fn._cache_size()
        assert compiled >= 1
        pubkeys, msgs, sigs = make_sigs(3)
        assert v.verify(pubkeys, msgs, sigs) == [True, True, True]
        assert fn._cache_size() == compiled, "post-warmup dispatch recompiled"


class TestMeshConfigKnobs:
    def _cfg(self):
        from tendermint_tpu.config import Config

        return Config(home="/tmp/x")

    @pytest.mark.parametrize("field,bad,match", [
        ("mesh", "sideways", "mesh"),
        ("mesh_devices", -1, "mesh_devices"),
        ("chunk_size", -8, "chunk_size"),
        ("chunk_depth", 0, "chunk_depth"),
    ])
    def test_bad_knob_rejected(self, field, bad, match):
        cfg = self._cfg()
        setattr(cfg.tpu, field, bad)
        with pytest.raises(ValueError, match=match):
            cfg.validate_basic()

    def test_defaults_validate(self):
        cfg = self._cfg()
        cfg.validate_basic()
        assert cfg.tpu.mesh == "auto"
        assert cfg.tpu.chunk_depth == 2

    def test_a_config_file_with_the_retired_tabulated_key_still_loads(self, tmp_path, caplog):
        """`save_config` wrote `tabulated = "auto"` under [tpu] into every
        config.toml until PR 28: such a file loads and validates, silently."""
        import dataclasses
        import warnings

        from tendermint_tpu.config import load_config, save_config

        path = str(tmp_path / "config.toml")
        save_config(self._cfg(), path)
        with open(path) as fh:
            text = fh.read()
        assert "tabulated" not in text
        old = text.replace("chunk_depth = 2\n", 'chunk_depth = 2\ntabulated = "auto"\n')
        assert old != text
        with open(path, "w") as fh:
            fh.write(old)
        with warnings.catch_warnings(), caplog.at_level(0):
            warnings.simplefilter("error")
            cfg = load_config(path, home=str(tmp_path))
            cfg.validate_basic()
        assert not caplog.records
        assert not hasattr(cfg.tpu, "tabulated")
        assert cfg.tpu.chunk_depth == 2 and len(dataclasses.fields(cfg.tpu)) == 11


# ---------------------------------------------------------------------------
# fused one-pass C host prep (csrc ed25519_prep_batch)
# ---------------------------------------------------------------------------


class TestFusedHostPrep:
    """The fused C pass must be bit-identical to the numpy reference
    pipeline it replaces — same digits, limbs, sign bits and prefilter
    verdicts for every entry shape callers can produce."""

    def _mixed_items(self):
        pubkeys, msgs, sigs = make_sigs(9, msg_fn=lambda i: b"m" * (i * 37))
        items = [
            (pubkeys[0], msgs[0], sigs[0]),
            None,  # caller-marked invalid
            (pubkeys[2], msgs[2], sigs[2]),
            (pubkeys[3], msgs[3], sigs[3][:40]),  # truncated sig
            (pubkeys[4][:16], msgs[4], sigs[4]),  # bad pubkey length
            # non-canonical S (== L): prefilter must reject
            (pubkeys[5], msgs[5], sigs[5][:32] + em.L.to_bytes(32, "little")),
            (pubkeys[6], b"", sigs[6]),  # empty message (still hashed)
            (pubkeys[7], msgs[7] * 100, sigs[7]),  # multi-block SHA-512 input
            (pubkeys[8], msgs[8], sigs[8]),
        ]
        return items

    def test_differential_vs_numpy_pipeline(self, monkeypatch):
        from tendermint_tpu.crypto import batch_verifier as bv
        from tendermint_tpu.crypto import hostprep

        items = self._mixed_items()
        fused = hostprep.prep_scalar_rows(items)
        if fused is None:
            pytest.skip("no C toolchain: fused prep unavailable")
        monkeypatch.setattr(hostprep, "prep_scalar_rows", lambda _: None)
        reference = bv._scalar_rows(items)
        for got, want, name in zip(
            fused, reference, ("h_digits", "s_digits", "r_y", "r_sign", "valid")
        ):
            np.testing.assert_array_equal(got, want, err_msg=name)

    def test_fused_feeds_verifier_correctly(self, verifier):
        pubkeys, msgs, sigs = make_sigs(24)
        bad = list(sigs)
        bad[7] = bad[7][:10] + bytes([bad[7][10] ^ 0xFF]) + bad[7][11:]
        expect = [True] * 24
        expect[7] = False
        assert verifier.verify(pubkeys, msgs, bad) == expect

    def test_host_verify_batch_matches_serial(self):
        from tendermint_tpu.crypto import hostprep

        pubkeys, msgs, sigs = make_sigs(6)
        sigs = list(sigs)
        sigs[2] = bytes(64)  # garbage
        sigs[4] = sigs[4][:32] + em.L.to_bytes(32, "little")  # non-canonical S
        res = hostprep.host_verify_batch(pubkeys, msgs, sigs)
        if res is None:
            pytest.skip("no C toolchain")
        from tendermint_tpu.crypto.keys import Ed25519PubKey

        want = [Ed25519PubKey(pk).verify(m, s) for pk, m, s in zip(pubkeys, msgs, sigs)]
        assert res == want == [True, True, False, True, False, True]


# ---------------------------------------------------------------------------
# dispatch RTT probe + chunked auto-selection
# ---------------------------------------------------------------------------


class TestRTTProbe:
    def test_probe_shape_and_caching(self):
        bv_inst = BatchVerifier()
        probe = bv_inst.probe_dispatch_rtt(samples=2)
        assert set(probe) == {"dispatch_rtt_ms", "prep_ms_per_chunk", "chunked_selected"}
        assert probe["dispatch_rtt_ms"] > 0
        assert probe["prep_ms_per_chunk"] > 0
        assert bv_inst.probe_dispatch_rtt() is probe  # cached
        assert isinstance(bv_inst.chunked_auto(), bool)

    def test_auto_selection_drives_indexed_path(self, monkeypatch):
        """chunked_single_shot=None defers to the probe verdict; both
        verdicts must produce identical results on the same batch."""
        from tendermint_tpu.crypto import batch_verifier as bv

        monkeypatch.setattr(bv, "_CHUNK", 16)
        pubkeys, msgs, sigs = make_sigs(8)
        n = 40
        idxs = [i % 8 for i in range(n)]
        ms = [msgs[i] for i in idxs]
        ss = [sigs[i] for i in idxs]
        ss[11] = bytes(64)
        expect = [True] * n
        expect[11] = False
        for selected in (0.0, 1.0):
            v = BatchVerifier()
            v._pallas = False
            v.rtt_probe = {
                "dispatch_rtt_ms": 1.0,
                "prep_ms_per_chunk": 2.0,
                "chunked_selected": selected,
            }
            table = PubkeyTable(pubkeys, v)
            assert table.chunked_single_shot is None  # auto by default
            assert table.verify_indexed(idxs, ms, ss) == expect


# ---------------------------------------------------------------------------
# adaptive flush quantum
# ---------------------------------------------------------------------------


class TestAdaptiveFlush:
    def test_quiet_window_policy(self):
        svc = AsyncBatchVerifier(
            BatchVerifier(), flush_interval=0.002, flush_min=0.0002
        )
        # no history: floor (flush as soon as the first window is quiet)
        assert svc._quiet_window() == svc.flush_min
        # sparse regime (next vote far beyond the deadline): floor
        svc._ewma_gap = 0.1
        assert svc._quiet_window() == svc.flush_min
        # trickle regime (more votes imminent): wait ~4 gaps for them
        svc._ewma_gap = 0.0003
        assert svc._quiet_window() == pytest.approx(0.0012)
        # storm regime: gaps tiny, floor again (arrivals re-extend anyway)
        svc._ewma_gap = 0.00001
        assert svc._quiet_window() == svc.flush_min

    async def test_sparse_and_burst_resolve(self):
        import asyncio
        import time

        pubkeys, msgs, sigs = make_sigs(32)
        # 500 ms cap: the fixed-quantum behavior would park a lone vote for
        # the whole cap; adaptive must flush it in ~a quiet window.  The
        # half-cap bound stays robust against CI contention (background
        # warmup compiles share this box's cores).
        svc = AsyncBatchVerifier(BatchVerifier(), flush_interval=0.5)
        await svc.start()
        try:
            assert await svc.verify_one(pubkeys[0], msgs[0], sigs[0]) is True  # warm
            t0 = time.perf_counter()
            assert await svc.verify_one(pubkeys[0], msgs[0], sigs[0]) is True
            assert time.perf_counter() - t0 < 0.25
            # burst: everything lands in one coalesced batch, all correct
            futs = [
                svc.verify_one(pk, m, s)
                for pk, m, s in zip(pubkeys, msgs, sigs)
            ]
            bad = svc.verify_one(pubkeys[0], msgs[1], sigs[0])
            assert await asyncio.gather(*futs) == [True] * 32
            assert await bad is False
        finally:
            await svc.stop()

    async def test_fixed_interval_mode_still_works(self):
        pubkeys, msgs, sigs = make_sigs(3)
        svc = AsyncBatchVerifier(BatchVerifier(), flush_interval=0.002, adaptive=False)
        await svc.start()
        try:
            import asyncio

            futs = [svc.verify_one(pk, m, s) for pk, m, s in zip(pubkeys, msgs, sigs)]
            assert await asyncio.gather(*futs) == [True, True, True]
        finally:
            await svc.stop()
