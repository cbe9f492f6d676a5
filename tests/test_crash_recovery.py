"""Crash-recovery rigs (reference: consensus/replay_test.go
crashingWAL + test/persist/test_failure_indices.sh + byzantine_test.go:27).

(a) crashing-WAL: kill consensus at every WAL record index, restart on the
    same stores, assert resume past the crash height.
(b) fail-point kills: real subprocess os._exit at each FAIL_TEST_INDEX
    crash site (finalize-*/applyblock-*), restart, assert recovery.
(c) byzantine proposer: conflicting proposals to different peers via the
    overridable decide_proposal; honest majority keeps committing.
"""

import asyncio
import os
import subprocess
import sys
import time

import pytest

import tendermint_tpu.node as node_module
from tendermint_tpu.cli import main as cli_main
from tendermint_tpu.config import test_config as make_test_cfg
from tendermint_tpu.consensus.wal import WAL
from tendermint_tpu.node import Node
from tendermint_tpu.types import GenesisDoc, GenesisValidator, MockPV

from tendermint_tpu.types.params import BlockParams as _BP, ConsensusParams as _CP

# time_iota_ms=1: test chains commit ~10 blocks/sec (skip_timeout_commit), so the
# reference's default 1000 ms BFT-time step would race header time ahead of wall
# clock and trip clock-drift guards (lite2 + propose-side) under suite load
_FAST_IOTA_PARAMS = _CP(block=_BP(time_iota_ms=1))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class WALCrash(Exception):
    pass


class CrashingWAL(WAL):
    """replay_test.go crashingWAL: raise on the Nth write, passthrough
    otherwise.  Class-level countdown so a fresh instance per node start
    still honors the schedule."""

    crash_after = -1  # set by the test; -1 = disabled

    def __init__(self, path):
        super().__init__(path)

    def _tick(self):
        cls = CrashingWAL
        if cls.crash_after < 0:
            return
        if cls.crash_after == 0:
            cls.crash_after = -1
            raise WALCrash("simulated WAL crash")
        cls.crash_after -= 1

    def write(self, payload):
        self._tick()
        super().write(payload)

    def write_sync(self, payload):
        self._tick()
        super().write_sync(payload)


def _solo_cfg(tmp_path, name):
    cfg = make_test_cfg(str(tmp_path / name))
    cfg.base.db_backend = "sqlite"
    cfg.rpc.laddr = ""
    cfg.consensus.skip_timeout_commit = False
    cfg.consensus.timeout_commit = 0.02
    cfg.ensure_dirs()
    return cfg


def _gen(pvs, chain="crash-chain"):
    return GenesisDoc(
        chain_id=chain,
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(pv.address(), pv.get_pub_key(), 10) for pv in pvs],
        consensus_params=_FAST_IOTA_PARAMS,
    )


class TestCrashingWAL:
    async def test_crash_at_every_wal_record_then_recover(self, tmp_path, monkeypatch):
        """Run a solo validator; for each crash index N, crash the WAL at
        record N mid-flight, restart on the same home, and require progress
        beyond the pre-crash height.  One shared home so each iteration
        also exercises handshake catchup over the previous history."""
        monkeypatch.setattr(node_module, "WAL", CrashingWAL)
        pv = MockPV()
        gen = _gen([pv])
        home_i = 0
        for crash_n in range(1, 14, 2):
            home_i += 1
            cfg = _solo_cfg(tmp_path, f"wal{home_i}")
            CrashingWAL.crash_after = crash_n
            node = Node(cfg, gen, priv_validator=pv)
            await node.start()
            # consensus dies at the Nth WAL record (receive loop exits)
            await asyncio.wait_for(node.consensus.wait_done(), 30.0)
            crashed_height = node.block_store.height()
            await node.stop()

            # restart clean on the same stores: WAL catchup + handshake
            CrashingWAL.crash_after = -1
            node2 = Node(cfg, gen, priv_validator=pv)
            await node2.start()

            async def past(h):
                while node2.block_store.height() < h:
                    await asyncio.sleep(0.02)

            await asyncio.wait_for(past(crashed_height + 2), 30.0)
            await node2.stop()


@pytest.mark.parametrize("indices", [range(0, 5), range(5, 10)])
class TestFailPointKills:
    def test_kill_and_recover(self, tmp_path, indices):
        """test_failure_indices.sh: run the node subprocess with
        FAIL_TEST_INDEX=i (hard os._exit at crash site i), then restart
        without it and require 2 more committed blocks."""
        home = str(tmp_path / "fp-home")
        assert cli_main(["--home", home, "init", "--chain-id", "fp-chain"]) == 0
        runner = os.path.join(REPO, "tests", "failpoint_node.py")
        base_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        base_env.pop("FAIL_TEST_INDEX", None)

        for i in indices:
            crash = subprocess.run(
                [sys.executable, runner, "--home", home, "--blocks", "3"],
                env={**base_env, "FAIL_TEST_INDEX": str(i)},
                capture_output=True,
                timeout=90,
                text=True,
            )
            assert crash.returncode == 1, (
                f"index {i}: expected fail-point exit, got rc={crash.returncode}\n"
                f"{crash.stdout}\n{crash.stderr}"
            )
            recover = subprocess.run(
                [sys.executable, runner, "--home", home, "--blocks", "2"],
                env=base_env,
                capture_output=True,
                timeout=90,
                text=True,
            )
            assert recover.returncode == 0, (
                f"index {i}: recovery failed rc={recover.returncode}\n"
                f"{recover.stdout}\n{recover.stderr}"
            )


class TestByzantineProposer:
    async def test_conflicting_proposals_do_not_halt_net(self, tmp_path):
        """byzantine_test.go:27 — node0 equivocates: proposal A (+parts) to
        one peer, proposal B to the others.  With 3 of 4 honest the network
        must keep committing and stay consistent."""
        from tests.test_consensus_net import make_net, stop_net, wait_all_height

        nodes, pvs = await make_net(tmp_path, 4, name="byzprop")
        byz = nodes[0]
        cs = byz.consensus
        reactor = byz.consensus_reactor

        from tendermint_tpu.consensus.reactor import DATA_CHANNEL, _enc
        from tendermint_tpu.types import BlockID
        from tendermint_tpu.types.part_set import BLOCK_PART_SIZE_BYTES
        from tendermint_tpu.types.proposal import Proposal

        async def byz_decide_proposal(height, round_):
            created = cs._create_proposal_block()
            if created is None:
                return
            block_a, parts_a = created
            # a second, conflicting block with different data
            commit = (
                cs.rs.last_commit.make_commit()
                if height > 1 and cs.rs.last_commit is not None
                else __import__(
                    "tendermint_tpu.types.block", fromlist=["Commit"]
                ).Commit(0, 0, BlockID(), [])
            )
            block_b = cs.sm_state.make_block(
                height, [b"byz-conflicting-tx"], commit, [], pvs[0].address()
            )
            parts_b = block_b.make_part_set(BLOCK_PART_SIZE_BYTES)

            peers = list(byz.switch.peers.values())
            half = max(1, len(peers) // 2)
            for grp, (blk, parts) in (
                (peers[:half], (block_a, parts_a)),
                (peers[half:], (block_b, parts_b)),
            ):
                prop = Proposal(
                    height=height,
                    round=round_,
                    pol_round=cs.rs.valid_round,
                    block_id=BlockID(blk.hash(), parts.header()),
                    timestamp_ns=time.time_ns(),
                )
                pvs[0].sign_proposal(cs.sm_state.chain_id, prop)
                for peer in grp:
                    await peer.send(DATA_CHANNEL, _enc("proposal", {"proposal": prop.to_dict()}))
                    for i in range(parts.total):
                        await peer.send(
                            DATA_CHANNEL,
                            _enc("block_part", {
                                "height": height, "round": round_,
                                "part": parts.get_part(i).to_dict(),
                            }),
                        )

        cs.decide_proposal = byz_decide_proposal
        try:
            start = max(n.block_store.height() for n in nodes)
            # honest nodes (1-3) must keep committing identical blocks
            await wait_all_height(nodes[1:], start + 4, timeout=60.0)
            for h in range(1, start + 4):
                hashes = {
                    n.block_store.load_block(h).hash()
                    for n in nodes[1:]
                    if n.block_store.load_block(h) is not None
                }
                assert len(hashes) <= 1, f"honest nodes diverged at {h}"
        finally:
            await stop_net(nodes)


class TestRestartOverWALBitRot:
    async def test_node_restarts_and_commits_over_mid_wal_corruption(self, tmp_path):
        """CrashingWAL-rig extension for the hostile-disk contract: a solo
        validator stops cleanly, ONE byte inside an early WAL record rots
        on disk, and the restart must come up and keep committing — the
        tolerant replay resyncs past the damaged region (and counts it)
        instead of refusing to boot or replaying garbage."""
        from tendermint_tpu.libs.autofile import walk_frames

        pv = MockPV()
        gen = _gen([pv], chain="walrot-chain")
        cfg = _solo_cfg(tmp_path, "walrot")
        node = Node(cfg, gen, priv_validator=pv)
        await node.start()

        async def past(n, h):
            while n.block_store.height() < h:
                await asyncio.sleep(0.02)

        await asyncio.wait_for(past(node, 3), 30.0)
        stopped_height = node.block_store.height()
        await node.stop()

        wal_path = cfg.wal_file()
        raw = bytearray(open(wal_path, "rb").read())
        offsets = [pos for k, pos, _ in walk_frames(bytes(raw)) if k == "record"]
        assert len(offsets) > 4
        raw[offsets[1] + 12] ^= 0xFF  # rot an EARLY record, mid-file
        open(wal_path, "wb").write(bytes(raw))

        node2 = Node(cfg, gen, priv_validator=pv)
        await node2.start()
        try:
            await asyncio.wait_for(past(node2, stopped_height + 2), 30.0)
            assert node2.consensus.wal.corrupt_regions_skipped >= 1
        finally:
            await node2.stop()


class TestWALFuzz:
    """consensus/wal_fuzz.go flavor: corrupted/torn WALs must either
    recover cleanly (torn tail = crash mid-write) or fail LOUDLY
    (mid-file corruption) — never silently misreplay."""

    def _wal(self, tmp_path):
        from tendermint_tpu.consensus.wal import WAL

        wal = WAL(str(tmp_path / "cs.wal" / "wal"))
        for h in (1, 2):
            wal.write_sync({"type": "msg", "height": h, "data": b"x" * 100})
            wal.write_end_height(h)
        wal.write_sync({"type": "msg", "height": 3, "data": b"y" * 100})
        wal.close()
        return str(tmp_path / "cs.wal" / "wal")

    def test_torn_tail_recovers(self, tmp_path):
        from tendermint_tpu.consensus.wal import WAL

        path = self._wal(tmp_path)
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[:-37])  # tear the last record mid-payload
        wal = WAL(path)
        records, found = wal.search_for_end_height(2)
        assert found
        assert records == []  # the torn height-3 msg is gone, cleanly
        # the WAL is appendable again after the torn read
        wal.write_sync({"type": "msg", "height": 3, "data": b"z"})
        assert wal.all_records()[-1]["height"] == 3
        wal.close()

    def test_mid_file_corruption_is_loud(self, tmp_path):
        import pytest as _pytest

        from tendermint_tpu.consensus.wal import WAL, WALCorruptionError

        path = self._wal(tmp_path)
        raw = bytearray(open(path, "rb").read())
        raw[40] ^= 0xFF  # flip a byte inside the first record's payload
        open(path, "wb").write(bytes(raw))
        wal = WAL(path)
        with _pytest.raises(WALCorruptionError):
            wal.all_records()
        wal.close()

    def test_random_garbage_never_misreplays(self, tmp_path):
        """Random mutations: every outcome is either a clean parse of a
        PREFIX of the original records or a WALCorruptionError — fuzzing
        the decoder invariant."""
        import random

        from tendermint_tpu.consensus.wal import WAL, WALCorruptionError

        path = self._wal(tmp_path)
        original = open(path, "rb").read()
        from tendermint_tpu.consensus.wal import decode_records

        full = list(decode_records(original))
        rng = random.Random(5)
        for _ in range(60):
            raw = bytearray(original)
            op = rng.randrange(3)
            if op == 0:  # truncate
                del raw[rng.randrange(1, len(raw)) :]
            elif op == 1:  # flip a byte
                raw[rng.randrange(len(raw))] ^= rng.randrange(1, 256)
            else:  # insert garbage
                pos = rng.randrange(len(raw))
                raw[pos:pos] = bytes(rng.randrange(256) for _ in range(8))
            try:
                got = list(decode_records(bytes(raw)))
            except WALCorruptionError:
                continue  # loud failure: acceptable
            except Exception:
                continue  # decoder surfaced garbage as an error: acceptable
            # silent success must be a strict prefix of the original
            assert got == full[: len(got)], "misreplayed/mutated records"
