"""privval tests: FilePV double-sign protection + remote signer socket.

Reference parity: privval/file_test.go (sign/re-sign/regression cases),
privval/signer_client_test.go.  The crash-safety test's
criterion: state persists BEFORE the signature escapes, so killing the
process after signing but before any other durable write cannot lead to a
conflicting re-sign after restart.
"""

import asyncio
import time

import pytest

from tendermint_tpu.config import test_config as make_test_cfg
from tendermint_tpu.node import Node
from tendermint_tpu.privval import FilePV, SignerClient, SignerServer
from tendermint_tpu.privval.file import (
    STEP_PRECOMMIT,
    STEP_PREVOTE,
    DoubleSignError,
    FilePVLastSignState,
)
from tendermint_tpu.types import BlockID, GenesisDoc, GenesisValidator, PartSetHeader, Vote
from tendermint_tpu.types.canonical import PRECOMMIT_TYPE, PREVOTE_TYPE
from tendermint_tpu.types.proposal import Proposal

from tendermint_tpu.types.params import BlockParams as _BP, ConsensusParams as _CP

# time_iota_ms=1: test chains commit ~10 blocks/sec (skip_timeout_commit), so the
# reference's default 1000 ms BFT-time step would race header time ahead of wall
# clock and trip clock-drift guards (lite2 + propose-side) under suite load
_FAST_IOTA_PARAMS = _CP(block=_BP(time_iota_ms=1))

CHAIN = "pv-chain"


def mk_vote(pv, h=5, r=0, t=PREVOTE_TYPE, blk=b"\x01" * 32, ts=None):
    return Vote(
        type=t,
        height=h,
        round=r,
        block_id=BlockID(blk, PartSetHeader(1, b"\x02" * 32)) if blk else BlockID(),
        timestamp_ns=ts if ts is not None else time.time_ns(),
        validator_address=pv.address(),
        validator_index=0,
    )


class TestFilePV:
    def _pv(self, tmp_path):
        return FilePV.load_or_generate(
            str(tmp_path / "pv_key.json"), str(tmp_path / "pv_state.json")
        )

    def test_gen_save_load_roundtrip(self, tmp_path):
        pv = self._pv(tmp_path)
        pv2 = FilePV.load(str(tmp_path / "pv_key.json"), str(tmp_path / "pv_state.json"))
        assert pv2.address() == pv.address()
        assert pv2.get_pub_key().bytes() == pv.get_pub_key().bytes()

    def test_sign_vote_persists_and_verifies(self, tmp_path):
        pv = self._pv(tmp_path)
        v = mk_vote(pv)
        pv.sign_vote(CHAIN, v)
        assert pv.get_pub_key().verify(v.sign_bytes(CHAIN), v.signature)
        lss = FilePVLastSignState.load(str(tmp_path / "pv_state.json"))
        assert (lss.height, lss.round, lss.step) == (5, 0, STEP_PREVOTE)
        assert lss.signature == v.signature

    def test_identical_resign_returns_same_signature(self, tmp_path):
        pv = self._pv(tmp_path)
        v = mk_vote(pv, ts=1234)
        pv.sign_vote(CHAIN, v)
        sig1 = v.signature
        v2 = mk_vote(pv, ts=1234)
        pv.sign_vote(CHAIN, v2)
        assert v2.signature == sig1

    def test_timestamp_only_diff_reuses_signature(self, tmp_path):
        """privval/file.go:296 — same vote, newer timestamp: release the
        previously signed timestamp + signature, do not sign fresh bytes."""
        pv = self._pv(tmp_path)
        v = mk_vote(pv, ts=1_000)
        pv.sign_vote(CHAIN, v)
        v2 = mk_vote(pv, ts=2_000)
        pv.sign_vote(CHAIN, v2)
        assert v2.timestamp_ns == 1_000
        assert v2.signature == v.signature

    def test_conflicting_same_hrs_refused(self, tmp_path):
        pv = self._pv(tmp_path)
        pv.sign_vote(CHAIN, mk_vote(pv, blk=b"\x01" * 32))
        with pytest.raises(DoubleSignError):
            pv.sign_vote(CHAIN, mk_vote(pv, blk=b"\x03" * 32))

    def test_hrs_regression_refused(self, tmp_path):
        pv = self._pv(tmp_path)
        pv.sign_vote(CHAIN, mk_vote(pv, h=5, r=2, t=PRECOMMIT_TYPE))
        with pytest.raises(DoubleSignError):  # height regression
            pv.sign_vote(CHAIN, mk_vote(pv, h=4, r=2))
        with pytest.raises(DoubleSignError):  # round regression
            pv.sign_vote(CHAIN, mk_vote(pv, h=5, r=1))
        with pytest.raises(DoubleSignError):  # step regression (precommit->prevote)
            pv.sign_vote(CHAIN, mk_vote(pv, h=5, r=2, t=PREVOTE_TYPE))

    def test_step_order_allows_forward_progress(self, tmp_path):
        pv = self._pv(tmp_path)
        p = Proposal(height=5, round=0, block_id=BlockID(b"\x01" * 32, PartSetHeader(1, b"\x02" * 32)), timestamp_ns=1)
        pv.sign_proposal(CHAIN, p)
        pv.sign_vote(CHAIN, mk_vote(pv, h=5, r=0, t=PREVOTE_TYPE))
        pv.sign_vote(CHAIN, mk_vote(pv, h=5, r=0, t=PRECOMMIT_TYPE))
        pv.sign_vote(CHAIN, mk_vote(pv, h=6, r=0, t=PREVOTE_TYPE))

    def test_kill_after_sign_no_double_sign_on_restart(self, tmp_path):
        """Sign, then 'crash' before any WAL write: a fresh process loading
        the same state file must refuse a conflicting same-HRS sign and
        must reproduce the identical signature for the same request."""
        pv = self._pv(tmp_path)
        v = mk_vote(pv, ts=777, blk=b"\x01" * 32)
        pv.sign_vote(CHAIN, v)

        # restart: state reloaded from disk only
        pv2 = FilePV.load(str(tmp_path / "pv_key.json"), str(tmp_path / "pv_state.json"))
        conflicting = mk_vote(pv2, ts=999, blk=b"\x0f" * 32)
        with pytest.raises(DoubleSignError):
            pv2.sign_vote(CHAIN, conflicting)
        same = mk_vote(pv2, ts=777, blk=b"\x01" * 32)
        pv2.sign_vote(CHAIN, same)
        assert same.signature == v.signature

    def test_state_file_is_atomic(self, tmp_path):
        pv = self._pv(tmp_path)
        for h in range(1, 30):
            pv.sign_vote(CHAIN, mk_vote(pv, h=h))
            lss = FilePVLastSignState.load(str(tmp_path / "pv_state.json"))
            assert lss.height == h


class TestRemoteSigner:
    async def test_sign_over_socket(self, tmp_path):
        file_pv = FilePV.load_or_generate(
            str(tmp_path / "k.json"), str(tmp_path / "s.json")
        )
        client = SignerClient("127.0.0.1:0", accept_timeout=10.0)
        # start listener without blocking on accept: run start concurrently
        start_task = asyncio.ensure_future(client.start())
        await asyncio.sleep(0.05)
        server = SignerServer(client.listen_addr, file_pv)
        await server.start()
        await start_task
        try:
            assert client.get_pub_key().bytes() == file_pv.get_pub_key().bytes()
            v = mk_vote(file_pv)
            await client.sign_vote(CHAIN, v)
            assert file_pv.get_pub_key().verify(v.sign_bytes(CHAIN), v.signature)
            # double-sign refusal crosses the socket as an error
            from tendermint_tpu.privval.signer import RemoteSignerError

            with pytest.raises(RemoteSignerError):
                await client.sign_vote(CHAIN, mk_vote(file_pv, blk=b"\x0c" * 32))
        finally:
            await server.stop()
            await client.stop()

    async def test_tcp_channel_is_encrypted(self, tmp_path):
        """tcp privval runs over SecretConnection (socket_listeners.go:80):
        sign-bytes must never appear in plaintext on the wire."""
        file_pv = FilePV.load_or_generate(str(tmp_path / "k.json"), str(tmp_path / "s.json"))
        client = SignerClient("127.0.0.1:0", accept_timeout=10.0)
        start_task = asyncio.ensure_future(client.start())
        await asyncio.sleep(0.05)
        server = SignerServer(client.listen_addr, file_pv)
        await server.start()
        await start_task
        try:
            assert client._conn._sc is not None  # SecretConnection active
            assert server._chan._sc is not None
        finally:
            await server.stop()
            await client.stop()

    async def test_reconnect_with_different_key_rejected(self, tmp_path):
        """An attacker who can reach priv_validator_laddr must not be able
        to replace the established signer with their own key."""
        real_pv = FilePV.load_or_generate(str(tmp_path / "k.json"), str(tmp_path / "s.json"))
        client = SignerClient("127.0.0.1:0", accept_timeout=10.0, timeout=2.0)
        start_task = asyncio.ensure_future(client.start())
        await asyncio.sleep(0.05)
        server = SignerServer(client.listen_addr, real_pv)
        await server.start()
        await start_task
        attacker_pv = FilePV.load_or_generate(
            str(tmp_path / "ak.json"), str(tmp_path / "as.json")
        )
        attacker = SignerServer(client.listen_addr, attacker_pv)

        # also: an attacker CLAIMING the victim's pubkey (it is public!)
        # must fail the proof-of-possession challenge
        class _ClaimingPV:
            def get_pub_key(self):
                return real_pv.get_pub_key()  # stated, not possessed

            def sign_challenge(self, nonce):
                return b"\x00" * 64  # cannot actually sign

            def sign_vote(self, chain_id, vote):
                vote.signature = b"\x00" * 64

            def sign_proposal(self, chain_id, proposal):
                proposal.signature = b"\x00" * 64

        claiming = SignerServer(client.listen_addr, _ClaimingPV())
        try:
            await attacker.start()
            await claiming.start()
            await asyncio.sleep(0.3)  # give the probes time to run + reject
            # the original signer still serves; signing still uses the real key
            v = mk_vote(real_pv)
            await client.sign_vote(CHAIN, v)
            assert real_pv.get_pub_key().verify(v.sign_bytes(CHAIN), v.signature)
            assert client.get_pub_key().bytes() == real_pv.get_pub_key().bytes()
        finally:
            await attacker.stop()
            await claiming.stop()
            await server.stop()
            await client.stop()

    async def test_unix_socket_roundtrip(self, tmp_path):
        file_pv = FilePV.load_or_generate(str(tmp_path / "k.json"), str(tmp_path / "s.json"))
        sock = str(tmp_path / "pv.sock")
        client = SignerClient(f"unix://{sock}", accept_timeout=10.0)
        start_task = asyncio.ensure_future(client.start())
        await asyncio.sleep(0.05)
        server = SignerServer(f"unix://{sock}", file_pv)
        await server.start()
        await start_task
        try:
            v = mk_vote(file_pv)
            await client.sign_vote(CHAIN, v)
            assert file_pv.get_pub_key().verify(v.sign_bytes(CHAIN), v.signature)
        finally:
            await server.stop()
            await client.stop()

    async def test_node_runs_with_remote_signer(self, tmp_path):
        """Solo validator produces blocks with signing delegated over the
        privval socket (the node/node.go:612 configuration)."""
        file_pv = FilePV.load_or_generate(
            str(tmp_path / "k.json"), str(tmp_path / "s.json")
        )
        gen = GenesisDoc(
            chain_id=CHAIN,
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=[GenesisValidator(file_pv.address(), file_pv.get_pub_key(), 10)],
            consensus_params=_FAST_IOTA_PARAMS,
        )
        client = SignerClient("127.0.0.1:0", accept_timeout=10.0)
        start_task = asyncio.ensure_future(client.start())
        await asyncio.sleep(0.05)
        server = SignerServer(client.listen_addr, file_pv)
        await server.start()
        await start_task

        cfg = make_test_cfg(str(tmp_path / "rsnode"))
        cfg.rpc.laddr = ""
        cfg.base.db_backend = "memdb"
        node = Node(cfg, gen, priv_validator=client, db_backend="memdb")
        try:
            await node.start()

            async def reach(h):
                while node.block_store.height() < h:
                    await asyncio.sleep(0.02)

            await asyncio.wait_for(reach(3), 30.0)
            # blocks were signed by the remote key
            commit = node.block_store.load_block_commit(2)
            assert commit.signatures[0].validator_address == file_pv.address()
        finally:
            await node.stop()
            await server.stop()
