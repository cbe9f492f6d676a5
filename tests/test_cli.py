"""CLI + testnet-generator tests (reference: cmd/tendermint/commands).

The localnet test's criterion: a 4-node net launches from
CLI-generated config trees (no hand-written Python wiring) and commits
blocks.
"""

import asyncio
import json
import os

from tendermint_tpu.cli import main as cli_main
from tendermint_tpu.config import load_config


def run_cli(*argv):
    return cli_main(list(argv))


class TestBasicCommands:
    def test_init_creates_tree(self, tmp_path, capsys):
        home = str(tmp_path / "home")
        assert run_cli("--home", home, "init", "--chain-id", "cli-chain") == 0
        assert os.path.exists(os.path.join(home, "config", "config.toml"))
        assert os.path.exists(os.path.join(home, "config", "genesis.json"))
        assert os.path.exists(os.path.join(home, "config", "priv_validator_key.json"))
        assert os.path.exists(os.path.join(home, "config", "node_key.json"))
        cfg = load_config(os.path.join(home, "config", "config.toml"), home=home)
        assert cfg.base.chain_id == "cli-chain"

    def test_gen_validator_json(self, capsys):
        assert run_cli("gen_validator") == 0
        d = json.loads(capsys.readouterr().out)
        assert len(bytes.fromhex(d["priv_key"]["value"])) == 32

    def test_show_node_id_and_validator(self, tmp_path, capsys):
        home = str(tmp_path / "home")
        run_cli("--home", home, "init")
        capsys.readouterr()
        assert run_cli("--home", home, "show_node_id") == 0
        node_id = capsys.readouterr().out.strip()
        assert len(node_id) == 40  # hex address
        assert run_cli("--home", home, "show_validator") == 0
        d = json.loads(capsys.readouterr().out)
        assert len(bytes.fromhex(d["value"])) == 32

    def test_unsafe_reset_all(self, tmp_path, capsys):
        home = str(tmp_path / "home")
        run_cli("--home", home, "init")
        marker = os.path.join(home, "data", "blockstore.db")
        open(marker, "w").write("x")
        assert run_cli("--home", home, "unsafe_reset_all") == 0
        assert not os.path.exists(marker)

    def test_version(self, capsys):
        assert run_cli("version") == 0
        assert capsys.readouterr().out.strip()


class TestTestnet:
    def test_generates_wired_configs(self, tmp_path, capsys):
        out = str(tmp_path / "net")
        assert run_cli("testnet", "-v", "4", "-o", out, "--chain-id", "tn") == 0
        genesis_hashes = set()
        ids = []
        for i in range(4):
            home = os.path.join(out, f"node{i}")
            cfg = load_config(os.path.join(home, "config", "config.toml"), home=home)
            assert cfg.base.chain_id == "tn"
            peers = cfg.p2p.persistent_peers.split(",")
            assert len(peers) == 3  # everyone else
            from tendermint_tpu.types import GenesisDoc

            gen = GenesisDoc.from_file(cfg.genesis_file())
            assert len(gen.validators) == 4
            genesis_hashes.add(gen.validator_hash())
            from tendermint_tpu.p2p.key import NodeKey

            ids.append(NodeKey.load(cfg.node_key_file()).id)
        assert len(genesis_hashes) == 1  # identical genesis everywhere
        assert len(set(ids)) == 4

    def test_bls_key_type_end_to_end(self, tmp_path):
        """Satellite: `testnet --key-type bls12381` end to end — keygen,
        address derivation, key-file round-trip, and a PoP-carrying
        genesis that passes the rogue-key gate."""
        from tendermint_tpu.crypto.bls import BlsPubKey
        from tendermint_tpu.crypto.tmhash import sum_truncated
        from tendermint_tpu.privval.file import FilePV
        from tendermint_tpu.types import GenesisDoc

        out = str(tmp_path / "blsnet")
        assert run_cli("testnet", "-v", "3", "-o", out, "--key-type", "bls12381",
                       "--chain-id", "bls-tn") == 0
        gen = None
        for i in range(3):
            home = os.path.join(out, f"node{i}")
            cfg = load_config(os.path.join(home, "config", "config.toml"), home=home)
            assert cfg.base.key_type == "bls12381"
            pv = FilePV.load(
                cfg.priv_validator_key_file(), cfg.priv_validator_state_file()
            )
            pub = pv.get_pub_key()
            assert isinstance(pub, BlsPubKey) and len(pub.bytes()) == 48
            assert pv.address() == sum_truncated(pub.bytes())
            again = FilePV.load(
                cfg.priv_validator_key_file(), cfg.priv_validator_state_file()
            )
            assert again.get_pub_key().bytes() == pub.bytes()
            assert again.address() == pv.address()
            gen = GenesisDoc.from_file(cfg.genesis_file())
            gen.validate_and_complete()  # PoP enforcement must pass on real files
        assert all(
            isinstance(v.pub_key, BlsPubKey) and v.pop for v in gen.validators
        )
        # `init --key-type bls12381` takes the same path for a solo node
        solo = str(tmp_path / "solo")
        assert run_cli("--home", solo, "init", "--chain-id", "bls-solo",
                       "--key-type", "bls12381") == 0
        cfg = load_config(os.path.join(solo, "config", "config.toml"), home=solo)
        assert cfg.base.key_type == "bls12381"
        pv = FilePV.load(
            cfg.priv_validator_key_file(), cfg.priv_validator_state_file()
        )
        assert isinstance(pv.get_pub_key(), BlsPubKey)
        GenesisDoc.from_file(cfg.genesis_file()).validate_and_complete()

    async def test_localnet_from_generated_configs(self, tmp_path):
        """Launch all 4 nodes exactly as `node` would (default_new_node on
        the generated config tree) and watch them commit together."""
        from tendermint_tpu.node import default_new_node

        from tests.test_tools import _free_base_port

        out = str(tmp_path / "net")
        run_cli("testnet", "-v", "4", "-o", out, "--base-port", str(_free_base_port(4)))
        nodes = []
        try:
            for i in range(4):
                home = os.path.join(out, f"node{i}")
                cfg = load_config(os.path.join(home, "config", "config.toml"), home=home)
                # operator-style tweaks for CI: memdb speed + quiet engine
                # (the device path is covered by test_node_wiring)
                cfg.base.db_backend = "memdb"
                cfg.tpu.enabled = False
                cfg.rpc.laddr = ""
                cfg.base.fast_sync = False
                cfg.consensus.timeout_commit = 0.1
                cfg.consensus.timeout_propose = 2.0
                nodes.append(default_new_node(cfg))
            await asyncio.gather(*(n.start() for n in nodes))

            async def all_reach(h):
                while not all(n.block_store.height() >= h for n in nodes):
                    await asyncio.sleep(0.05)

            await asyncio.wait_for(all_reach(2), 60.0)
            hashes = {n.block_store.load_block(1).hash() for n in nodes}
            assert len(hashes) == 1
        finally:
            for n in nodes:
                if n.is_running:
                    await n.stop()


class TestDebugBundles:
    """`debug dump --offline`: a dead node's forensics bundle built purely
    from its home directory — the spool replay stands in for the live
    recorder, and the derived span report proves the pre-crash chains."""

    def _crashed_home(self, tmp_path, heights=6):
        from tendermint_tpu.libs.tracing import FlightRecorder, FlightSpool

        home = str(tmp_path / "home")
        run_cli("--home", home, "init", "--chain-id", "dbg-chain")
        cfg = load_config(os.path.join(home, "config", "config.toml"), home=home)
        rec = FlightRecorder(size=8192)
        sp = FlightSpool(cfg.flight_spool_file(), rec, node="dbg-node")
        for h in range(1, heights + 1):
            for s in ("Propose", "Prevote", "Precommit", "Commit"):
                rec.record("step", height=h, round=0, step=s)
            rec.record("commit", height=h, txs=0, block=f"h{h}")
            sp.flush()
        # NO close(): the node was SIGKILLed — the spool is all there is
        return home

    def test_debug_dump_offline_reconstructs_from_spool(self, tmp_path, capsys):
        import tarfile

        home = self._crashed_home(tmp_path)
        out = str(tmp_path / "bundles")
        assert run_cli(
            "--home", home, "debug", "dump", "--offline", "--output", out
        ) == 0
        capsys.readouterr()
        bundles = [f for f in os.listdir(out) if f.endswith(".tar.gz")]
        assert len(bundles) == 1
        sections = {}
        with tarfile.open(os.path.join(out, bundles[0])) as tar:
            for m in tar.getmembers():
                sections[os.path.basename(m.name)] = tar.extractfile(m).read()
        assert {"manifest.json", "config.toml", "spool.json",
                "span_report.json", "loop_report.json",
                "flight.spool.tail"} <= set(sections)
        manifest = json.loads(sections["manifest.json"])
        assert manifest["mode"] == "offline"
        assert manifest["event_source"] == "spool"
        # the acceptance shape: every interior pre-crash height has a
        # complete propose→prevote→precommit→commit chain, from disk alone
        rep = json.loads(sections["span_report.json"])
        assert rep["bad"] == {} and rep["interior"] == 4
        assert len(rep["complete"]) == rep["interior"]
        spool = json.loads(sections["spool.json"])
        assert spool["node"] == "dbg-node" and spool["events"]
        # offline mode never touched the RPC sections
        assert "status.json" not in sections

    def test_debug_dump_periodic_count(self, tmp_path, capsys):
        home = self._crashed_home(tmp_path, heights=3)
        out = str(tmp_path / "periodic")
        assert run_cli(
            "--home", home, "debug", "dump", "--offline", "--output", out,
            "--frequency", "0.05", "--count", "2",
        ) == 0
        capsys.readouterr()
        assert len([f for f in os.listdir(out) if f.endswith(".tar.gz")]) == 2

    def test_debug_dump_live_degrades_to_home_dir_when_rpc_dead(
        self, tmp_path, capsys
    ):
        import tarfile

        home = self._crashed_home(tmp_path, heights=3)
        out = str(tmp_path / "degraded")
        # no --offline, but nothing listens on the laddr: the bundle must
        # still be written from the home dir, with the RPC failure noted
        assert run_cli(
            "--home", home, "debug", "dump", "--output", out,
            "--rpc-laddr", "127.0.0.1:1",
        ) == 0
        capsys.readouterr()
        bundles = [f for f in os.listdir(out) if f.endswith(".tar.gz")]
        assert len(bundles) == 1
        with tarfile.open(os.path.join(out, bundles[0])) as tar:
            names = {os.path.basename(m.name) for m in tar.getmembers()}
        assert "spool.json" in names and "config.toml" in names
