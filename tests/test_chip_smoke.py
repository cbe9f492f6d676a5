"""Bring-up guards: chip_smoke.py's phases at toy size on the CPU mesh, its
refusal to run without a TPU, the engine's failure reporting (no swallowed
compile / table / profile error), compile-cache placement, and per-CPU
naming of the C-extension artifacts.
"""

import logging
import os
import shutil
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from tendermint_tpu.crypto import hostprep  # noqa: E402
from tendermint_tpu.crypto.batch_verifier import (  # noqa: E402
    BatchVerifier,
    PubkeyTable,
    TableCache,
)
from tendermint_tpu.crypto.keys import Ed25519PrivKey  # noqa: E402
from tendermint_tpu.libs.tracing import FlightRecorder  # noqa: E402

ENGINE_LOGGER = "tendermint_tpu.crypto.batch_verifier"


def _wait_event(rec, kind, budget=30.0):
    deadline = time.monotonic() + budget
    while time.monotonic() < deadline:
        evs = [e for e in rec.events() if e["kind"] == kind]
        if evs:
            return evs[-1]
        time.sleep(0.02)
    raise AssertionError(f"no {kind} event within {budget} s")


def _sigs(n):
    keys = [Ed25519PrivKey.from_secret(b"smoke-test-%d" % i) for i in range(n)]
    msgs = [b"msg-%d" % i for i in range(n)]
    return [k.pub_key().bytes() for k in keys], msgs, [k.sign(m) for k, m in zip(keys, msgs)]


# -- the smoke itself -------------------------------------------------------


class TestSmoke:
    async def test_phases_pass_at_toy_size_on_the_cpu_mesh(self, tmp_path):
        """64 validators, a 64-vote flush, a live node sharded over the 8
        virtual CPU devices: every verdict equals the host reference, both
        batches reach a device path, and the multi-device placement checks
        (the four-chip rehearsal) hold."""
        import jax

        def configure(cfg):
            cfg.tpu.mesh = "on"  # mesh=auto ignores virtual CPU devices
            cfg.consensus.timeout_commit = 0.1

        body = await chip_smoke.run(
            seed=7, n_validators=64, n_votes=64, n_txs=2, deadline_s=300.0,
            home=str(tmp_path / "home"), configure=configure,
        )
        n_dev = len(jax.devices())
        assert body["shards"] == n_dev == 8
        commit, votes = body["phases"]["commit"], body["phases"]["votes"]
        assert body["phases"]["node"]["read_back"] is True
        assert commit["path"] in chip_smoke.TABLE_PATHS
        assert votes["path"] in chip_smoke.DEVICE_PATHS
        assert commit["verdicts_equal"] and votes["verdicts_equal"]
        assert set(commit["tampered"]) == {
            "signature", "message", "noncanonical_s", "invalid_pubkey"
        }
        assert commit["placement"] == {
            "table_devices": n_dev, "verdict_devices": n_dev, "kernel": "straus"}
        assert commit["kernel"] == votes["kernel"] == "xla-straus"

    def test_straus_on_a_mesh_of_tpus_fails_the_smoke(self):
        """The placement check reads the dispatch event's `kernel`: four TPU
        chips that dispatched XLA Straus are refused before anything else is
        looked at, a CPU mesh (the rehearsal above) is not."""
        import jax

        n_dev = len(jax.devices())
        straus = {"shards": n_dev, "kernel": "straus", "path": "indexed", "bucket": 64}
        with pytest.raises(chip_smoke.SmokeFailure, match="kernel='straus', not the Pallas ladder"):
            chip_smoke.mesh_placement(None, None, straus, platform="tpu")
        with pytest.raises(chip_smoke.SmokeFailure, match="kernel=None"):  # an older engine's event
            chip_smoke.mesh_placement(None, None, {"shards": n_dev}, platform="tpu")
        with pytest.raises(AttributeError):  # the ladder passes on to the table checks
            chip_smoke.mesh_placement(None, None, {**straus, "kernel": "ladder"}, platform="tpu")

    def test_main_refuses_a_cpu(self, capsys):
        assert chip_smoke.main([]) != 0
        out = capsys.readouterr()
        assert out.out == ""
        assert "needs a TPU" in out.err and "'cpu'" in out.err

    def test_last_stdout_line_is_the_bare_verdict(self, capsys):
        """The chip check parses the last line strictly: `ok` and `device`
        (platform, kind, count) and no other key; the observations go on
        the line before it."""
        import json

        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        chip_smoke.print_result({"device": device, "compile_s": 1.5, "phases": {}})
        report, verdict = map(json.loads, capsys.readouterr().out.splitlines())
        assert verdict == {"ok": True, "device": device}
        assert report["report"]["compile_s"] == 1.5

    def test_warm_rule_and_failed_events_are_refused(self):
        cold = {"kind": "verify.dispatch", "n": 64, "bucket": 64, "path": "host-cold"}
        small = {"kind": "verify.dispatch", "n": 1, "bucket": 0, "path": "host"}
        assert chip_smoke.engine_failures([cold, small], 16, warm=False) == []
        assert len(chip_smoke.engine_failures([cold, small], 16, warm=True)) == 1
        failed = {"kind": "verify.bucket_compile", "bucket": 512, "ok": False, "error": "boom"}
        assert "boom" in chip_smoke.engine_failures([failed], 16, warm=False)[0]


# -- no fallback that hides the device --------------------------------------


class TestFailuresAreReported:
    def _engine(self):
        rec = FlightRecorder(size=256)
        return BatchVerifier(min_device_batch=1, recorder=rec), rec

    def _refused(self, rec):
        """The smoke's checker on this recorder: must raise."""
        watch = chip_smoke.EngineWatch(rec, min_device_batch=16)
        with pytest.raises(chip_smoke.SmokeFailure) as exc:
            watch.poll()
        return str(exc.value)

    def test_bucket_compile_failure(self, monkeypatch, caplog):
        verifier, rec = self._engine()

        def refuse(b):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

        monkeypatch.setattr(verifier, "_compile_bucket", refuse)
        with caplog.at_level(logging.ERROR, logger=ENGINE_LOGGER):
            verifier.start_warmup()
            ev = _wait_event(rec, "verify.bucket_compile")
        assert ev["ok"] is False and "Mosaic failed" in ev["error"]
        assert any("compile failed" in r.message for r in caplog.records)
        # liveness kept: the bucket is served by the host tier, correctly
        pubkeys, msgs, sigs = _sigs(3)
        assert verifier.verify(pubkeys, msgs, sigs) == [True] * 3
        assert rec.events(kinds=["verify.dispatch"])[-1]["path"] == "host-cold"
        assert "Mosaic failed" in self._refused(rec)

    @pytest.mark.parametrize("where", ["constructor", "warm_dispatch"])
    def test_table_build_failure(self, where, monkeypatch, caplog):
        """Whether the table's construction fails or the warm dispatch that
        precedes its publication: reported, logged, the set not published."""
        verifier, rec = self._engine()
        verifier._warmup_mode = True  # node mode: tables build in the background
        cache = TableCache(verifier)

        def oom(*args):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

        if where == "constructor":
            monkeypatch.setattr(cache, "_new_table", oom)
        else:
            monkeypatch.setattr(PubkeyTable, "verify_indexed", oom)
        pubkeys, msgs, sigs = _sigs(4)
        with caplog.at_level(logging.ERROR, logger=ENGINE_LOGGER):
            assert cache.verify_indexed(b"k" * 32, pubkeys, [0, 1, 2, 3], msgs, sigs) is None
            ev = _wait_event(rec, "verify.table_build")
        assert ev["ok"] is False and "RESOURCE_EXHAUSTED" in ev["error"]
        assert ev["validators"] == 4
        assert any("table build failed" in r.message for r in caplog.records)
        assert not cache.has_table(b"k" * 32) and not cache._building
        assert "RESOURCE_EXHAUSTED" in self._refused(rec)

    def test_table_is_published_only_after_its_warm_dispatch(self, monkeypatch):
        """A table visible before its kernels are compiled would make the
        next verify_commit compile inline on the consensus event loop."""
        verifier, rec = self._engine()
        verifier._warmup_mode = True
        cache = TableCache(verifier)
        seen = []
        real = PubkeyTable.verify_indexed

        def spy(self, idxs, msgs, sigs):
            seen.append(cache.has_table(b"s" * 32))
            return real(self, idxs, msgs, sigs)

        monkeypatch.setattr(PubkeyTable, "verify_indexed", spy)
        pubkeys, msgs, sigs = _sigs(4)
        verifier.min_device_batch = 1 << 30  # host tier: no compile in this test
        assert cache.verify_indexed(b"s" * 32, pubkeys, [0, 1, 2, 3], msgs, sigs) is None
        assert _wait_event(rec, "verify.table_build")["ok"] is True
        assert seen == [False] and cache.has_table(b"s" * 32)

    def test_rtt_probe_failure(self, monkeypatch, caplog):
        verifier, rec = self._engine()

        def down(samples=7):
            raise RuntimeError("device plane down")

        monkeypatch.setattr(verifier, "probe_dispatch_rtt", down)
        with caplog.at_level(logging.ERROR, logger=ENGINE_LOGGER):
            assert verifier.chunked_auto() is False
        ev = rec.events(kinds=["verify.chunked"])[-1]
        assert ev["ok"] is False and "device plane down" in ev["error"]
        assert any("RTT probe failed" in r.message for r in caplog.records)

    def test_mesh_probe_failure_is_logged(self, monkeypatch, caplog):
        import jax

        from tendermint_tpu.crypto import backend

        def down(*a, **k):
            raise RuntimeError("device plane down")

        monkeypatch.setattr(jax, "devices", down)
        with caplog.at_level(logging.ERROR, logger="tendermint_tpu.crypto.backend"):
            mesh, shards, reason = backend.resolve_mesh("auto", 0)
        assert mesh is None and shards == 1
        assert reason.startswith(backend.MESH_PROBE_FAILED) and "device plane down" in reason
        assert any("mesh probe failed" in r.message for r in caplog.records)

    def test_mesh_reason_names_backend_and_device_kind(self):
        from tendermint_tpu.crypto import backend

        _, _, reason = backend.resolve_mesh("on", 8)
        assert "cpu cpu" in reason  # default_backend + device_kind


# -- one compile cache, placeable from outside ------------------------------


class TestCompileCachePlacement:
    def _cache_dir(self, env_value):
        env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
        if env_value is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_value
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; from tendermint_tpu import ops; "
             "print(jax.config.jax_compilation_cache_dir)"],
            capture_output=True, text=True, timeout=120, cwd=REPO, env=env, check=True,
        )
        return out.stdout.strip().splitlines()[-1]

    def test_env_var_wins(self, tmp_path):
        assert self._cache_dir(str(tmp_path / "placed")) == str(tmp_path / "placed")

    def test_default_is_in_the_checkout(self):
        assert self._cache_dir(None) == os.path.join(REPO, ".jax_cache")


# -- C extensions are per-CPU artifacts -------------------------------------


@pytest.mark.skipif(shutil.which("cc") is None, reason="no C toolchain")
class TestNativeArtifactNaming:
    def test_name_changes_with_cpu_identity(self, tmp_path, monkeypatch):
        src = tmp_path / "one.c"
        src.write_text("int one(void) { return 1; }\n")
        here = hostprep.build_native_lib(str(src), "one")
        assert hostprep.cpu_identity() in os.path.basename(here)
        assert hostprep.build_native_lib(str(src), "one") == here  # cached
        # the same tree seen from another CPU: the artifact built here is
        # not that machine's artifact, so it is rebuilt, not loaded
        monkeypatch.setattr(hostprep, "cpu_identity", lambda: "x86_64-0ther000")
        there = hostprep.build_native_lib(str(src), "one")
        assert there != here and os.path.exists(there)
        assert "x86_64-0ther000" in os.path.basename(there)

    def test_cpu_identity_reads_the_isa_flags(self, monkeypatch):
        import builtins
        import io

        real_open = builtins.open

        def fake_cpuinfo(flags):
            def _open(path, *a, **k):
                if path == "/proc/cpuinfo":
                    return io.StringIO(f"model name\t: Some CPU\nflags\t\t: {flags}\n")
                return real_open(path, *a, **k)

            return _open

        monkeypatch.setattr(builtins, "open", fake_cpuinfo("fpu sse2 avx2"))
        narrow = hostprep.cpu_identity()
        monkeypatch.setattr(builtins, "open", fake_cpuinfo("fpu sse2 avx2 avx512f"))
        assert hostprep.cpu_identity() != narrow
