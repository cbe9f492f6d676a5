# Build/test/bench entry points (reference parity: Makefile).
PY ?= python

.PHONY: test test-fast chip-smoke bench bench-smoke mesh-smoke trace-smoke trace-net-smoke statesync-smoke chaos-smoke disk-smoke scale-smoke bls-smoke bls-ext load-smoke lite-smoke forensics-smoke finality-smoke rotation-smoke localnet lint fmt csrc clean abci-cli signer-harness

test:            ## full suite (virtual 8-device CPU mesh)
	$(PY) -m pytest tests/ -q

test-fast:       ## the quick tiers only
	$(PY) -m pytest tests/ -q -x --ignore=tests/test_tools.py

chip-smoke:      ## the node's verify path end to end on the TPU this process holds (refuses a CPU); stdout: a {"report": ...} line, then last the bare {"ok", "device"} verdict
	$(PY) chip_smoke.py

bench:           ## BASELINE benchmarks on the attached chip -> one JSON line
	$(PY) bench.py

bench-smoke:     ## small-batch engine regression tripwire (~1 min, asserts budgets)
	$(PY) bench.py --smoke

mesh-smoke:      ## sharded verify engine over 8 virtual CPU devices: bit-identical verdicts vs single-device, live node must route commit verifies sharded, scaling ratio reported
	$(PY) networks/local/mesh_smoke.py --json

trace-smoke:     ## short localnet; fails unless every block has a complete propose→commit span chain
	rm -rf build-trace
	$(PY) -m tendermint_tpu.cli testnet --validators 4 --output ./build-trace --base-port 28656 --fast
	$(PY) networks/local/run_localnet.py ./build-trace --duration 8 --trace-check --json
	rm -rf build-trace

trace-net-smoke: ## 4-val localnet → dump every recorder → merged causal timeline + per-block loop attribution must be complete
	rm -rf build-tracenet
	$(PY) -m tendermint_tpu.cli testnet --validators 4 --output ./build-tracenet --base-port 28756 --fast
	$(PY) networks/local/run_localnet.py ./build-tracenet --duration 8 --dump-recorders ./build-tracenet/dumps --json
	$(PY) -m tendermint_tpu.cli trace-net ./build-tracenet/dumps/*.json --check
	rm -rf build-tracenet

statesync-smoke: ## empty 4th node joins a 3-val localnet via snapshot restore (fails on genesis replay)
	$(PY) networks/local/statesync_smoke.py --json
	rm -rf build-statesync

chaos-smoke:     ## scripted partition/kill/twin scenario on a 4-val localnet; fails on any invariant violation
	$(PY) networks/local/chaos_smoke.py --json
	rm -rf build-chaos

disk-smoke:      ## storage-fault chaos: seeded block-store bit-rot must be scan-detected, quarantined + refilled from peers; ENOSPC must halt cleanly (read path + alarm up) and recover after heal
	$(PY) networks/local/disk_smoke.py --json
	rm -rf build-disk

scale-smoke:     ## 100-validator in-proc net (engine ON, relay gossip): >=10 consecutive commits + partition/heal invariants
	$(PY) networks/local/scale_smoke.py --json

bls-smoke:       ## BLS12-381 localnet: every stored commit must be ONE aggregate signature + bitmap (C pairing tier asserted engaged when a toolchain exists); empty joiner fastsyncs over them
	$(PY) networks/local/bls_smoke.py --json
	rm -rf build-bls

rotation-smoke:  ## dynamic validator sets: staking-driven 4→7→6 growth, partition+twin across a set change, epoch barrel-shift, live ed25519→BLS migration (aggregation engages AND disengages), fastsync + lite2 bisection over the rotated history, zero checker violations
	$(PY) networks/local/rotation_smoke.py --json

bls-ext:         ## prebuild the BLS12-381 C pairing tier (.so) so suite/node runs don't pay the compile; fails without a working toolchain
	$(PY) -c "from tendermint_tpu.crypto.bls import ctier; import sys; sys.exit(0 if ctier.available() else 1)"

load-smoke:      ## tx-ingress firehose vs a QoS-configured 4-val localnet: explicit overload errors, zero checker violations, commit rate recovers
	$(PY) networks/local/load_smoke.py --json
	rm -rf build-load

lite-smoke:      ## multi-tenant light-client gateway vs a live 4-val localnet: 64 bisecting sessions off one shared engine, then an adversarial twin-signing primary gets detected, demoted, and rolled back
	$(PY) networks/local/lite_smoke.py --json
	rm -rf build-lite

forensics-smoke: ## watchdog detects an injected partition live; a SIGKILLed node's debug bundle reconstructs its pre-crash span chains from the spool, offline
	$(PY) networks/local/forensics_smoke.py --json
	rm -rf build-forensics

finality-smoke:  ## consensus-pipeline A/B: serial vs pipelined stage budgets on a 4-val localnet; pipelined commit-to-commit p50 must beat 100 ms and never regress past the serial arm
	$(PY) networks/local/finality_smoke.py --json
	rm -rf build-finality

localnet:        ## 4-validator net as OS processes (no docker)
	$(PY) -m tendermint_tpu.cli testnet --validators 4 --output ./build
	$(PY) networks/local/run_localnet.py ./build

lint:            ## syntax + import sanity over the package
	$(PY) -m compileall -q tendermint_tpu tests bench.py chip_smoke.py __graft_entry__.py

csrc:            ## force-rebuild the C host-prep extension
	rm -f tendermint_tpu/csrc/*.so
	$(PY) -c "from tendermint_tpu.crypto import hostprep; assert hostprep._load_lib()"

abci-cli:        ## serve the example kvstore app on :26658
	$(PY) -m tendermint_tpu.abci_cli kvstore

signer-harness:  ## remote signer acceptance tests (listens on :31559)
	$(PY) -m tendermint_tpu.tools.signer_harness

clean:
	rm -rf build .pytest_cache .jax_cache chiprun_out tendermint_tpu/csrc/*.so
	find . -name __pycache__ -type d -exec rm -rf {} +
