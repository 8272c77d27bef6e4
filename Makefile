# Test tiers (CI mirror; reference CI = `go test -v ./...`,
# .circleci/config.yml:26-28 — here split so the fast tier stays minutes-fast
# on one core even with a cold XLA compile cache).
#
# Measured on this image's single core: the pre-split full tier (fast +
# kernel modules) ran 181 tests in 54:21 with a warm compile cache —
# XLA-compile-bound, not runtime-bound — so the JAX kernel modules
# (test_{fp,tower,curve,pairing,bls12_381}_jax, test_bn254_device)
# are slow-tier: nightly/CI coverage via test-slow/test-all.
# The fast tier keeps the pure-Python curve oracles, the full protocol/
# sim/transport planes, and the 8-device sharding guards — measured
# post-split: 135 tests in 2:00 on the same core (warm cache), restoring
# the minutes-fast contract.

PY ?= python

.PHONY: test test-fast test-slow test-all dryrun

# fast tier: protocol + transports + sim harness + oracle + sharding guards
test-fast:
	$(PY) -m pytest tests/ -x -q

# compile-heavy + reference-scale tier: JAX kernel modules, 333-node
# failures, 37-node real crypto, BLS12-381 e2e
test-slow:
	$(PY) -m pytest tests/ -x -q -m slow

test-all:
	$(PY) -m pytest tests/ -x -q -m ""

test: test-fast

dryrun:
	GRAFT_DRYRUN_DEVICES=8 $(PY) __graft_entry__.py
