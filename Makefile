# Build / toolchain layer (reference Makefile:1-4 had `build` compiling
# main.c with ASan and `clean` removing the binary).  Here `build`
# compiles the native host runtime ahead of time (it otherwise builds
# lazily on first use), and the reference's manual run-then-diff
# workflow is replaced by real targets.

PY ?= python

.PHONY: build lint test test-fast test-lint test-faults test-parallel test-spill test-chaos test-chaos-all test-wal test-qos test-serve test-serve-device test-daemon test-obs test-segments test-attrib test-cluster test-native-asan test-native-ubsan clean clean-native

build:
	$(PY) -c "from parallel_computation_of_an_inverted_index_using_map_reduce_tpu import native; \
	          assert native.available(), 'native build failed'; print('native runtime built')"

# repo-contract static analysis (tools/mrilint): exit 0 means clean
# against the checked-in shrink-only baseline
lint:
	$(PY) -m tools.mrilint

test:
	$(PY) -m pytest tests/ -q

# Tier-1 selection (-m "not slow") — includes the fast `parallel_host`
# multi-worker map/reduce tests — parallelized over workers when
# pytest-xdist is installed (falls back to a serial run when not —
# the verify pipeline's own serial invocation is untouched)
test-fast: lint
	$(PY) -m pytest tests/ -q -m "not slow" \
	  $$($(PY) -c "import importlib.util as u; print('-n auto' if u.find_spec('xdist') else '')")
	$(PY) tools/chaos.py --all --fast

# mrilint's own suite: checker semantics on planted fixtures under
# tests/fixtures/lint/ plus the repo-clean gate
test-lint:
	$(PY) -m pytest tests/ -q -m lint

# failure-semantics suite only: fault injection, retry/skip policy,
# crash-safe resume (tests marked `faults`)
test-faults:
	$(PY) -m pytest tests/ -q -m faults

# multi-worker host map/reduce suite only (steal queue, (K, M)
# byte-identity matrix, letter-partitioned reduce)
test-parallel:
	$(PY) -m pytest tests/ -q -m parallel_host

# out-of-core build suite: spill container integrity, shard-merge
# algebra, (shards, budget, K, M) byte-identity matrix, quarantine /
# takeover degradation, SIGKILL-at-spill-boundary resume
test-spill:
	$(PY) -m pytest tests/ -q -m spill

# chaos suite: the fast matrix cycle runs in tier-1 (`chaos and not
# slow`); this target adds the full 50+-trial seeded soaks (build
# matrix, daemon scenarios, segments schedules, and the --wal
# durability/replication sweep)
test-chaos:
	$(PY) -m pytest tests/ -q -m chaos

# every chaos mode off the `tools/chaos.py --list` registry, full
# trial counts, one process per mode; a new mode added to the registry
# is picked up here with no Makefile edit.  `--fast` (the test-fast
# cycle) runs the same sweep at reduced trials/deadlines
test-chaos-all:
	$(PY) tools/chaos.py --all

# durability suite: WAL container integrity, torn-tail quarantine,
# crash replay (incl. SIGKILL during a buffered tombstone batch),
# lease semantics, segment-shipping replica catch-up + rollback refusal
test-wal:
	$(PY) -m pytest tests/ -q -m wal

# multi-tenant QoS + result-cache suite: generation-keyed cache
# byte-identity/invalidation, LRU byte accounting, token-bucket
# admission, weighted-fair dequeue, per-tenant stats/flightdump/top
test-qos:
	$(PY) -m pytest tests/ -q -m qos

# query-serving suite: index.mri format + Engine parity vs a naive text
# scan, artifact corruption rejection, LRU cache semantics
test-serve:
	$(PY) -m pytest tests/ -q -m serve

# device query-engine suite: host/device byte parity (batches 1..8192),
# shared-prefix fixup, zero-recompile steady state — forced onto the
# jax cpu backend so it runs on any box (the same code path serves
# accelerators)
test-serve-device:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m device_serve

# the sanitizer suite targets the native C++ runtime: every native /
# tokenizer / emit test plus the oracle conformance check.  Tests that
# jit through jax are excluded under ASan only — its __cxa_throw
# interceptor aborts inside jaxlib's bundled MLIR bindings (a toolchain
# clash, not a bug in this code) — and kept out of the ubsan run too so
# both targets certify the same selection.
NATIVE_SAN_TESTS = tests/test_native.py tests/test_native_serve.py \
  tests/test_tokenizer.py \
  tests/test_emit_backend.py tests/test_conformance.py
NATIVE_SAN_K = not tpu and not single_chip and not numpy_tokenizer \
  and not backends_agree and not degenerate_configs

# native tokenizer under AddressSanitizer: MRI_NATIVE_SANITIZE=asan
# compiles a separately-tagged .so (never shadows the production one)
# and the runtime loads it.  libasan must be first in the process, so
# it is LD_PRELOADed into the python interpreter; leak checking is off
# because the long-lived interpreter never frees everything at exit.
test-native-asan:
	LD_PRELOAD=$$(g++ -print-file-name=libasan.so) \
	ASAN_OPTIONS=detect_leaks=0 \
	MRI_NATIVE_SANITIZE=asan JAX_PLATFORMS=cpu \
	$(PY) -m pytest $(NATIVE_SAN_TESTS) -q -m "not slow" -k "$(NATIVE_SAN_K)"

# same suite under UndefinedBehaviorSanitizer (traps on UB, no preload
# needed — libubsan is a direct dependency of the tagged .so)
test-native-ubsan:
	MRI_NATIVE_SANITIZE=ubsan JAX_PLATFORMS=cpu \
	$(PY) -m pytest $(NATIVE_SAN_TESTS) -q -m "not slow" -k "$(NATIVE_SAN_K)"

# resident serve-daemon suite: JSON-lines protocol parity, admission
# control / load shedding, deadlines, graceful drain, crash-safe hot
# reload, serve-side chaos trials; none are `slow`, so the default
# `make test-fast` sweep runs them too
test-daemon:
	$(PY) -m pytest tests/ -q -m daemon

# observability layer (obs/): metrics registry semantics, Prometheus
# exposition parity with the legacy stats op, request tracing over the
# wire, slow-query log, Chrome-trace build export; none are `slow`, so
# the default `make test-fast` sweep runs them too
test-obs:
	$(PY) -m pytest tests/ -q -m obs

# incremental indexing (segments/): manifest + tombstone integrity,
# append/delete/compact lifecycle, multi-segment byte-identity vs a
# from-scratch build, fault kinds, CLI + daemon admin surfaces
test-segments:
	$(PY) -m pytest tests/ -q -m segments

# scale-out serving suite (cluster/): corpus partitioner invariants,
# D-way gather merge byte-identity (incl. the D in {1,2,4,8} fuzz vs a
# monolithic build), router failover / hedging / deadline semantics
# against live shard daemons; none are `slow`, so the default
# `make test-fast` sweep runs them too
test-cluster:
	$(PY) -m pytest tests/ -q -m cluster

# query-cost attribution suite: per-request EXPLAIN reports vs registry
# counter parity (host/device/multi-segment), daemon explain + flight
# recorder dumps, OpenMetrics exemplars, trace-coverage checker; none
# are `slow`, so the default `make test-fast` sweep runs them too
test-attrib: lint
	$(PY) -m pytest tests/ -q -m attrib

# drop every hashed native build artifact — production AND sanitizer
# variants, in both the in-tree dir and the /tmp fallback (stale .so
# files of the same variant are also auto-pruned on every rebuild).
# The serve kernels (mri_serve_*) live in the same tagged .so as the
# build-path symbols, so one sweep covers both API families.
clean-native:
	rm -rf parallel_computation_of_an_inverted_index_using_map_reduce_tpu/native/_build
	rm -rf /tmp/mri_tpu_native_$$(id -u)

clean: clean-native
	find . -name __pycache__ -type d -prune -exec rm -rf {} +
