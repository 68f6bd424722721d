BUILD_DIR := native/build

.PHONY: native test soak asan tsan test-asan test-tsan tsan-test asan-test contract-check lint lint-sarif obs-smoke serve-smoke serving-fleet-smoke spec-smoke paged-smoke train-smoke collectives-smoke clean

native:
	cmake -S native -B $(BUILD_DIR) -G Ninja -DCMAKE_BUILD_TYPE=RelWithDebInfo >/dev/null
	cmake --build $(BUILD_DIR)

# Static analysis (tools/tpulint): fiber-safety, lock-order, IOBuf
# ownership, tidl wire-contract drift, metric hygiene, Python handler
# blocking. Pure CPython, no native toolchain needed — this is the half of
# the safety story that runs where test-asan/test-tsan (the dynamic half)
# cannot. Non-zero exit on any finding not justified by an inline
# `tpulint: allow(...)` or grandfathered in tools/tpulint/baseline.json.
lint:
	python -m tools.tpulint

lint-sarif:
	python -m tools.tpulint --format sarif > tpulint.sarif

# The contract half of lint on its own: the cross-language locks
# (wire_contract.lock incl. __capi__/__meta_keys__/__codes__,
# error_codes.lock, sanitizer_suppressions.lock) plus the negotiation /
# state-machine / arena-alias dataflow rules. `make lint` already runs
# all of it; this target exists so the smoke gates can name the contract
# guarantee explicitly and so CI logs show WHICH half failed.
contract-check:
	python -m tools.tpulint --no-baseline brpc_tpu examples
	python -m tools.tpulint

# Fast local gate for the fleet observability plane: the cross-process
# trace-assembly + /fleetz scrape + sampling tests, then lint. The pure
# assembly/skew tests run even without the native library; the
# live-fleet halves skip cleanly there.
obs-smoke:
	python -m pytest tests/test_fleet_view.py -q
	$(MAKE) --no-print-directory contract-check

# Fast local gate for the serving plane (the obs-smoke analog): the
# session/scheduler units + the live streamed-decode tests, then lint.
# The pure halves run even without the native library; the native halves
# skip cleanly there.
serve-smoke:
	python -m pytest tests/test_serving.py -q
	$(MAKE) --no-print-directory contract-check

# Fast local gate for the serving FLEET plane (the serve-smoke analog
# one level up): routing determinism, migration/paging round trips, and
# — with the native lib present — the live drain-migration parity,
# prefill/decode split and /fleetz serving-column tests, then lint.
# The pure halves run even without the native library.
serving-fleet-smoke:
	python -m pytest tests/test_serving_fleet.py -q
	$(MAKE) --no-print-directory contract-check

# Fast local gate for speculative decoding (the serve-smoke analog):
# the verify-window bitwise-parity pin, spec==plain engine parity
# (n-gram + model drafts, adversarial k-clamp), migration/prefill
# parity with speculation on both ends, then lint. The native halves
# (streamed A/B, live drain, /fleetz accept columns) skip cleanly
# without the lib.
spec-smoke:
	python -m pytest tests/test_spec_decode.py -q
	$(MAKE) --no-print-directory contract-check

# Fast local gate for the paged KV plane (the spec-smoke analog): the
# block pool accounting units, paged==monolithic token-parity pins
# (single/batched/spec-on, across spill and migration), CoW shared-
# prefix behavior, then lint (incl. the block-account rule). The native
# halves (armed-watchdog server drives, /fleetz prefix-hit columns,
# slim-migration byte pins) skip cleanly without the lib.
paged-smoke:
	python -m pytest tests/test_paged_kv.py -q
	$(MAKE) --no-print-directory contract-check

# Fast local gate for the overlapped training step (the obs-smoke
# analog): the pure scheduler units (topology, failure propagation,
# serial==overlapped equivalence) plus — with the native lib present —
# the overlapped-vs-serial parity drive over a live ParameterServer,
# then lint. The native halves skip cleanly without the lib. The
# parallelism-regime halves ride along: the 1F1B schedule math +
# graph-builder units, thread-pipe PP trajectory parity, and the
# tensor-parallel layer wrappers (all tier-1-pure; the WirePipe native
# test skips cleanly without the lib).
train-smoke:
	python -m pytest tests/test_step_overlap.py tests/test_pp_sched.py \
		tests/test_tp_layers.py -q
	$(MAKE) --no-print-directory contract-check

# Fast local gate for the fleet-collectives plane (the obs-smoke
# analog): the pure schedule/codec/EF/salvage units plus — with the
# native lib present — the live ring/tree drives, PushQ parity and the
# collective step driver, then lint. The native halves skip cleanly
# without the lib.
collectives-smoke:
	python -m pytest tests/test_collectives.py -q
	$(MAKE) --no-print-directory contract-check

# Slow-marked tests (the watchdog soak) are excluded here, same as
# tier-1; run them explicitly with `make soak`.
test: native
	python -m pytest tests/ -x -q -m 'not slow'

# Watchdog soak: repeated async pull_all/push_all bursts over tpu:// with
# the stall watchdog armed. Fails if health ever reaches `stalled`
# WITHOUT a dump artifact (a hang the framework cannot explain); a wedge
# WITH forensics is a captured finding. SOAK_SECONDS=N scales the run.
soak: native
	python -m pytest tests/test_soak.py -q -m slow

# Sanitizer trees. The fiber runtime carries the required annotations
# (tbthread/sanitizer_fiber.h): ASan gets start/finish_switch_fiber around
# every context jump; TSan gets per-fiber contexts + switch notifications,
# making -fsanitize=thread usable for real race hunting over fibers.
asan:
	cmake -S native -B native/build-asan -G Ninja -DCMAKE_BUILD_TYPE=RelWithDebInfo \
	  -DCMAKE_CXX_FLAGS="-fsanitize=address -fno-omit-frame-pointer" \
	  -DCMAKE_EXE_LINKER_FLAGS="-fsanitize=address" >/dev/null
	cmake --build native/build-asan

tsan:
	cmake -S native -B native/build-tsan -G Ninja -DCMAKE_BUILD_TYPE=RelWithDebInfo \
	  -DCMAKE_CXX_FLAGS="-fsanitize=thread -fno-omit-frame-pointer" \
	  -DCMAKE_CXX_FLAGS_RELWITHDEBINFO="-O1 -g -DNDEBUG" \
	  -DCMAKE_EXE_LINKER_FLAGS="-fsanitize=thread" >/dev/null
	cmake --build native/build-tsan

# Run the native suite against the sanitizer trees (slow; keeps the
# "TSan-clean" claim enforced rather than aspirational).
test-asan: asan
	cd native/build-asan && ctest -j1 --output-on-failure

test-tsan: tsan
	cd native/build-tsan && ctest -j1 --output-on-failure

# Preset-driven sanitizer gates (the -DTPU_SANITIZE=thread|address path
# through native/CMakeLists.txt) with the pinned suppression files
# applied. Skips cleanly — exit 0 with a SKIPPED line — where the native
# toolchain is absent (tier-1 CI guarantees CPython only), same contract
# as the smoke targets' native halves.
tsan-test:
	@if ! command -v cmake >/dev/null 2>&1; then \
	  echo "tsan-test: SKIPPED (cmake not found; tier-1 is CPython-only)"; \
	else \
	  cmake -S native -B native/build-tsan -DTPU_SANITIZE=thread >/dev/null && \
	  cmake --build native/build-tsan -j && \
	  cd native/build-tsan && \
	  TSAN_OPTIONS="suppressions=$(CURDIR)/native/sanitizers/tsan.supp" \
	    ctest -j1 --output-on-failure; \
	fi

asan-test:
	@if ! command -v cmake >/dev/null 2>&1; then \
	  echo "asan-test: SKIPPED (cmake not found; tier-1 is CPython-only)"; \
	else \
	  cmake -S native -B native/build-asan -DTPU_SANITIZE=address >/dev/null && \
	  cmake --build native/build-asan -j && \
	  cd native/build-asan && \
	  ASAN_OPTIONS="suppressions=$(CURDIR)/native/sanitizers/asan.supp" \
	  LSAN_OPTIONS="suppressions=$(CURDIR)/native/sanitizers/lsan.supp" \
	    ctest -j1 --output-on-failure; \
	fi

clean:
	rm -rf $(BUILD_DIR) native/build-asan native/build-tsan
