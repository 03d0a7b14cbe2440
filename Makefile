# Build/test entry points for the mxtpu native runtime and test suite.
# The analogue of the reference's ci/docker/runtime_functions.sh build
# configs, including the sanitizer builds (ref sanitizer/asan profiles).
#
#   make native       release libmxtpu.so (what mxnet_tpu._native builds JIT)
#   make native-test  plain native unit-test binary + run
#   make asan         native tests under AddressSanitizer
#   make tsan         native tests under ThreadSanitizer
#   make test         python suite on the 8-device virtual CPU mesh
#   make ci           everything CI runs

CXX      ?= g++
CXXFLAGS ?= -std=c++17 -O2 -fPIC -Wall -pthread
SRC      := $(wildcard src/mxtpu/*.cc)
TESTSRC  := src/mxtpu/tests/test_native.cc
BUILD    := build

.PHONY: native native-test asan tsan test test-par test-slow test-all \
	telemetry-smoke pipeline-smoke chaos-smoke warmup-smoke spmd-smoke \
	trace-smoke kernels-smoke serve-smoke decode-smoke disagg-smoke \
	obs-smoke fleet-smoke lint-hybrid lint-threads lint-graph ci clean

native: $(BUILD)/libmxtpu.so

$(BUILD)/libmxtpu.so: $(SRC) src/mxtpu/engine.h
	@mkdir -p $(BUILD)
	$(CXX) $(CXXFLAGS) -shared -o $@ $(SRC)

$(BUILD)/test_native: $(SRC) $(TESTSRC) src/mxtpu/engine.h
	@mkdir -p $(BUILD)
	$(CXX) $(CXXFLAGS) -o $@ $(SRC) $(TESTSRC)

native-test: $(BUILD)/test_native
	$(BUILD)/test_native

$(BUILD)/test_native_asan: $(SRC) $(TESTSRC) src/mxtpu/engine.h
	@mkdir -p $(BUILD)
	$(CXX) $(CXXFLAGS) -O1 -g -fsanitize=address -fno-omit-frame-pointer \
		-o $@ $(SRC) $(TESTSRC)

asan: $(BUILD)/test_native_asan
	ASAN_OPTIONS=detect_leaks=1 $(BUILD)/test_native_asan

$(BUILD)/test_native_tsan: $(SRC) $(TESTSRC) src/mxtpu/engine.h
	@mkdir -p $(BUILD)
	$(CXX) $(CXXFLAGS) -O1 -g -fsanitize=thread -fno-omit-frame-pointer \
		-o $@ $(SRC) $(TESTSRC)

tsan: $(BUILD)/test_native_tsan
	$(BUILD)/test_native_tsan

test:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not slow"

test-par:
	# multi-core boxes: same fast suite, one worker per core, file-level
	# isolation (verified green under xdist loadfile). Wall time is
	# recorded so the <10-min budget is a checked fact (CI uploads it).
	@start=$$(date +%s); \
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not slow" \
		-n auto --dist loadfile; rc=$$?; \
	secs=$$(( $$(date +%s) - start )); \
	echo "test-par wall time: $${secs}s" | tee test-par-timing.txt; \
	exit $$rc

test-slow:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m slow

test-all:
	env JAX_PLATFORMS=cpu python -m pytest tests/ -q

telemetry-smoke:
	# 20 instrumented LeNet train steps; fails unless the core telemetry
	# metrics tick and land in telemetry.json (docs/telemetry.md)
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		python tools/telemetry_smoke.py

pipeline-smoke:
	# 20 LeNet steps through DataLoader -> DevicePrefetcher ->
	# ShardedTrainer; fails unless the transfers moved off the training
	# thread and in-flight depth exceeds 1; both phases' wait p50 are
	# reported, not gated (docs/pipeline.md)
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		python tools/pipeline_smoke.py

chaos-smoke:
	# short LeNet loop under MXNET_FAULT_INJECT: barrier + dataloader +
	# checkpoint faults injected; fails unless every recovery path holds
	# and the crash->resume run matches bit-for-bit — plus the elastic
	# reshape-resume case: heartbeat loss on an 8-device zero1 mesh,
	# migrate to 4, trajectory matches uninterrupted (docs/resilience.md)
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		python tools/chaos_smoke.py

warmup-smoke:
	# persistent-compile-cache gate: the same LeNet workload in two fresh
	# processes sharing one cache dir; fails unless the cold process
	# filled the cache, the warm one had persistent-cache hits > 0 and
	# both computed the same loss; compile wall times are reported, not
	# gated (docs/jit.md)
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		python tools/warmup_smoke.py

spmd-smoke:
	# 2-D/3-D mesh gate: LeNet (8x1) zero1 must match replicated to few
	# ULP over 20 steps with opt-state bytes/device <= replicated/dp
	# x 1.1; tiny-BERT must train mp=2 tensor-sharded + zero1 on a 4x2
	# mesh matching the replicated run; overlap=True (bucketed flush)
	# must match over 12 steps for sgd AND momentum; pp=2 GPipe windows
	# must match over 20 windows with the exact bubble gauge; and the
	# dp x mp x pp 2x2x2 composition must match with ZERO post-warmup
	# jit compiles (docs/sharding.md).  Serial — single-core box, never
	# concurrent with tier-1.
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		python tools/spmd_smoke.py

trace-smoke:
	# mx.trace gate: 20 LeNet steps through the instrumented stack must
	# export a parseable Perfetto JSON with spans from >=6 subsystems at
	# <=5% trace-on overhead, and a forced dist.barrier fault must leave
	# a flight-recorder dump on disk (docs/tracing.md).  Serial —
	# single-core box, never concurrent with tier-1.
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		MXNET_TRACE=1 python tools/trace_smoke.py

kernels-smoke:
	# mx.kernels gate: tiny-BERT must train through the pallas-interpret
	# flash attention fwd+bwd matching the kernels-off run, the flat-arena
	# optimizer step HLO must carry no per-leaf concatenate/stack of
	# params, and a CPU-relative bench delta is recorded to
	# kernels_smoke.json (docs/kernels.md).  Serial — single-core box,
	# never concurrent with tier-1.
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		python tools/kernels_smoke.py

serve-smoke:
	# mx.serve gate: a LeNet + tiny-BERT registry AOT-warmed over the
	# bucket grids must serve N concurrent ragged requests with ZERO
	# compiles, batched throughput >= 2x sequential dispatch, e2e p99
	# under bound, and a forced queue overflow must shed (503) at least
	# one request (docs/serving.md).  Serial — single-core box, never
	# concurrent with tier-1.
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		MXNET_THREAD_CHECK=raise python tools/serve_smoke.py

decode-smoke:
	# generative decode gate: a tiny transformer-LM DecodeEntry AOT-warmed
	# over the prefill/step/slot-write/growth grid must serve N prompts
	# with ZERO compiles across >=2 capacity buckets and >=2 occupancies,
	# token-level batched decode >= 2x sequential tokens/s, per-token step
	# p99 under bound, and the donated KV cache must lint X004-clean AND
	# observably alias (docs/serving.md "Decode lifecycle").  Serial —
	# single-core box, never concurrent with tier-1.
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		MXNET_THREAD_CHECK=raise python tools/decode_smoke.py

disagg-smoke:
	# disaggregated prefill/decode gate (docs/serving.md): the same mixed
	# long-prompt/short-decode open-loop workload through a unified and a
	# prefill-pooled server — the two must emit the same greedy tokens,
	# prefix-cache hits must skip serve.prefill_seconds entirely with
	# bit-exact greedy outputs, ZERO compiles after warmup on both
	# pools, xlalint-clean, and no mx-* thread may survive close(); TTFT
	# p99s and tokens/s are reported, not gated.  Serial — single-core
	# box, never concurrent with tier-1.
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		MXNET_THREAD_CHECK=raise python tools/disagg_smoke.py

obs-smoke:
	# mx.obs gate: LeNet served with the metrics endpoint armed — a
	# second thread scraping /metrics + /statusz mid-load gets all
	# 200s, the windowed histogram count equals the telemetry timer
	# count at quiesce, and two real worker processes aggregate into
	# one fleet view with EXACT merged counts + a dead URL only flagged,
	# never raised; the obs-on over obs-off wall time is reported, not
	# gated (docs/obs.md).  Serial — single-core box, never concurrent
	# with tier-1.
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		MXNET_OBS=1 MXNET_THREAD_CHECK=raise python tools/obs_smoke.py

fleet-smoke:
	# network edge + elastic fleet gate (docs/serving.md "Network edge
	# + fleet"): N worker replicas behind the router answer every
	# admitted request of a concurrent load (RPS reported, not gated); a
	# SIGKILLed replica under load loses ZERO admitted requests, is
	# respawned from the persistent compile cache (hits > 0 in its READY
	# announcement) with the recovery time recorded; SSE streaming
	# delivers tokens incrementally and bit-exact vs in-process greedy;
	# fleet.dispatch chaos at p=0.5 is absorbed by the retry path; and
	# zero post-warmup compiles per replica.  Serial — single-core box,
	# never concurrent with tier-1.
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		MXNET_OBS=1 MXNET_THREAD_CHECK=raise python tools/fleet_smoke.py

lint-hybrid:
	# hybridize-safety static analysis (docs/analysis.md). The committed
	# baseline makes legacy suppressions explicit; NEW violations fail.
	# mxlint loads mx.analysis standalone (no jax import): sub-second.
	python tools/mxlint.py --format=json \
		--baseline tools/mxlint_baseline.json \
		mxnet_tpu example tools

lint-threads:
	# concurrency lint (docs/analysis.md T rules): lock/thread model of
	# the serving tier — inversions, blocking under locks, unjoined
	# threads.  Loads mx.analysis standalone (no jax import): sub-second.
	python tools/threadlint.py --format=json \
		--baseline tools/threadlint_baseline.json \
		mxnet_tpu tools

lint-graph:
	# XLA executable lint (docs/analysis.md X rules): compiles the
	# canonical models on CPU and gates their HLO against the per-model
	# budgets in tools/xlalint_budgets.json (surprise collectives, arena
	# concatenate bound, zero1 opt-state placement, unaliased donations,
	# f64 leaks, host callbacks, async_required collectives appearing in
	# blocking form — X007, overlap model).  Budget drift re-baselines via
	# tools/xlalint.py --update-budgets.  Serial — single-core box,
	# never concurrent with tier-1.
	env JAX_PLATFORMS=cpu MXNET_TELEMETRY=1 \
		python tools/xlalint.py

ci: native native-test asan tsan lint-hybrid lint-threads lint-graph \
	test test-slow \
	telemetry-smoke pipeline-smoke chaos-smoke warmup-smoke spmd-smoke \
	trace-smoke kernels-smoke serve-smoke decode-smoke disagg-smoke \
	obs-smoke fleet-smoke

clean:
	rm -rf $(BUILD)
