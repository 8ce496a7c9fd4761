# Build the native runtime library (C++ engine + recordio) and the
# C predict ABI (CPython-embedding deployment library).
CXX ?= g++
CXXFLAGS ?= -O3 -std=c++17 -fPIC -Wall -pthread
LIB := mxnet_tpu/_native/libmxtpu.so
SRCS := $(wildcard src/native/*.cc)
PREDICT_LIB := mxnet_tpu/_native/libmxtpu_predict.so
PREDICT_SRCS := $(wildcard src/capi/*.cc)
# deferred expansion: only runs python3-config when building $(PREDICT_LIB)
PY_INCLUDES = $(shell python3-config --includes)
PY_LDFLAGS = $(shell python3-config --ldflags --embed)
HAS_PYCONFIG := $(shell command -v python3-config 2>/dev/null)

ifeq ($(HAS_PYCONFIG),)
all: $(LIB)
	@echo "python3-config not found: skipping $(PREDICT_LIB) (needs python dev headers; build later with 'make predict')"
else
all: $(LIB) $(PREDICT_LIB)
endif

predict: $(PREDICT_LIB)

# Perl frontend (perl-package/): XS glue over the C ABI, the role the
# reference's R-package played over its C API.
PERL_SO := perl-package/blib/auto/MXNetTPU/MXNetTPU.so
PERL_CORE = $(shell perl -MConfig -e 'print $$Config{archlibexp}')/CORE
PERL_CCFLAGS = $(shell perl -MConfig -e 'print $$Config{ccflags}')

perl: $(PREDICT_LIB) $(PERL_SO)

$(PERL_SO): perl-package/MXNetTPU.xs include/mxnet_tpu/c_api.h $(PREDICT_LIB)
	@mkdir -p perl-package/blib/auto/MXNetTPU
	xsubpp -typemap $(shell perl -MConfig -e 'print $$Config{privlibexp}')/ExtUtils/typemap \
		perl-package/MXNetTPU.xs > perl-package/blib/MXNetTPU.c
	$(CC) -O2 -fPIC -shared -o $@ perl-package/blib/MXNetTPU.c \
		$(PERL_CCFLAGS) -I$(PERL_CORE) -Iinclude \
		-Lmxnet_tpu/_native -lmxtpu_predict \
		-Wl,-rpath,$(abspath mxnet_tpu/_native)

$(LIB): $(SRCS)
	@mkdir -p mxnet_tpu/_native
	$(CXX) $(CXXFLAGS) -shared -o $@ $(SRCS)

$(PREDICT_LIB): $(PREDICT_SRCS) $(wildcard include/mxnet_tpu/*.h) $(wildcard src/capi/*.h)
	@mkdir -p mxnet_tpu/_native
	$(CXX) $(CXXFLAGS) $(PY_INCLUDES) -shared -o $@ $(PREDICT_SRCS) $(PY_LDFLAGS)

test: $(LIB)
	python -m pytest tests/ -q

lint:
	python tools/graftlint.py mxnet_tpu tools bench.py chip_smoke.py \
	    --baseline tools/graftlint_baseline.json --check-env-docs

# xprof views over the newest BENCH artifacts in the repo
# root (compile registry, op-category FLOPs, HBM, device-time table)
profile-report:
	python tools/trace_report.py --profile-report

# dp-scaling smoke on 8 simulated devices: the sharded fused step
# (device_sync kvstore) measured at dp=1,2,4,8 -> MULTICHIP_scaling.json
multichip:
	python bench.py multichip

# FSDP tier on the same 8 simulated devices, mesh factored
# dp=2 x fsdp=4: per-device params+opt-state byte ratio, one-dispatch
# proof, exact-parity witness -> merged under the "fsdp" key of
# MULTICHIP_scaling.json
fsdp-bench:
	python bench.py multichip --fsdp

# continuous-batching serving tier: open-loop Poisson load swept until
# the tail-latency SLO breaks -> SERVE_bench.json (goodput, p50/p99,
# batch occupancy, zero-retrace proof)
serve-bench:
	python bench.py serve

# tensor-parallel serving tier on the same 8 simulated devices, group
# factored dp=4 x tp=2: per-device param byte ratio, the preflight
# bigger-than-one-chip proof, in-graph collectives inside the one
# dispatch, and the delta-aware weight stream -> merged under the
# "tp" key of SERVE_bench.json
tp-serve-bench:
	python bench.py serve --tp

# closed-loop kernel/config search: candidates compiled through the
# xprof registry, pruned or timed, fenced rows into
# MFU_EXPERIMENTS.jsonl, winners into .autotune_cache.json
# -> AUTOTUNE_search.json (read it with trace_report --view tune)
autotune:
	python bench.py autotune

# fault-tolerant serving fleet: goodput vs replica count, a replica
# killed mid-load (zero client-visible errors, measured recovery
# window), rolling param-swap purity with torn_swap armed
# -> FLEET_bench.json (read it with trace_report --view fleet)
fleet-bench:
	python bench.py fleet

# socket transport: the fleet bench's network tier — zero-copy frame
# codec vs pickle, socket-vs-pipe p99 overhead, chaos over TCP
# (net_drop/net_partition/net_reorder armed, zero client errors), and
# the 2-process netfeed epoch -> the "socket" record in
# FLEET_bench.json (read it with trace_report --view wire)
net-bench:
	python bench.py fleet --smoke
	python tools/trace_report.py --view wire

# distributed-tracing smoke: the fleet bench (smoke profile) with the
# tracer armed must produce a loadable merged chrome trace holding at
# least one kept span tree -> FLEET_trace.json (read it with
# trace_report --view waterfall, or load it in Perfetto)
trace-smoke:
	MXNET_TPU_DTRACE=1 python bench.py fleet --smoke
	python -c "import json; d=json.load(open('FLEET_trace.json')); \
	evs=[e for e in d['traceEvents'] if e.get('cat')=='dtrace']; \
	assert evs, 'no dtrace events in FLEET_trace.json'; \
	print('FLEET_trace.json ok: %d dtrace events' % len(evs))"

# preemption-safety suite: crash-safe writes, torn-file detection,
# bit-identical kill-at-step-k resume, elastic dp rejoin, SIGTERM grace
ckpt-test:
	python -m pytest tests/test_checkpoint.py tests/test_elastic_recovery.py -q

# numerics observability suite: the in-graph stats pack (one dispatch,
# one trace signature), NaN provenance, skip/rollback guards, detector
# wiring, the disabled-path overhead pin, and the numerics report view
numwatch-test:
	python -m pytest tests/test_numwatch.py -q

# perf-regression gate: current bench artifacts (SERVE / FLEET / OBS /
# MULTICHIP, plus the BENCH_r* trajectory) vs tools/bench_baselines.json.
# Exit 1 names the regressed metric, artifact, and measured delta;
# missing artifacts are INCOMPLETE (exit 0) -> BENCH_GATE.json
bench-gate:
	python tools/bench_gate.py

# observability gate: lint the new surface, run the obswatch + gate
# test files, then the regression gate itself, recording the verdict
# into PROGRESS.jsonl so the growth log carries pass/fail history
obs-gate: lint
	python -m pytest tests/test_obswatch.py tests/test_bench_gate.py \
	    tests/test_telemetry.py -q
	python tools/bench_gate.py --progress PROGRESS.jsonl

clean:
	rm -rf mxnet_tpu/_native perl-package/blib

.PHONY: all predict perl test lint profile-report multichip fsdp-bench serve-bench tp-serve-bench fleet-bench net-bench trace-smoke ckpt-test numwatch-test bench-gate obs-gate clean
