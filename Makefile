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
	python tools/graftlint.py mxnet_tpu tools chip_smoke.py \
	    --baseline tools/graftlint_baseline.json --check-env-docs

# preemption-safety suite: crash-safe writes, torn-file detection,
# bit-identical kill-at-step-k resume, elastic dp rejoin, SIGTERM grace
ckpt-test:
	python -m pytest tests/test_checkpoint.py tests/test_elastic_recovery.py -q

# numerics observability suite: the in-graph stats pack (one dispatch,
# one trace signature), NaN provenance, skip/rollback guards, detector
# wiring, the disabled-path overhead pin, and the numerics report view
numwatch-test:
	python -m pytest tests/test_numwatch.py -q

# observability gate: lint the surface, then the obswatch and
# telemetry test files
obs-gate: lint
	python -m pytest tests/test_obswatch.py tests/test_telemetry.py -q

clean:
	rm -rf mxnet_tpu/_native perl-package/blib

.PHONY: all predict perl test lint ckpt-test numwatch-test obs-gate clean
