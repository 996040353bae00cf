# Convenience targets (see README.md).  Everything runs from source via
# PYTHONPATH=src; no install step.

PY ?= python
export PYTHONPATH := src:$(PYTHONPATH)

# Benchmark-run environment: tcmalloc preloaded when present (allocator
# jitter is visible in realized `*/wall` rows on shared cores).  The
# device tier needs no 64-bit mode: its kernels use explicit
# uint32/int32/f32, and the decode and skim tests pin bit-identity
# without it.
TCMALLOC := $(firstword $(wildcard /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so* \
	/usr/lib/x86_64-linux-gnu/libtcmalloc.so* /usr/lib/libtcmalloc_minimal.so*))
BENCH_ENV := $(if $(TCMALLOC),LD_PRELOAD=$(TCMALLOC))

.PHONY: test bench smoke chaos lint quickstart

test:  ## tier-1 suite
	$(PY) -m pytest -x -q

bench:  ## full benchmark harness (CSV on stdout)
	PYTHONPATH=src:. $(BENCH_ENV) $(PY) benchmarks/run.py

smoke:  ## fast benchmark smoke (executor + cluster + pruning + expr + cascade + device + service + obs + faults; the CI step).  Emits BENCH_<pr>.json + BENCH_<pr>_trace.json.
	PYTHONPATH=src:. $(BENCH_ENV) $(PY) benchmarks/run.py --smoke --json \
		--only pipeline,cluster,prune,expr,cascade,device,service,obs,faults

chaos:  ## seeded fault-injection sweep (tests/test_chaos.py)
	$(PY) -m pytest -q -m chaos tests/test_chaos.py

lint:  ## style/correctness lint (pip install -r requirements-dev.txt)
	ruff check src tests benchmarks examples tools
	$(PY) -m tools.skimlint src/repro --self-test --verify-fixtures
	$(PY) tools/check_extras.py

quickstart:
	$(PY) examples/quickstart.py
