# Canonical entry points (parity: the reference's make targets +
# tools/pip_package).  Native C++ compiles lazily at import; `make
# native` just forces it ahead of time.

PY ?= python
# 4 xdist workers when pytest-xdist is installed (~12 min full suite vs
# ~35 serial); empty otherwise so bare environments still run
XDIST := $(shell $(PY) -c "import xdist" 2>/dev/null && printf -- "-n 4")

.PHONY: test fast chip bench-tests wheel sdist native clean lint

test: lint       ## full suite (~14 min with 4 xdist workers)
	$(PY) -m pytest tests/ -q $(XDIST)

fast: lint       ## <5-minute iteration tier
	$(PY) -m pytest tests/ -q -m fast $(XDIST)

lint:            ## graftlint + concurrency model: fail on NEW findings only
	$(PY) tools/graftcheck.py mxnet_tpu --concurrency \
		--baseline .graftlint-baseline.json

chip:            ## serial accelerator tier (needs the real chip; from the sandbox: chiprun -- make chip)
	MXTPU_CHIP_TESTS=1 $(PY) -m pytest tests/test_consistency_sweep.py \
		tests/test_consistency.py tests/test_convergence.py \
		tests/test_pallas.py -q --numprocesses 0
	# the COMPILED Pallas kernels against XLA's TPU programs (the other
	# tests of that module pin cpu-context contracts)
	MXTPU_CHIP_TESTS=1 $(PY) -m pytest tests/test_pallas_kernels.py -q \
		--numprocesses 0 -k "pool_backward or bn_"

bench-tests:     ## the benchmark's own tests (the benchmark itself: python3 -m benchmark.run --workload <cell>, on the chip)
	$(PY) -m pytest benchmark/tests -q

wheel:
	$(PY) -m pip wheel . --no-build-isolation --no-deps -w dist/

sdist:
	$(PY) setup.py -q sdist

native:          ## force-build the lazy C++ libraries now
	$(PY) -c "from mxnet_tpu import io_native as n; \
	          print(n.get_lib()); print(n.get_capi_lib())"

clean:
	rm -rf build dist *.egg-info mxnet_tpu/_native \
	       mxnet_tpu/io_native/*.so
