# Canonical entry points (parity: the reference's make targets +
# tools/pip_package).  Native C++ compiles lazily at import; `make
# native` just forces it ahead of time.

PY ?= python
# 4 xdist workers when pytest-xdist is installed (~12 min full suite vs
# ~35 serial); empty otherwise so bare environments still run
XDIST := $(shell $(PY) -c "import xdist" 2>/dev/null && printf -- "-n 4")

.PHONY: test fast chip bench bench-smoke wheel sdist native clean lint

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

bench:           ## throughput numbers of record (run on an IDLE host)
	$(PY) bench.py

bench-smoke:     ## exec-cache + observability + serving + fleet-SLO + health + io-pipeline + pallas-kernel + memprof + comm + coldstart + autotune + elastic smoke: dumps /tmp/mxnet_tpu_smoke_{trace,telemetry}.json + flight dumps + a memory report + COLDSTART_r07.json, fails on recompile regressions (incl. telemetry/health/pipeline/memprof on-vs-off, the serving warmup contract, the paged-KV decode contract: open-loop transformer decode with zero steady-state retraces incl. mid-traffic COW, every stream bitwise-equal to solo decode, the prefix-cache hit ratio asserted on a shared-prompt phase plus a tokens/s + decode-MFU row, pipeline starvation vs the measured in-memory baseline, the kernel-flag <=1-retrace/off-path-untouched contract, the recompile_cause explainer, the OOM black box, the comm contracts: bucketed-overlap parity + >=2 interleaved all-reduces + the 2-bit <=1/8-wire-bytes assert on the 8-device harness, the persistent program cache's warm-replica contract: zero retraces + zero backend compiles + bitwise outputs + >=5x time-to-serving in fresh subprocesses, the autotune loop: traffic-shaped serving buckets cut padded rows >=30% with zero steady-state retraces, the comm tuner converges within its <=4-retrace budget, traceview --tuning parses the decision log from a flight dump, the request-tracing loop: every SLO-breaching/shed request tail-captured into the flight requests ring with a complete fleet waterfall, segments explaining >=90% of tail latency, the sampled ring under its byte cap, a subprocess worker inheriting the env-propagated trace root, traceview --requests/--fleet rc 0, and zero added retraces, and the elastic loop: kill a dp=8 worker at step 22 under a chaos plan, corrupt the newest checkpoint, resume from step 15 with final params BITWISE-equal to the uninterrupted run and zero backend compiles on the warm resume, plus a dp=4 re-factorized resume training to allclose params, and the locksan legs: the serving storm and the dp=8 warm resume re-run under MXNET_TPU_LOCKSAN=1 with zero lock-order/dispatch violations, zero added retraces, bitwise outputs, and the health plane: the time-series sampler + env-declared SLO burn-rate rule provably firing under the 2x+burst overload and resolving on calm traffic, transitions in the flight alerts ring, traceview --dash/--alerts rc 0, sampling bitwise-off when unset and retrace-free when on)
	$(PY) bench.py --smoke
	$(PY) bench.py --serve-smoke
	$(PY) bench.py --slo-smoke
	$(PY) bench.py --alert-smoke
	$(PY) bench.py --decode-smoke
	$(PY) bench.py --reqtrace-smoke
	$(PY) bench.py --health-smoke
	$(PY) bench.py --io-smoke
	$(PY) bench.py --kernel-smoke
	$(PY) bench.py --mem-smoke
	$(PY) bench.py --comm-smoke
	$(PY) bench.py --coldstart-smoke
	$(PY) bench.py --tune-smoke
	$(PY) bench.py --elastic-smoke

roofline:        ## kernel-class decomposition of the train step
	$(PY) tools/roofline_probe.py

e2e:             ## input-pipeline -> train composition benchmark
	$(PY) tools/e2e_bench.py

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
