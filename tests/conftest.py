"""Test configuration: the CPU platform with 8 virtual devices.

``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count=8`` is
how the suite runs everywhere except the opt-in chip tier: multi-device
tests see eight cpu devices (``mx.tpu(i)`` maps onto them, context.py),
single-device tests run on cpu(0).  Under ``MXTPU_CHIP_TESTS=1`` the
platform is left alone and the chip is the default backend — the
reference's cpu<->gpu consistency strategy (SURVEY.md §4.2).
"""
import os

import pytest

# MXTPU_CHIP_TESTS=1: leave the platform alone so the real chip is the
# default backend — the once-per-round accelerator tier (`make chip`).
# Run it SERIALLY (-n 0): a chip belongs to one process at a time.
if os.environ.get("MXTPU_CHIP_TESTS") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
# Tests count backend compiles (memprof build totals, the program-cache
# warm-start proofs): a hit in JAX's persistent compilation cache
# (mxnet_tpu/base.py) would make those counts depend on what an earlier
# run left on disk.  Off through JAX's own switch; subprocesses inherit.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

# -- fast tier -------------------------------------------------------------
# `pytest -m fast` is the <5-minute iteration tier (the full suite runs
# ~40 min).  Modules here are the quick, broad-coverage ones; the heavy
# sweeps (op sweep, consistency, models, parallel, dist-multiprocess) stay
# full-suite only.
_FAST_MODULES = {
    "test_analysis", "test_autograd", "test_executor_cache",
    "test_fused_extra", "test_fused_optimizers", "test_gluon_data",
    "test_health", "test_io_metric_kvstore", "test_io_pipeline",
    "test_kvstore_ici", "test_module", "test_ndarray",
    "test_namespaces", "test_optimizer", "test_symbol", "test_elastic",
    "test_serving", "test_pallas_kernels", "test_comm_overlap",
    "test_program_cache", "test_autotune", "test_reqtrace",
    "test_concurrency", "test_timeseries",
}


def pytest_addoption(parser):
    # `make test` passes `-n 4` when pytest-xdist is installed (see the
    # Makefile's XDIST probe).  When xdist is absent, register the option
    # ourselves as a no-op so an explicit `-n 0` / `--numprocesses 0`
    # (e.g. the chip tier) still parses instead of dying unrecognized.
    try:
        import xdist  # noqa: F401
    except ImportError:
        try:
            parser.addoption("-n", "--numprocesses", action="store",
                             default=None,
                             help="ignored: pytest-xdist is not installed; "
                                  "tests run serially")
        except ValueError:
            # pytest>=8 reserves lowercase short options for itself; the
            # long spelling still lets `--numprocesses 0` parse, and the
            # suite simply runs serially
            parser.addoption("--numprocesses", action="store", default=None,
                             help="ignored: pytest-xdist is not installed")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "fast: quick iteration tier (run with -m fast)")
    config.addinivalue_line(
        "markers", "slow: outside tier-1 (which runs -m 'not slow')")
    # self-enforce the chip tier's serial-only contract: a chip belongs
    # to one process, so parallel workers fail or hang on it
    if os.environ.get("MXTPU_CHIP_TESTS") == "1" and (
            os.environ.get("PYTEST_XDIST_WORKER")
            or getattr(config.option, "numprocesses", None) not in (None,
                                                                    0, "0")):
        raise pytest.UsageError(
            "MXTPU_CHIP_TESTS=1 must run serially (-n 0): a chip "
            "belongs to one process at a time")


# long-running convergence tests inside otherwise-fast modules; they stay
# in the full suite but out of the iteration tier
_SLOW_WITHIN_FAST = {
    "test_fused_dp_step_multi_device", "test_module_fit_learns",
    "test_fused_dp_compressed_converges_and_cuts_wire",
    "test_bf16_multi_precision_trains", "test_module_multi_device",
    "test_reshape_preserves_f32_masters",
    # spawn-pool workers re-import the package (~10s on a cold cache)
    "test_process_mode_matches_thread_mode",
    # three cachectl subprocesses, each a full framework import
    "test_cachectl_ls_verify_prune",
    # two shipper subprocesses, each a full framework import
    "test_fleet_shipper_merges_processes",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__ in _FAST_MODULES \
                and item.originalname not in _SLOW_WITHIN_FAST \
                and item.name not in _SLOW_WITHIN_FAST:
            item.add_marker(pytest.mark.fast)


# -- thread hygiene ---------------------------------------------------------
# Every package thread is spawned through mxnet_tpu.threads.spawn with a
# structured `mxnet_tpu/<subsystem>/<role>` name, so "did close() really
# stop everything?" is one enumerate() away.  The threaded-subsystem
# modules must leave zero package threads behind after each test — a
# leaked dispatch/feeder thread in one test is a use-after-close crash
# (or a deadlock) in a later one.
_LEAK_CHECK_MODULES = {
    "test_serving", "test_serving_fleet", "test_io_pipeline",
    "test_concurrency", "test_timeseries",
}


@pytest.fixture(autouse=True)
def _no_package_thread_leaks(request):
    yield
    if request.module.__name__ not in _LEAK_CHECK_MODULES:
        return
    import time

    from mxnet_tpu import threads as _threads

    # closed subsystems join their threads, but a worker parked on a
    # poll interval (0.05 s) may need a beat to observe the stop flag
    deadline = time.monotonic() + 5.0
    while _threads.live_package_threads() \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    leaked = _threads.live_package_threads()
    assert not leaked, (
        "package threads leaked past the test: %s — close()/stop() the "
        "owning subsystem (threads spawned via mxnet_tpu.threads.spawn "
        "must be joined by their owner's shutdown path)"
        % sorted(t.name for t in leaked))
