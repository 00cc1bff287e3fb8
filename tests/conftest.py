"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh BEFORE any backend use, so
the multi-chip sharding paths compile and run without TPU hardware —
the in-process analog of the reference's strategy of testing the
cluster token service directly in-JVM (SURVEY.md §4)."""

import os

# Serialize XLA:CPU's LLVM codegen (default split 32 compiles modules on
# a thread pool): repeated pjit compiles in one long process segfaulted
# inside backend_compile_and_load / the executable serializer, which
# smells like concurrent-codegen state corruption — and a 1-core box
# gains nothing from parallel codegen anyway. Must be set before the
# first backend use.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_cpu_parallel_codegen_split_count=1"
).strip()

# The persistent compilation cache stays off for the whole tier (see
# _bound_jit_state below), in spawned children too: the program turns it
# on in its entry points (utils/backend.enable_compile_cache), and this
# variable, which children inherit, overrides that.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

from sentinel_tpu.utils.backend import force_cpu

force_cpu(8)

import gc  # noqa: E402
import signal  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

# Hard wall-clock bound for one `mp`-marked test: generous against the
# 1-core box's spawn+import cost (each worker process re-imports jax),
# but finite — a wedged worker handshake must fail THIS test, never
# hang the whole tier.
MP_TEST_TIMEOUT_S = 240


@pytest.fixture(autouse=True)
def _mp_watchdog(request):
    """SIGALRM watchdog for tests that spawn real worker processes
    (the ``mp`` marker): the multi-process ingest plane blocks on
    cross-process handshakes (ready queues, verdict waits), and a hung
    worker would otherwise wedge tier-1 forever. The alarm raises in
    the test thread; test helpers terminate their children in
    ``finally`` blocks."""
    if "mp" not in request.keywords:
        yield
        return

    def _expired(signum, frame):
        raise TimeoutError(
            f"mp test exceeded {MP_TEST_TIMEOUT_S}s watchdog "
            "(hung worker process?)"
        )

    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(MP_TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)

# Long single-process runs accumulate XLA:CPU/LLVM JIT state until the
# native compiler eventually segfaults (observed twice deep into the
# slow tier: once in backend_compile_and_load after ~45 min of
# compiles, once in the persistent-cache executable serializer; any
# single test passes in isolation). Round-5 diagnosis: each compiled
# executable pins JIT code-page mmaps, and the process walks into
# vm.max_map_count (65530 default) — LLVM then reports "Cannot
# allocate memory" and segfaults; /proc/<pid>/maps showed ~30k maps
# after two differential streams, dropping to ~1k on clear_caches().
# Mitigation: periodically drop the in-memory executable caches and
# collect, bounding resident JIT state (diffbatch_worker does the same
# between streams). The persistent disk cache is deliberately NOT
# enabled — its serialize path was itself a crash site.
_TESTS_SINCE_CLEAR = {"n": 0}


@pytest.fixture(autouse=True)
def _bound_jit_state():
    yield
    _TESTS_SINCE_CLEAR["n"] += 1
    if _TESTS_SINCE_CLEAR["n"] % 15 == 0:
        jax.clear_caches()
        gc.collect()


@pytest.fixture()
def manual_clock():
    """The fake-clock fixture — equivalent of the reference's
    AbstractTimeBasedTest (PowerMock-mocked TimeUtil). Installs a
    ManualClock as the process default, resets the global engine to use
    it, and restores afterwards."""
    from sentinel_tpu.core import api
    from sentinel_tpu.utils.clock import ManualClock, set_default_clock

    clock = ManualClock(start_ms=0)
    prev = set_default_clock(clock)
    api.reset(clock=clock)
    yield clock
    set_default_clock(prev)
    api.reset()


@pytest.fixture()
def engine(manual_clock):
    from sentinel_tpu.core import api

    return api.get_engine()


def pytest_collection_modifyitems(config, items):
    """Auto-skip ``mesh``-marked tests when the environment has too
    few devices for the sharded flush (parallel.mesh_unavailable_reason):
    a skip with a reason, not a wall of failures hiding real
    regressions."""
    from sentinel_tpu.parallel import mesh_unavailable_reason

    reason = mesh_unavailable_reason(8)
    if not reason:
        return
    skip = pytest.mark.skip(reason=reason)
    for item in items:
        if "mesh" in item.keywords:
            item.add_marker(skip)
