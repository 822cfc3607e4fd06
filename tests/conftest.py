"""Test-wide environment: hermetic CPU backend with 8 fake devices.

The distributed test strategy (SURVEY.md §4.2): pjit sharding + collectives
are validated on a fake multi-device CPU mesh via
``--xla_force_host_platform_device_count`` — the substitute for the
reference lineage's "run it on a Databricks cluster" manual testing.
This must run before jax initializes, hence module top-level in conftest.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# tpudl.runtime turns the persistent compile cache on at import; the
# hermetic run — this process and every script or worker it spawns —
# neither fills nor reads that directory (the two cache tests turn it
# on for themselves under tmp_path).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# The environment-failure guard.
#
# The known environment-failure bucket (this CPU jaxlib cannot run
# cross-process computations — "Multiprocess computations aren't
# implemented on the CPU backend") is pinned by nodeid below. Any NEW
# test failing with that signature is flagged loudly at session end: it
# should either use the spawn-free fake-mesh idiom or carry the
# needs_multiprocess marker, not silently grow the bucket. What needs
# the chip itself is not a pytest tier: chip_smoke.py runs on it, and
# tests/test_tpu_compile.py asks the chip's compiler without one.
# ---------------------------------------------------------------------------

_ENV_FAILURE_SIGNATURE = "Multiprocess computations aren't implemented"
#: Non-slow tests known to hit the CPU-jaxlib multiprocess limitation at
#: HEAD (the `slow`-marked spawn tests are deselected from tier-1 and
#: tracked in CHANGES.md PR 4 instead). These now carry
#: @pytest.mark.needs_multiprocess and auto-skip above, so tier-1 runs
#: fully green here — the nodeids stay pinned so a marker accidentally
#: removed surfaces as a KNOWN failure, not a silently NEW one, while
#: any OTHER test failing with the signature is still flagged loudly.
_KNOWN_ENV_FAILURES = frozenset({
    "tests/test_graft_entry.py::test_dryrun_multichip_8",
})
_new_env_failures = []


def pytest_collection_modifyitems(config, items):
    skip_mp = pytest.mark.skip(
        reason="requires a multi-process-capable backend: this CPU "
        "jaxlib cannot compile cross-process computations "
        "('Multiprocess computations aren't implemented on the CPU "
        "backend')"
    )
    for item in items:
        if "needs_multiprocess" in item.keywords:
            item.add_marker(skip_mp)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    report = outcome.get_result()
    if (
        report.failed
        and call.excinfo is not None
        and _ENV_FAILURE_SIGNATURE in repr(call.excinfo.value)
        and item.nodeid not in _KNOWN_ENV_FAILURES
    ):
        _new_env_failures.append(item.nodeid)


def pytest_terminal_summary(terminalreporter):
    if _new_env_failures:
        terminalreporter.section(
            "NEW environment-limited failures", sep="!"
        )
        terminalreporter.write_line(
            "These tests failed with the known CPU-backend multiprocess "
            "limitation but are NOT in conftest._KNOWN_ENV_FAILURES:"
        )
        for nodeid in _new_env_failures:
            terminalreporter.write_line(f"  {nodeid}")
        terminalreporter.write_line(
            "Do not grow the environment-failure bucket: use the fake "
            "8-device CPU mesh (no process spawn) or mark the test "
            "@pytest.mark.needs_multiprocess / @pytest.mark.slow."
        )


@pytest.fixture(autouse=True)
def _startup_timeline_of_its_own():
    """Every test begins with an empty start-up recorder: a recorder
    that a test turns on is handed what THAT test's start-up recorded
    (tpudl.obs.spans.enable), not what the tests before it in the
    process left behind."""
    from tpudl.obs import spans

    if spans._startup is not None:
        spans._startup.drain()
    yield


@pytest.fixture(autouse=True)
def _chaos_env_guard(request):
    """Chaos-marked tests drive env-gated fault injectors
    (TPUDL_SERVE_CHAOS_*): snapshot and restore those knobs around each
    one, so a failing chaos test cannot leak a kill/freeze knob into
    every later engine constructed in this process."""
    if "chaos" not in request.keywords:
        yield
        return
    saved = {
        k: v for k, v in os.environ.items()
        if k.startswith("TPUDL_SERVE_CHAOS_")
    }
    try:
        yield
    finally:
        for k in [
            k for k in os.environ if k.startswith("TPUDL_SERVE_CHAOS_")
        ]:
            del os.environ[k]
        os.environ.update(saved)


@pytest.fixture(scope="session")
def mesh8():
    from tpudl.runtime.mesh import MeshSpec, make_mesh

    return make_mesh(MeshSpec(dp=2, fsdp=2, sp=1, tp=2))


@pytest.fixture
def rng_np():
    return np.random.default_rng(0)


@pytest.fixture
def compile_cache_config():
    """conftest turns the persistent cache off for the hermetic run; a
    cache test turns it on for itself and leaves the session as it
    found it."""
    from jax.experimental.compilation_cache import compilation_cache

    names = (
        "jax_enable_compilation_cache",
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {name: getattr(jax.config, name) for name in names}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()  # un-latch: later tests stay uncached
