"""Test configuration.

Force an 8-device virtual CPU mesh BEFORE jax initialises, per SURVEY.md §4's
test strategy: multi-device distributed tests run on one host (the analogue
of the reference's multi-process localhost tests, test_dist_base.py:778).
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


# NOTE: this JAX build lowers f32 matmuls to bf16 passes by default
# (TPU-style). Do NOT globally raise jax_default_matmul_precision here — on
# this CPU backend non-default precision makes conv compiles ~10x slower.
# Numeric-gradient checks raise precision locally (see op_test.check_grad).

# Persistent compilation cache: XLA:CPU compiles dominate suite runtime;
# warm runs hit disk instead of recompiling. The suite keeps a cache of
# its own apart from the program's in-checkout default, unless the
# environment places the cache (JAX_COMPILATION_CACHE_DIR, which jax
# reads into its config by itself): then that directory is used.
TEST_CACHE_DIR = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                  or "/tmp/jax_test_cache")
jax.config.update("jax_compilation_cache_dir", TEST_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: drives the paddle_tpu.testing.chaos fault injector "
        "(injector state is reset around every test by the autouse "
        "_chaos_isolation fixture)")
    config.addinivalue_line(
        "markers",
        "serve: exercises the paddle_tpu.serving engine (engine global "
        "state — live engines, request-id counter — is reset around "
        "every test by the autouse _serving_isolation fixture)")
    config.addinivalue_line(
        "markers",
        "multichip: exercises DP×TP×PP programs over the 8-device "
        "virtual CPU mesh this conftest forces via "
        "--xla_force_host_platform_device_count (pipeline schedule "
        "stats are reset around every test by the autouse "
        "_pipeline_isolation fixture)")
    config.addinivalue_line(
        "markers",
        "pallas: runs ops.pallas kernel BODIES on the CPU test backend "
        "via the Pallas interpreter (the autouse _pallas_interpret "
        "fixture forces FLAGS_pallas_interpret for marked tests, so "
        "kernel dispatch serves the real kernels instead of the XLA "
        "fallbacks; fallback stats are reset around every test)")
    config.addinivalue_line(
        "markers",
        "recsys: exercises the paddle_tpu.recsys giant-embedding "
        "subsystem (tier caches, the table registry, tmp SSD log "
        "files and RECSYS_STATS are reset around every test by the "
        "autouse _recsys_isolation fixture)")
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 wall-clock budget "
        "(`-m 'not slow'`); multi-minute drills carry it")


@pytest.fixture(autouse=True)
def _pallas_interpret(request):
    """``pallas``-marked tests run the real kernel bodies on CPU through
    the Pallas interpreter (FLAGS_pallas_interpret); every test starts
    with clean fallback stats so kill-switch tests can assert on exactly
    the fallbacks they caused."""
    import sys
    if "paddle_tpu.ops.pallas" in sys.modules:
        sys.modules["paddle_tpu.ops.pallas"].reset_pallas_stats()
    if request.node.get_closest_marker("pallas"):
        from paddle_tpu.core.flags import flag_scope
        with flag_scope("pallas_interpret", True):
            yield
    else:
        yield


@pytest.fixture(autouse=True)
def _pipeline_isolation():
    """Pipeline-schedule telemetry (PIPELINE_STATS, the fallback
    warn-once set) must not leak between tests, so multichip tests can
    pin exact program-build/fallback counts."""
    import sys
    mod = sys.modules.get(
        "paddle_tpu.distributed.meta_parallel.spmd_pipeline")
    if mod is not None:
        mod.reset_pipeline_stats()
    yield
    mod = sys.modules.get(
        "paddle_tpu.distributed.meta_parallel.spmd_pipeline")
    if mod is not None:
        mod.reset_pipeline_stats()


@pytest.fixture(autouse=True)
def _chaos_isolation():
    """Chaos plans must never leak between tests: the injector is fully
    disarmed (and any chaos-hung worker threads cancelled) before AND
    after every test, whether or not the test is marked ``chaos``."""
    from paddle_tpu.testing import chaos
    chaos.reset()
    yield
    chaos.reset()


@pytest.fixture(autouse=True)
def _serving_isolation():
    """Serving-engine global state (live engines, the request-id
    counter, the scan-fallback warn-once set) must not leak between
    tests. Only touches paddle_tpu.serving when a test imported it."""
    import sys
    yield
    if "paddle_tpu.serving" in sys.modules:
        import paddle_tpu.serving as serving
        serving.reset()


@pytest.fixture(autouse=True)
def _recsys_isolation():
    """Recsys global state (the table registry — whose reset also
    closes tables owning tmp SSD log files — RECSYS_STATS, live
    serving-engine queues, the request-id counter) must not leak
    between tests. Only touches paddle_tpu.recsys when a test
    imported it."""
    import sys
    yield
    if "paddle_tpu.recsys" in sys.modules:
        import paddle_tpu.recsys as recsys
        recsys.reset()


@pytest.fixture(autouse=True)
def _admin_server_isolation():
    """The embedded admin HTTP server (monitor/server.py) must not
    leak threads/sockets or provider registrations between tests.
    Only touches the module when a test imported it."""
    import sys
    yield
    mod = sys.modules.get("paddle_tpu.monitor.server")
    if mod is not None:
        mod.stop_server()


@pytest.fixture(autouse=True)
def _fleet_monitor_isolation():
    """The fleet federator (monitor/fleet.py) must not leak its scrape
    thread, admin socket or registry between tests. Only touches the
    module when a test imported it."""
    import sys
    yield
    mod = sys.modules.get("paddle_tpu.monitor.fleet")
    if mod is not None:
        mod.stop_federator()


@pytest.fixture(autouse=True)
def _trace_isolation():
    """Structured-tracer state (retained ring, live traces, allocation
    probe) must not leak between tests — the zero-overhead pin reads
    the probe from a clean 0."""
    from paddle_tpu.monitor import trace as trace_mod
    yield
    if trace_mod._tracer is not None:
        trace_mod._tracer.reset()
    trace_mod.reset_trace_stats()


@pytest.fixture(autouse=True)
def _goodput_isolation():
    """Goodput-ledger module state (the process ledger, GOODPUT_STATS
    allocation probe, last layer-health vector, dump-provider
    registrations) must not leak between tests — the zero-overhead pin
    reads the probe from a clean 0. Only touches the module when a test
    imported it."""
    import sys
    yield
    mod = sys.modules.get("paddle_tpu.monitor.goodput")
    if mod is not None:
        mod.reset()


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(1234)
    np.random.seed(1234)
    # Tests that fleet.init() / set_mesh() must not leak the global mesh into
    # later tests (sharding constraints would bind to a stale 8-way mesh).
    # Snapshot/restore keeps module-scoped mesh fixtures working.
    from paddle_tpu.distributed import env as dist_env
    snap = dict(dist_env._global)
    yield
    dist_env._global.update(snap)


@pytest.fixture(autouse=True)
def _flight_recorder_isolation(tmp_path):
    """Watchdog-trip flight-recorder dumps must land in the test's tmp
    dir (not the repo cwd), and recorder ring state must not leak
    between tests."""
    from paddle_tpu.core.flags import flag_scope
    from paddle_tpu.monitor import flight_recorder as fr
    old = fr.set_flight_recorder(None)
    with flag_scope("flight_recorder_dir", str(tmp_path)):
        yield
    current = fr.set_flight_recorder(old)
    if current is not None:
        current.uninstall()


@pytest.fixture(autouse=True)
def _fleet_isolation():
    """fleet state must not leak between tests: whatever a test does to
    the fleet globals (init, strategy attach) is rolled back to the
    pre-test snapshot, so outcomes are order-independent while
    module-scoped mesh fixtures (test_mp_layers) keep working."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import topology
    state_snap = dict(fleet._fleet_state)
    hcg_snap = topology.get_hybrid_communicate_group()
    yield
    fleet._fleet_state.update(state_snap)
    topology.set_hybrid_communicate_group(hcg_snap)
