"""Test harness: run everything on an 8-device virtual CPU mesh.

The JAX analogue of the reference's logical-device splitting
(``test_util.set_logical_devices_to_at_least`` — SURVEY.md §4): one host CPU
is split into 8 XLA devices so every multi-device code path (DP/FSDP/TP/PP/
SP/EP meshes, collectives, sharding) runs on a laptop-class machine.

Must run before any JAX backend initialization.  The platform is pinned to
the CPU here and in the environment the tests' subprocesses inherit; the
persistent compilation cache is switched off for the whole session so
neither this process nor a ``train.py`` child writes one into the
checkout (``runtime.init_compile_cache`` would place it there).
"""

import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

# --- fast/slow lanes (SURVEY.md §4; VERDICT r3 #8) --------------------------
# `pytest -m "not slow"` is the tier-1 lane; the full suite stays the
# landing gate.  Two sources of `slow`:
#   1. tests/slow_tests.txt — nodeids measured >= ~5s on the 1-core CI box
#      (regenerate from `pytest --durations=60` when timings drift);
#   2. _PROCESS_TEST_FILES — files that spawn OS processes (multi-process
#      collectives, PS clusters, coordinator workers, subprocess smokes):
#      structurally slow AND the natural habitat of timing flakes, so they
#      are slow-laned wholesale regardless of measured time.
_SLOW_LIST = os.path.join(os.path.dirname(__file__), "slow_tests.txt")
_PROCESS_TEST_FILES = {
    "test_multi_process.py",
    "test_param_server.py",
    "test_coordinator_process.py",
    "test_data_service.py",
    "test_pipeline_mpmd.py",
    "test_bench_smoke.py",
    "test_examples.py",
    "test_sidecar.py",
    "test_combined_axes.py",
    "test_train_introspection_smoke.py",
    "test_train_auto_profile_smoke.py",
    "test_train_chaos_smoke.py",
    "test_train_elastic_smoke.py",
    "test_train_dynamics_smoke.py",
    "test_train_netchaos_smoke.py",
    "test_train_zero_smoke.py",
    "test_train_quant_smoke.py",
    "test_train_data_service_smoke.py",
    "test_train_fleet_smoke.py",
    "test_train_alert_chaos_smoke.py",
    "test_serve_smoke.py",
}


def _load_slow_nodeids():
    try:
        with open(_SLOW_LIST) as f:
            return {
                line.strip() for line in f
                if line.strip() and not line.startswith("#")
            }
    except OSError:
        return set()


def pytest_collection_modifyitems(config, items):
    slow_ids = _load_slow_nodeids()
    mark = pytest.mark.slow
    for item in items:
        fname = os.path.basename(item.fspath.strpath)
        if fname in _PROCESS_TEST_FILES or item.nodeid in slow_ids:
            item.add_marker(mark)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture()
def mesh8(devices):
    """data=2 × fsdp=2 × model=2 mesh over the 8 virtual devices."""
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=2, fsdp=2, model=2), devices)


@pytest.fixture()
def dp_mesh(devices):
    """Pure data-parallel mesh over all 8 devices."""
    from distributedtensorflow_tpu.parallel import MeshSpec, build_mesh

    return build_mesh(MeshSpec(data=-1), devices)
