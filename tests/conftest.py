"""Test harness: force an 8-device virtual CPU platform.

Mirrors the build plan's test strategy (SURVEY.md §4): the reference had no
tests at all; here sharding/serving logic runs in CI on a fake-TPU CPU mesh
via ``xla_force_host_platform_device_count`` so no TPU hardware is needed.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# LLMK_TEST_TPU=1 keeps the real accelerator visible — used by
# tests/test_tpu_hardware.py to pin kernel lowering on actual hardware
# (everything else skips itself or tolerates the platform). Set before
# anything imports jax; subprocess servers inherit it.
if os.environ.get("LLMK_TEST_TPU") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import pytest

# Test tiers (pyproject markers): "unit" is the fast inner loop —
# `pytest -m unit` stays under 60 s by construction, so only modules
# with no model compiles or subprocess servers are listed. "e2e" covers
# the serving-path modules (real sockets, subprocess engines/routers).
# Everything keeps working unmarked; tiers are additive selection aids.
_UNIT_MODULES = {
    "test_adapters", "test_faults", "test_grammar", "test_helm_golden",
    "test_hub", "test_manifests", "test_router", "test_tools",
    "test_tracing",
}
_E2E_MODULES = {
    "test_bench", "test_cold_start", "test_entrypoints", "test_kind_e2e",
    "test_multihost_e2e", "test_native_router", "test_native_sanitizers",
    "test_server", "test_server_extras",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.path.stem if item.path else ""
        explicit = {m.name for m in item.iter_markers()}
        if mod in _UNIT_MODULES and not ({"slow", "e2e"} & explicit):
            item.add_marker(pytest.mark.unit)
        elif mod in _E2E_MODULES and "e2e" not in explicit:
            item.add_marker(pytest.mark.e2e)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Every compiled XLA:CPU executable holds a few memory mappings until
    its jit-cache entry dies (~2-3k per engine-heavy module). A full run
    piles them past ``vm.max_map_count`` (65530 here) and the interpreter
    then dies inside whichever test compiles next — the segfault around
    tests/test_speculation.py at the PR-20 seed. Dropping jax's caches
    brings the count back to ~700; doing it after EVERY module costs ~50%
    in recompiles, so only once the count is half way to the limit."""
    yield
    try:
        with open("/proc/self/maps") as f:
            n_maps = sum(1 for _ in f)
    except OSError:
        return
    if n_maps > 30000:
        import gc

        import jax

        jax.clear_caches()
        gc.collect()


def free_port() -> int:
    """Bind-and-release a localhost port for subprocess servers."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
