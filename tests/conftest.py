"""Test configuration.

Tests run on CPU with a virtual 8-device platform so that every sharding
path (mesh construction, pjit/shard_map collectives) is exercised without
TPU hardware. This must be set before jax is first imported anywhere.
"""

import os

# Tests must never claim a real TPU: hold JAX to the CPU with eight
# virtual devices. Both variables are read when the CPU backend is
# created, so they are set before anything imports jax. The compile
# cache is left where fishnet_tpu.utils.compile_cache puts it (or where
# JAX_COMPILATION_CACHE_DIR says): no other directory is set here.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: full-spec-shape tests (heavier)")


@pytest.fixture
def anyio_backend():
    # aiohttp requires asyncio; never run async tests on trio.
    return "asyncio"


@pytest.fixture(autouse=True)
def _fresh_eval_cache(monkeypatch):
    # The position-keyed eval cache is process-wide BY DESIGN (it
    # outlives services to survive respawns), which in a shared pytest
    # process would couple tests: a warm cache turns later tests'
    # dispatches into whole-batch skips and skews every dispatch-count
    # assertion. Reset around each test; warm-cache behavior is
    # exercised explicitly inside tests/test_eval_cache.py.
    #
    # Bounds seeding and speculative pad-row evals are likewise pinned
    # off by default: both legitimately change node counts and
    # prewire-hit totals, which dozens of older tests assert exactly.
    # Tests that exercise them monkeypatch the hatches back off.
    from fishnet_tpu.search import eval_cache

    monkeypatch.setenv("FISHNET_NO_BOUNDS", "1")
    monkeypatch.setenv("FISHNET_NO_SPECULATION", "1")
    eval_cache.reset_cache()
    yield
    eval_cache.reset_cache()
