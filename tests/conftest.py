"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective tests run
against 8 virtual CPU devices, mirroring the reference's strategy of testing
its cluster logic in-process without a real cluster (SURVEY.md §4).
Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Belt and braces: if something imported jax before this file ran, the env
# var above came too late for it — set the config value as well.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import asyncio  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def run():
    """Run a coroutine to completion on a fresh event loop.

    debug=True is the asyncio analogue of the reference's `go test -race`
    CI (SURVEY §5): it surfaces never-awaited coroutines, cross-thread
    loop-unsafe calls, and >100ms event-loop stalls (the class of bug the
    storage-hashing offload fixed) as warnings/errors during every test."""

    def _run(coro):
        return asyncio.run(coro, debug=True)

    return _run
