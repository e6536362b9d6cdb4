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
import faulthandler  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

pytest_plugins = ("pytester",)  # tests/test_time_limit.py runs pytest on a test file

# Every test's own time limit, in seconds, for each of its setup, call and
# teardown. Past it, every thread's stack goes to stderr and the test fails
# in the main thread, and the run goes on. At twice it (a main thread stuck in
# C code, where no signal handler runs) faulthandler ends the process, which
# xdist replaces and names. 3x the slowest tier-1 phase under the driver's
# `-n 6` (70 s, test_chip_smoke.py's call, PR 40). Read when each phase
# starts, so tests/test_time_limit.py can shrink it.
TEST_TIME_LIMIT_S = 210.0

_stderr_fd = -1  # the real stderr (pytest captures fd 2 while a test runs)


class TimeLimitExceeded(Exception):
    pass


def pytest_configure(config):
    global _stderr_fd
    if _stderr_fd < 0:
        _stderr_fd = os.dup(2)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    limit = TEST_TIME_LIMIT_S
    on_main = threading.current_thread() is threading.main_thread()
    dumped = False

    def expired(signum, frame):
        nonlocal dumped
        if not dumped:
            dumped = True
            os.write(_stderr_fd, f"\n{item.nodeid} ran past {limit} s:\n".encode())
            faulthandler.dump_traceback(file=_stderr_fd, all_threads=True)
        raise TimeLimitExceeded(
            f"{item.nodeid} ran past {limit} s (TEST_TIME_LIMIT_S, tests/conftest.py);"
            " every thread's stack is in stderr"
        )

    if on_main:
        previous = signal.signal(signal.SIGALRM, expired)
        # again every second after: code that swallows one raise (a finally
        # that hangs too) gets the next
        signal.setitimer(signal.ITIMER_REAL, limit, 1.0)
    faulthandler.dump_traceback_later(2 * limit, exit=True, file=_stderr_fd)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        if on_main:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, previous)


pytest_runtest_call = pytest_runtest_teardown = pytest_runtest_setup


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
