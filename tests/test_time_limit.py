"""The per-test time limit of tests/conftest.py, run on a test file of its
own with the limit shrunk: a test past it fails with every thread's stack in
stderr and the run goes on; a test the signal cannot reach ends its process
at twice the limit, with the stacks, instead of holding the run."""

from pathlib import Path

CONFTEST = Path(__file__).with_name("conftest.py")


def _use_tier1_limit(pytester, limit_s: float) -> None:
    # the inner run loads tests/conftest.py under another module name (the
    # inner conftest.py would shadow `conftest`) and shrinks its limit
    pytester.makeconftest(
        f"""
        import importlib.util

        spec = importlib.util.spec_from_file_location("tier1_conftest", {str(CONFTEST)!r})
        tier1 = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tier1)
        tier1.TEST_TIME_LIMIT_S = {limit_s}
        pytest_configure = tier1.pytest_configure
        pytest_runtest_setup = tier1.pytest_runtest_setup
        pytest_runtest_call = tier1.pytest_runtest_call
        pytest_runtest_teardown = tier1.pytest_runtest_teardown
        """
    )


def test_a_test_past_the_limit_fails_with_stacks_and_the_run_goes_on(pytester):
    _use_tier1_limit(pytester, 1.0)
    pytester.makepyfile(
        """
        import threading
        import time

        def test_sleeps_past_the_limit():
            threading.Thread(target=time.sleep, args=(30,), name="bystander", daemon=True).start()
            time.sleep(30)

        def test_after_it():
            pass
        """
    )
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider", timeout=60)
    result.assert_outcomes(passed=1, failed=1)
    result.stdout.fnmatch_lines(["E *TimeLimitExceeded: *test_sleeps_past_the_limit ran past 1.0 s*"])
    err = result.stderr.str()
    assert "test_sleeps_past_the_limit ran past 1.0 s" in err
    # every thread: the sleeping main thread and the bystander
    assert "Current thread 0x" in err and "Thread 0x" in err
    assert "in test_sleeps_past_the_limit" in err


def test_a_main_thread_the_signal_cannot_reach_ends_at_twice_the_limit(pytester):
    _use_tier1_limit(pytester, 1.0)
    pytester.makepyfile(
        """
        import signal
        import time

        def test_blocks_the_alarm():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            time.sleep(30)
        """
    )
    result = pytester.runpytest_subprocess("-p", "no:cacheprovider", timeout=60)
    assert result.ret != 0
    err = result.stderr.str()
    assert "Timeout (0:00:02)!" in err
    assert "in test_blocks_the_alarm" in err
