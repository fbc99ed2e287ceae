"""The per-test deadline of tests/conftest.py, tried on generated files.

Each case writes a small test file beside a conftest that loads this
repo's tests/conftest.py and sets its two constants (DEADLINE_S, GRACE_S)
to a second or two, runs pytest on it in a subprocess, and reads the
outcome and the log. The waits in the generated tests are 30 s long:
without the deadline a case takes that long and fails.
"""

import os
import re
import subprocess
import sys
import textwrap
import time

import pytest

_CONFTEST = os.path.join(os.path.dirname(os.path.abspath(__file__)), "conftest.py")

_INNER_CONFTEST = """\
import importlib.util
import sys

spec = importlib.util.spec_from_file_location("repo_conftest", {conftest!r})
repo_conftest = importlib.util.module_from_spec(spec)
sys.modules["repo_conftest"] = repo_conftest
spec.loader.exec_module(repo_conftest)
repo_conftest.DEADLINE_S = {deadline_s}
repo_conftest.GRACE_S = {grace_s}
from repo_conftest import *  # noqa: E402,F401,F403 — its hooks and fixtures
"""


def run_generated(tmp_path, source, *args, deadline_s=1.5, grace_s=1.5):
    """Run pytest on `source` under the repo's conftest with the given
    constants; returns (returncode, stdout+stderr, seconds)."""
    (tmp_path / "conftest.py").write_text(
        _INNER_CONFTEST.format(
            conftest=_CONFTEST, deadline_s=deadline_s, grace_s=grace_s
        )
    )
    (tmp_path / "test_gen.py").write_text(textwrap.dedent(source))
    t0 = time.monotonic()
    done = subprocess.run(
        [
            sys.executable, "-m", "pytest", "test_gen.py", "-q",
            "-p", "no:cacheprovider", *args,
        ],
        cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        timeout=120,
    )
    return done.returncode, done.stdout, time.monotonic() - t0


def line_of(source, needle):
    lines = textwrap.dedent(source).splitlines()
    (n,) = [i for i, line in enumerate(lines, 1) if needle in line]
    return n


WAITS = {
    "sleep": """
        import time

        def test_waits():
            time.sleep(30)  # waits here

        def test_after():
            pass
    """,
    # inside asyncio.run, with a background task of its own: the loop is
    # torn down by the failure, and what the test left pending is not
    # held against the next test (the sanitizer drains first)
    "asyncio": """
        import asyncio

        def test_waits():
            async def body():
                other = asyncio.ensure_future(asyncio.sleep(30))
                await asyncio.sleep(30)  # waits here
                await other

            asyncio.run(body())

        def test_after():
            asyncio.run(asyncio.sleep(0))
    """,
}


@pytest.mark.parametrize("kind", sorted(WAITS))
def test_deadline_fails_the_test_and_the_file_goes_on(tmp_path, kind):
    source = WAITS[kind]
    rc, log, seconds = run_generated(tmp_path, source)
    assert rc == 1, log
    assert "1 failed, 1 passed" in log, log
    assert "FAILED test_gen.py::test_waits" in log, log
    assert "deadline: the test is past its 1.5 s" in log, log
    # the log names the test and shows the line that waited
    assert "+++ deadline: test_gen.py::test_waits is past its 1.5 s" in log
    # (a thread's stack for the sleep, a task's for the await)
    where = rf'test_gen\.py", line {line_of(source, "# waits here")},? in '
    assert re.search(where, log.split("+++ deadline:")[1]), log
    assert seconds < 25, f"the wait was not cut short: {seconds:.1f} s"


@pytest.mark.parametrize(
    "default_s, marker_s, marked_sleeps, unmarked_sleeps, fails",
    [(30, 0.5, 30, 1, "test_marked"), (0.5, 10, 2, 30, "test_unmarked")],
    ids=["marker_shorter", "marker_longer"],
)
def test_marker_wins_over_the_default(
    tmp_path, default_s, marker_s, marked_sleeps, unmarked_sleeps, fails
):
    source = f"""
        import time

        import pytest

        @pytest.mark.timeout({marker_s})
        def test_marked():
            time.sleep({marked_sleeps})

        def test_unmarked():
            time.sleep({unmarked_sleeps})
    """
    rc, log, seconds = run_generated(tmp_path, source, deadline_s=default_s)
    assert rc == 1 and "1 failed, 1 passed" in log, log
    its = marker_s if fails == "test_marked" else default_s
    assert f"+++ deadline: test_gen.py::{fails} is past its {its:g} s" in log
    assert seconds < 25, f"the wait was not cut short: {seconds:.1f} s"


def test_blocked_signal_is_ended_by_the_watchdog(tmp_path):
    """The timer signal held blocked: the test never fails from inside.
    GRACE_S later the watchdog dumps the stacks and ends the worker;
    xdist reports the test as failed, once, and a new worker runs the
    rest of the file."""
    source = """
        import signal
        import time

        def test_before():
            pass

        def test_blocked():
            signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
            time.sleep(30)  # waits here

        def test_after():
            pass
    """
    rc, log, seconds = run_generated(
        tmp_path, source, "-p", "xdist", "-n", "1", "--dist", "loadfile"
    )
    assert rc == 1, log
    assert "1 failed, 2 passed" in log, log
    assert log.count("crashed while running 'test_gen.py::test_blocked'") == 1
    assert "Timeout (0:00:03)!" in log, log
    where = rf'test_gen\.py", line {line_of(source, "# waits here")} in '
    assert re.search(where + "test_blocked", log), log
    assert seconds < 25, f"the wait was not cut short: {seconds:.1f} s"


def test_timed_out_test_leaves_no_child(tmp_path):
    source = """
        import subprocess
        import sys

        def test_waits_for_a_child():
            child = subprocess.Popen(
                [sys.executable, "-c", "import time; time.sleep(600)"]
            )
            with open("child.pid", "w") as f:
                f.write(str(child.pid))
            child.wait()

        def test_after():
            pass
    """
    rc, log, _ = run_generated(tmp_path, source)
    assert rc == 1 and "1 failed, 1 passed" in log, log
    pid = int((tmp_path / "child.pid").read_text())
    try:
        with open(f"/proc/{pid}/stat") as f:
            state = f.read().rsplit(")", 1)[1].split()[0]
    except FileNotFoundError:
        state = "gone"
    # a zombie nobody has collected yet is dead all the same
    assert state in ("gone", "Z"), f"child {pid} still runs (state {state})"
