"""Test harness config.

Force JAX onto a virtual 8-device CPU platform so sharding/pjit tests
exercise real multi-device code paths without TPU hardware (the driver
separately dry-runs the multi-chip path; bench.py and chip_smoke.py use
the real chip). Both variables are set before jax is imported, which is
all the installed jax needs.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def pytest_report_header(config):
    return f"jax devices: {jax.devices()}"


def _build_native(config):
    """Build `native/` once where it is not built and can be, so that a
    clean checkout and a built tree run the same tests: `native/build/`
    is not committed, and the native engine's tests skip without it
    (they still do on a machine with no `make` or C++ compiler). Only
    the process that starts the run builds — under xdist the controller,
    before it starts its workers."""
    import shutil
    import subprocess

    from openr_tpu.ops.native_spf import native_available

    if hasattr(config, "workerinput") or native_available():
        return
    cxx = os.environ.get("CXX", "g++")
    if shutil.which("make") is None or shutil.which(cxx) is None:
        return
    done = subprocess.run(
        ["make", "-C", os.path.join(_REPO, "native")],
        capture_output=True, text=True, check=False,
    )
    if done.returncode != 0:
        # not fatal: the native tests skip, and say why
        print(f"make -C native failed:\n{done.stdout}{done.stderr}")


# --------------------------------------------------------------------------
# deadline: every test runs under one, @pytest.mark.timeout(seconds) where
# it carries the marker and DEADLINE_S otherwise, counted over its setup,
# call and teardown together. When it passes, the stacks of all threads
# (and of the running event loop's tasks) go to the run's stderr under the
# test's id (the real one, past pytest's capture, so that a `-q` log names
# what waited and where), the processes the test started are killed, and
# the test fails from inside whatever it was waiting in; the run goes on.
# A test that never comes back to the interpreter (blocked in C with the
# signal held, a native deadlock) is ended GRACE_S later by faulthandler's
# watchdog thread, which dumps the stacks and exits the process: under
# xdist that is one worker, reported as that test's failure and replaced.

import asyncio  # noqa: E402
import faulthandler  # noqa: E402
import signal  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402

#: seconds a test without the marker may take (the slowest that passes
#: takes under 20 s under `-n 6`, PR 35)
DEADLINE_S = 300.0
#: seconds between a test's deadline and the end of its process
GRACE_S = 30.0
#: while a deadline stands expired the signal comes again this often: the
#: first one may land in a background asyncio task, which keeps any
#: exception but KeyboardInterrupt and SystemExit to itself
_AGAIN_S = 1.0

_log_fd = 2  # the run's own stderr, duplicated before capture takes fd 2


class _Deadline:
    item = None  # the test the clock runs for
    seconds = 0.0
    at = 0.0  # time.monotonic() at which it passes
    dumped = False


def _descendants(pid: int) -> list[int]:
    """Every live process below `pid` (Linux lists children a thread)."""
    out, todo = [], [pid]
    while todo:
        parent = todo.pop()
        try:
            tasks = os.listdir(f"/proc/{parent}/task")
        except OSError:
            continue
        for tid in tasks:
            try:
                with open(f"/proc/{parent}/task/{tid}/children") as f:
                    kids = [int(k) for k in f.read().split()]
            except OSError:
                continue
            out += kids
            todo += kids
    return out


def _deadline_passed(signum, frame):
    d = _Deadline
    if not d.dumped:
        d.dumped = True
        os.write(
            _log_fd,
            f"\n+++ deadline: {d.item.nodeid} is past its {d.seconds:g} s; "
            f"stacks of all threads:\n".encode(),
        )
        faulthandler.dump_traceback(file=_log_fd, all_threads=True)
        loop = asyncio._get_running_loop()
        if loop is not None:
            # a thread waiting in the loop's select says nothing of what
            # the loop waits for: its tasks do
            with os.fdopen(os.dup(_log_fd), "w") as log:
                for task in asyncio.all_tasks(loop):
                    task.print_stack(file=log)
        # a wait for a child that will not die ends with the child; their
        # owners (Popen, asyncio's watcher) collect the exit statuses
        for pid in _descendants(os.getpid()):
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
    pytest.fail(
        f"deadline: the test is past its {d.seconds:g} s (stacks of all "
        f"threads are in the run's stderr under '+++ deadline: "
        f"{d.item.nodeid}')"
    )


def pytest_configure(config):
    global _log_fd
    _log_fd = os.dup(2)  # capture is suspended while plugins configure
    signal.signal(signal.SIGALRM, _deadline_passed)
    _build_native(config)


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def pytest_runtest_protocol(item):
    marker = item.get_closest_marker("timeout")
    d = _Deadline
    d.item, d.dumped = item, False
    d.seconds = float(marker.args[0]) if marker else DEADLINE_S
    d.at = time.monotonic() + d.seconds
    faulthandler.dump_traceback_later(
        d.seconds + GRACE_S, exit=True, file=_log_fd
    )
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.hookimpl(hookwrapper=True, tryfirst=True)
def _deadline_phase(item):
    """The signal is armed only while pytest is inside setup, call or
    teardown, where it takes an exception for the test's outcome, and
    not again once it has failed the test: what is left of the test
    then (its teardown) has until the watchdog."""
    if not _Deadline.dumped:
        left = max(_Deadline.at - time.monotonic(), 0.001)
        signal.setitimer(signal.ITIMER_REAL, left, _AGAIN_S)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)


pytest_runtest_setup = pytest_runtest_call = _deadline_phase
pytest_runtest_teardown = _deadline_phase


@pytest.hookimpl(optionalhook=True)
def pytest_handlecrashitem(crashitem, report, sched):
    """xdist's `--dist loadfile` scheduler puts a dead worker's file back
    in its queue with the test that killed the worker still to run: the
    next worker would die of it too, and so on to the restart limit, with
    the tests behind it never run. It has its failure: tick it off."""
    units = [getattr(sched, "workqueue", {})]
    units += getattr(sched, "assigned_work", {}).values()
    for by_scope in units:
        for unit in by_scope.values():
            if crashitem in unit:
                unit[crashitem] = True


# --------------------------------------------------------------------------
# asyncio sanitizer: every event loop a test creates (asyncio.run included)
# runs in DEBUG mode with a recording exception handler and an
# instrumented task factory. After each test the autouse fixture fails the
# test if any task leaked an exception that was never retrieved, was
# destroyed while still pending, or is still pending on a closed loop —
# the failure classes `guard_task`/`reap` (openr_tpu.common.tasks) and
# orlint OR002/OR005 exist to prevent. Opt out for a test that provokes
# these on purpose with @pytest.mark.asyncio_sanitizer_off.

import gc  # noqa: E402
import weakref  # noqa: E402


#: exception-handler messages that are task-hygiene failures. Everything
#: else (e.g. "Error on transport creation" for a deliberately rejected
#: TLS handshake, "Fatal error on transport" for a peer reset) is a
#: transport-level condition a correct server hits under hostile peers —
#: logged by asyncio but not a leak.
_FAIL_SUBSTRINGS = (
    "never retrieved",
    "was destroyed but it is pending",
    "Unhandled exception",
    "Unhandled error",
    "Exception in callback",
    "unhandled exception during asyncio.run() shutdown",
)


class AsyncioSanitizer:
    """Collects unhandled-asyncio evidence across every loop."""

    def __init__(self):
        self.events: list[str] = []
        self._task_refs: list[weakref.ref] = []
        # loop.set_debug() for loops created while True. The seeded
        # cluster-storm suites (test_chaos/test_soak) opt down via
        # @pytest.mark.asyncio_debug_off: debug's per-task traceback
        # capture is a ~10x tax at 9-node-grid scale and breaks their
        # convergence budgets — the sanitizer's handler, task
        # accounting and teardown checks stay fully active there.
        self.debug_enabled = True

    # -- hooks installed on every new loop ---------------------------------

    def handler(self, loop, context) -> None:
        msg = context.get("message", "unhandled asyncio error")
        if any(s in msg for s in _FAIL_SUBSTRINGS):
            src = (
                context.get("task")
                or context.get("future")
                or context.get("handle")
            )
            exc = context.get("exception")
            self.events.append(f"{msg} [{src!r}] exc={exc!r}")
        loop.default_exception_handler(context)

    def task_factory(self, loop, coro, context=None):
        # `context` arrives on Python >=3.11 (asyncio.Runner passes it);
        # the Task ctor only accepts it there too
        if context is None:
            t = asyncio.tasks.Task(coro, loop=loop)
        else:
            t = asyncio.tasks.Task(coro, loop=loop, context=context)
        self._task_refs.append(weakref.ref(t))
        return t

    # -- per-test accounting -----------------------------------------------

    def drain(self) -> list[str]:
        """Evidence since the last drain: recorded handler events plus
        tasks still PENDING on a CLOSED loop (they can never complete —
        a leaked fiber someone forgot to cancel/await)."""
        out, self.events = self.events, []
        live: list[weakref.ref] = []
        for ref in self._task_refs:
            t = ref()
            if t is None:
                continue
            if not t.done() and t.get_loop().is_closed():
                out.append(
                    f"task still pending on closed loop: {t!r}"
                )
                continue  # reported once; drop the ref
            live.append(ref)
        self._task_refs = live
        return out


_SANITIZER = AsyncioSanitizer()


class _SanitizerPolicy(asyncio.DefaultEventLoopPolicy):
    def new_event_loop(self):
        loop = super().new_event_loop()
        # OPENR_ASYNCIO_DEBUG=0 turns off debug mode (slower loops) but
        # keeps the sanitizer's handler + task accounting — useful when
        # bisecting timing-sensitive failures
        loop.set_debug(
            _SANITIZER.debug_enabled
            and os.environ.get("OPENR_ASYNCIO_DEBUG", "1") != "0"
        )
        # debug-mode's 100 ms "slow callback" warnings are noise for
        # JAX-compiling tests; the sanitizer is after leaks, not latency
        loop.slow_callback_duration = 10.0
        loop.set_exception_handler(_SANITIZER.handler)
        loop.set_task_factory(_SANITIZER.task_factory)
        return loop


asyncio.set_event_loop_policy(_SanitizerPolicy())


# (the asyncio_sanitizer_off / asyncio_debug_off markers are registered
# in pyproject.toml [tool.pytest.ini_options] markers — the single
# declared registry)


# --------------------------------------------------------------------------
# jit compile sanitizer: the compile-stability analogue of the asyncio
# one. The session installs the process compile ledger (hooks
# jax_log_compiles; openr_tpu/monitor/compile_ledger.py). A test marked
# @pytest.mark.jit_steady_state declares a warmup boundary by calling
# compile_ledger.mark_warm() once its warmup calls are done; the autouse
# fixture then FAILS the test if any jax compilation (jit cache miss,
# new eager-op shape, static-arg variant) lands after the mark — the
# invariant the padding buckets and OR008-OR010 exist to uphold.
# Unmarked tests are unaffected (the ledger only counts).

from openr_tpu.monitor import compile_ledger  # noqa: E402

compile_ledger.install()


@pytest.fixture(autouse=True)
def jit_compile_sanitizer(request):
    marked = request.node.get_closest_marker("jit_steady_state")
    led = compile_ledger.ledger()
    led.reset_warm()
    yield
    if not marked:
        led.reset_warm()
        return
    if not led.warm_marked:
        pytest.fail(
            "@pytest.mark.jit_steady_state test never called "
            "compile_ledger.mark_warm() — mark the end of warmup so "
            "the steady-state rounds can be checked"
        )
    new = led.compiles_since_warm()
    led.reset_warm()
    if new:
        detail = ", ".join(f"{fn} x{n}" for fn, n in sorted(new.items()))
        pytest.fail(
            f"jit compile sanitizer: {sum(new.values())} steady-state "
            f"compilation(s) after mark_warm() ({detail}) — a shape "
            f"leaked past the padding buckets or a static arg took a "
            f"fresh value (docs/Linting.md OR008-OR010)"
        )


# --------------------------------------------------------------------------
# work-proportionality sanitizer: the third sanitizer in the PR 5/PR 7
# lineage (asyncio, jit compiles, now dataflow work). A test marked
# @pytest.mark.work_proportional declares its warmup boundary by calling
# work_ledger.mark_warm(); the autouse fixture then FAILS the test if any
# steady-state round touched more than k*delta + floor entities in any
# scoped pipeline stage (openr_tpu/monitor/work_ledger.py) — the delta-
# proportionality contract the scoped-rebuild paths exist to uphold.
# Marker kwargs: k= (slope, default work_ledger.DEFAULT_K), floor=
# (per-round constant allowance), exempt= (stage names allowed to stay
# O(routes) — e.g. ("spf_full", "diff") for tests whose steady rounds
# legitimately take full solves; merge and redistribute are delta-
# native since ISSUE 17 and no longer belong in any exempt list).
# Unmarked tests are unaffected.

from openr_tpu.monitor import work_ledger  # noqa: E402


@pytest.fixture(autouse=True)
def work_proportional_sanitizer(request):
    marked = request.node.get_closest_marker("work_proportional")
    led = work_ledger.ledger()
    led.reset_warm()
    yield
    if not marked:
        led.reset_warm()
        return
    if not led.warm_marked:
        pytest.fail(
            "@pytest.mark.work_proportional test never called "
            "work_ledger.mark_warm() — mark the end of warmup so the "
            "steady-state rounds can be checked"
        )
    report = work_ledger.steady_violation_report(
        k=marked.kwargs.get("k", work_ledger.DEFAULT_K),
        floor=marked.kwargs.get("floor", work_ledger.DEFAULT_FLOOR),
        exempt=tuple(marked.kwargs.get("exempt", ())),
    )
    led.reset_warm()
    if report:
        pytest.fail(
            f"work-proportionality sanitizer: steady-state round did "
            f"O(table) work in a scoped stage ({report}) — a full-table "
            f"walk leaked into the delta path (docs/Monitor.md "
            f"\"Work ledger\")"
        )


@pytest.fixture(autouse=True)
def asyncio_sanitizer(request):
    """Fail any test that leaks pending tasks or never-retrieved task
    exceptions (GC is forced so parked exceptions surface NOW, in the
    test that caused them, not in a random later one)."""
    _SANITIZER.drain()  # don't blame this test for earlier leftovers
    if request.node.get_closest_marker("asyncio_debug_off"):
        _SANITIZER.debug_enabled = False
    try:
        yield
    finally:
        _SANITIZER.debug_enabled = True
    gc.collect()
    evidence = _SANITIZER.drain()
    if not evidence:
        return
    if request.node.get_closest_marker("asyncio_sanitizer_off"):
        return
    details = "\n  ".join(evidence)
    pytest.fail(
        f"asyncio sanitizer: {len(evidence)} leaked task/exception "
        f"event(s) during this test (guard fire-and-forget tasks with "
        f"openr_tpu.common.tasks.guard_task; see docs/Linting.md):\n"
        f"  {details}"
    )
