"""Test harness config: run everything on a virtual 8-device CPU mesh.

Mirrors Presto's ring-3 testing strategy (DistributedQueryRunner boots N
in-process servers, reference presto-tests/.../DistributedQueryRunner.java:76):
we get N devices in one process via XLA's host platform device count.

Tests run without a chip, so the CPU platform is forced here (the
environment form for subprocesses tests spawn, the config form for this
process); the chip is driven by chip_smoke.py, never by pytest.

Every test runs under a time limit (``TIME_LIMIT_S``, or the test's own
``@pytest.mark.time_limit(seconds)``): a test that waits fails with
every thread's stack in its report, and its file goes on.
"""
import faulthandler
import os
import signal
import tempfile

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent XLA compile cache shared across test processes/runs: the
# suite's wall-clock is dominated by kernel compiles (lax.sort at 2^17
# costs tens of seconds per variant on XLA:CPU), and the same shapes
# recur run over run (reference discipline: LocalQueryRunner reuse,
# presto-main/.../testing/LocalQueryRunner.java:210). The tests keep
# their own fixed directory, placed from outside like any other
# (presto_tpu.enable_compile_cache reads the environment form, and so do
# the server/worker subprocesses tests spawn), so CPU entries stay apart
# from the chip's .jax_cache.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache_cpu"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Mesh-native execution defaults to AUTO with >1 device (PR 12) — and
# the 8 virtual devices above would put EVERY LocalRunner test on the
# SPMD path, paying shard_map compiles across the whole suite. Pin the
# harness to the single-device path; the mesh suites (test_mesh_default,
# test_distributed*) opt back in per query via the mesh_execution
# session property, which overrides this environment default.
os.environ.setdefault("PRESTO_TPU_MESH_EXECUTION", "off")

import presto_tpu  # noqa: E402

presto_tpu.enable_compile_cache()

import pytest  # noqa: E402

#: seconds a test may take, set-up and tear-down included. The suite's
#: slowest test takes 62.5 s alone and about 90 s beside other load; the
#: rehearsals' own LIMIT_S (300 s, tests/test_benchmark_rehearsals.py)
#: plus their kill is below it, so the inner limit speaks first.
TIME_LIMIT_S = 360
#: seconds after the limit at which the timer fires again (a tear-down
#: that waits too) and the watchdog ends a worker whose main thread is
#: inside C and took no signal
AFTER_S = 60

#: where the watchdog writes: the terminal, duplicated while capture is off
_STDERR_FD = pytest.StashKey[int]()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "time_limit(seconds): this test's limit, in place of "
                   "TIME_LIMIT_S of tests/conftest.py")
    config.stash[_STDERR_FD] = os.dup(2)


# trylast: innermost of the wrappers, so that of faulthandler's one
# watchdog it is this one that stands while a test runs, not the
# print-only one of pytest.ini's faulthandler_timeout (which is for the
# processes that do not load this file: the rehearsals' children)
@pytest.hookimpl(wrapper=True, trylast=True)
def pytest_runtest_protocol(item):
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else TIME_LIMIT_S

    def on_alarm(signum, frame):
        # into a file, not onto the terminal: text in a row of dots
        # makes the driver's count of dots drop the row
        with tempfile.TemporaryFile("w+") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
            f.seek(0)
            stacks = f.read()
        pytest.fail(f"{item.nodeid} ran past its time limit of "
                    f"{limit:g} s. Every thread's stack, the test's own "
                    f"last:\n{stacks}")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit, AFTER_S)
    faulthandler.dump_traceback_later(limit + AFTER_S, exit=True,
                                      file=item.config.stash[_STDERR_FD])
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(scope="session")
def mesh_runner():
    """Factory of runners whose every query runs on the mesh, through
    the door the four-chip cell takes (``LocalRunner.execute`` ->
    ``execute_plan`` -> ``select_mesh`` -> ``DistributedExecutor``).
    Under ``mesh_execution = 'on'`` a plan the fragmenter refuses
    raises, so a test that passes has been on the mesh.
    ``n_devices`` 0 is every visible device (the 8 above)."""
    from presto_tpu.exec.runner import LocalRunner

    def make(catalogs=None, n_devices=0, rows_per_batch=1 << 16, **kw):
        r = LocalRunner(catalogs=catalogs, rows_per_batch=rows_per_batch,
                        **kw)
        r.execute("SET SESSION mesh_execution = 'on'")
        r.execute(f"SET SESSION mesh_devices = {int(n_devices)}")
        return r
    return make
