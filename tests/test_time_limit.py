"""The time limit of ``tests/conftest.py``, held to what it promises: a
test that waits fails alone, with every thread's stack in its report,
and its file goes on.

Each case runs ``python -m pytest`` on a two-test file of its own, as
``test_benchmark_rehearsals.py`` runs its children. A file outside
``tests/`` does not see ``tests/conftest.py`` by itself: the child loads
it as a plugin (``-p tests.conftest``, from the root of the repo).
"""
import os
import re
import signal
import sys
import time

import pytest

from test_benchmark_rehearsals import KILL_WAIT_S, run_limited

#: seconds a child may take (it imports jax before its first test)
CHILD_LIMIT_S = 120

HEAD = """\
import signal, subprocess, threading, time
import pytest

def idles():
    time.sleep(60)
"""

WAITS_ON_AN_EVENT = HEAD + """
@pytest.mark.time_limit(1)
def test_first():
    threading.Thread(target=idles, daemon=True).start()
    threading.Event().wait()  # the waiting line

def test_second():
    pass
"""

WAITS_IN_COMMUNICATE = HEAD + """
@pytest.mark.time_limit(1)
def test_first():
    threading.Thread(target=idles, daemon=True).start()
    p = subprocess.Popen(["sleep", "600"])
    try:
        p.communicate()  # the waiting line
    finally:
        p.kill()

def test_second():
    pass
"""

ENDS_IN_TIME = HEAD + """
@pytest.mark.time_limit(1)
def test_first():
    pass

def test_second():
    time.sleep(1.5)
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 1.5
"""

TAKES_NO_SIGNAL = HEAD + """
import tests.conftest
tests.conftest.AFTER_S = 1

@pytest.mark.time_limit(1)
def test_first():
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    time.sleep(60)  # the waiting line

def test_second():
    pass
"""


def run_child(tmp_path, source):
    path = tmp_path / "test_child.py"
    path.write_text(source)
    return run_limited(
        [sys.executable, "-m", "pytest", "-p", "tests.conftest", str(path),
         "-q", "-p", "no:cacheprovider", "-p", "no:xdist"],
        CHILD_LIMIT_S, "the child")


@pytest.mark.parametrize("source", [WAITS_ON_AN_EVENT, WAITS_IN_COMMUNICATE],
                         ids=["event_wait", "communicate"])
def test_a_test_that_waits_fails_alone_with_every_stack(tmp_path, source):
    rc, out = run_child(tmp_path, source)
    assert rc == 1, out[-3000:]
    assert "1 failed, 1 passed" in out, out[-3000:]
    assert "test_first ran past its time limit of 1 s" in out
    # the report's own traceback ends at the line that waited ...
    assert re.search(r"^>.*# the waiting line$", out, re.M), out[-3000:]
    # ... and the dump holds the test's thread and the second thread
    assert re.search(r'test_child\.py", line \d+ in test_first', out)
    assert re.search(r'test_child\.py", line \d+ in idles', out)


def test_a_test_that_ends_in_time_leaves_no_timer_armed(tmp_path):
    rc, out = run_child(tmp_path, ENDS_IN_TIME)
    assert rc == 0 and "2 passed" in out, out[-3000:]


def test_a_test_that_takes_no_signal_ends_its_process_with_stacks(tmp_path):
    """The watchdog behind the timer: the child has no xdist to start a
    new worker, so its run ends there (exit code 1, no summary line)."""
    rc, out = run_child(tmp_path, TAKES_NO_SIGNAL)
    assert rc == 1, out[-3000:]
    assert "Timeout (0:00:02)!" in out, out[-3000:]
    assert re.search(r'test_child\.py", line \d+ in test_first', out)
    assert "passed" not in out and "failed" not in out, out[-3000:]


def test_a_rehearsal_whose_pipe_stays_held_fails_within_its_limit():
    """``run_limited`` past its limit: the child's group is killed, and a
    grandchild in a session of its own, which holds the pipe, is not
    waited for."""
    limit_s = 2
    child = ("import subprocess, time\n"
             "g = subprocess.Popen(['sleep', '600'], start_new_session=True)\n"
             "print('grandchild', g.pid, flush=True)\n"
             "time.sleep(600)\n")
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as failure:
        run_limited([sys.executable, "-c", child], limit_s, "the child")
    took = time.monotonic() - t0
    message = str(failure.value)
    os.kill(int(re.search(r"grandchild (\d+)", message).group(1)),
            signal.SIGKILL)
    assert f"the child ran past {limit_s} s" in message
    assert "the pipe was still held open" in message
    assert limit_s + KILL_WAIT_S <= took < limit_s + KILL_WAIT_S + 60
