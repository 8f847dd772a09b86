"""Tier-1 runs the benchmark's own rehearsals (``benchmarks/tests``).

They are what holds an engine PR to the names the yardstick reads: the
counters, spans and program names behind the per-layer metrics, the
float32 control and the faults that must make ``correct`` false. Each
file runs as the documented command does, in a process of its own (the
rehearsals keep their own ``conftest.py`` and ``sys.path``), one case a
file, found by glob: a new rehearsal file is a new case with no edit
here. Nothing in them is a device number.
"""
import glob
import os
import signal
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The one rehearsal that fails on this tree, since PR 26: it asserts
#: the EXACT set of per-layer metrics a CPU run reports (ROADMAP R14e).
#: ``benchmarks/`` is a `benchmark` PR's to edit: that PR turns its
#: ``==`` into ``>=`` and then deletes this constant.
FAILS_SINCE_PR26 = ("benchmarks/tests/test_cells.py::"
                    "test_traced_run_reports_the_per_layer_metrics_it_can_read")

#: seconds a file may take; a hang fails its case, not the suite (the
#: slowest, test_cells.py, takes about 40 s alone on a CPU)
LIMIT_S = 300
#: seconds more for the pipe to close once the file's process group is
#: killed; whatever holds it after that is not worth waiting for
KILL_WAIT_S = 5

FILES = sorted(
    os.path.relpath(p, ROOT).replace(os.sep, "/")
    for p in glob.glob(os.path.join(ROOT, "benchmarks", "tests",
                                    "test_*.py")))


def _env() -> dict:
    """The documented command's environment. What tests/conftest.py
    exports for tier-1's own sake (eight virtual devices, the mesh
    pinned off) is not the rehearsals'."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PRESTO_TPU_MESH_EXECUTION", None)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def test_there_are_rehearsals():
    assert FILES, "benchmarks/tests holds no test_*.py"


def run_limited(cmd, limit_s, what):
    """Run ``cmd`` in a session of its own and return its exit code and
    output; past ``limit_s`` seconds the case fails, and never waits
    longer than ``KILL_WAIT_S`` more."""
    p = subprocess.Popen(cmd, cwd=ROOT, env=_env(), text=True,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         start_new_session=True)
    try:
        out, _ = p.communicate(timeout=limit_s)
    except subprocess.TimeoutExpired:
        # the rehearsals start processes of their own: end the group
        os.killpg(p.pid, signal.SIGKILL)
        try:
            out, _ = p.communicate(timeout=KILL_WAIT_S)
            held = ""
        except subprocess.TimeoutExpired as e:
            # a process outside the group still holds the pipe
            out = (e.output or b"").decode(errors="replace")
            p.stdout.close()
            p.wait(timeout=KILL_WAIT_S)
            held = (f" (and {KILL_WAIT_S} s after the group's SIGKILL "
                    f"the pipe was still held open: not waited for)")
        pytest.fail(f"{what} ran past {limit_s} s{held}:\n{out[-3000:]}")
    return p.returncode, out


@pytest.mark.parametrize("path", FILES)
def test_rehearsal(path):
    cmd = [sys.executable, "-m", "pytest", path, "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist"]
    if FAILS_SINCE_PR26.startswith(path + "::"):
        cmd += ["--deselect", FAILS_SINCE_PR26]
    rc, out = run_limited(cmd, LIMIT_S, path)
    assert rc == 0, out[-3000:]
