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


@pytest.mark.parametrize("path", FILES)
def test_rehearsal(path):
    cmd = [sys.executable, "-m", "pytest", path, "-q",
           "-p", "no:cacheprovider", "-p", "no:xdist"]
    if FAILS_SINCE_PR26.startswith(path + "::"):
        cmd += ["--deselect", FAILS_SINCE_PR26]
    p = subprocess.Popen(cmd, cwd=ROOT, env=_env(), text=True,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         start_new_session=True)
    try:
        out, _ = p.communicate(timeout=LIMIT_S)
    except subprocess.TimeoutExpired:
        # the rehearsals start processes of their own: end the group
        os.killpg(p.pid, signal.SIGKILL)
        out, _ = p.communicate()
        pytest.fail(f"{path} ran past {LIMIT_S} s:\n{out[-3000:]}")
    assert p.returncode == 0, out[-3000:]
