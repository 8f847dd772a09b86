"""The ``tpch_sf10_mesh4`` configuration and its cell: the files load
through ``harness.Cell``, differ from ``tpch_sf10`` in the mesh's
session properties alone, and the cell runs on four devices (virtual
ones here: ``XLA_FLAGS=--xla_force_host_platform_device_count=4``, in a
process of its own, since a process's device count is fixed once JAX
starts) at SF0.01 with the mesh path taken and the answers correct."""
import json
import os
import subprocess
import sys

from conftest import BENCH, ROOT

CELL = "tpch_sf10_q1_mesh4"


def test_the_files_load_through_the_harness(bench):
    import harness
    cell = harness.Cell(bench, CELL, ROOT)
    assert cell.chips == 4 and cell.sf == 10
    assert cell.traffic["template"] == "q1"
    one = harness.Cell(bench, "tpch_sf10_q1", ROOT)
    assert cell.traffic == one.traffic
    props, base = (c.config["session_properties"] for c in (cell, one))
    assert props == dict(base, mesh_execution="on", mesh_devices="4")
    same = ("scale_factor", "catalog", "connector", "reference_data",
            "rows_per_batch", "scan_cache_bytes", "tables", "reduced")
    assert all(cell.config[k] == one.config[k] for k in same)
    assert cell.config["source"] != one.config["source"]
    names = {m["name"] for m in cell.metrics("end_to_end")}
    assert names == {"query_s", "setup_s"}


def test_four_chip_cells_are_at_most_half(bench):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)


SCRIPT = r"""
import json, sys, time
sys.path[:0] = [%(bench)r, %(root)r]
import presto_tpu
import harness
cell = harness.load_cell(%(cell)r)
conn = dict(cell.config["connector"], args={"sf": 0.01})
cell.config = dict(cell.config, scale_factor=0.01, connector=conn,
                   rows_per_batch=8192)
cell.sf = 0.01
import jax
assert len(jax.devices()) >= cell.chips
seen = {}
counters = harness.counters
def spy():
    out = counters()
    seen.setdefault("first", out)
    seen["last"] = out
    return out
harness.counters = spy
harness.TRACE_SECONDS = 0.5     # untraced queries first, then traced
out = harness.run_cell(cell, 2147483659, 1.5, True, time.perf_counter())
out["mesh_selected"] = (seen["last"]["mesh_path_selected_total"]
                        - seen["first"].get("mesh_path_selected_total", 0))
print(json.dumps(out))
"""


def test_the_cell_runs_on_four_devices_through_the_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c",
         SCRIPT % {"bench": BENCH, "root": ROOT, "cell": CELL}],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] >= 4
    assert out["mesh_selected"] >= out["attempted"] >= 1
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["launches_per_query"] > 0 and m["dispatch_host_ms"] > 0
