"""Rehearsals of the benchmark on the CPU at SF0.01. Run them with

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

They are not part of the repo's tier-1 tests (``tests/``): nothing here
is a device number.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache_cpu"))
sys.path[:0] = [BENCH, ROOT]

import pytest  # noqa: E402

SMALL_SF = 0.01

#: the entry that adds `tpch_sf10_q6` once the program answers it
#: rightly on the chip (PERF.md, Open questions, first row): its files
#: are here already, and the rehearsals run it on the CPU, where the
#: engine is right
PENDING = [{"name": "tpch_sf10_q6", "config": "tpch_sf10",
            "traffic": "closed1_q6", "chips": 1,
            "why": "closed loop, 1 client, 2 bindings in turn; scan, "
                   "three DOUBLE compares, a multiply and a global sum "
                   "over 60M rows in 59 batches"}]


@pytest.fixture(scope="session")
def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def small_cell(bench):
    """``small_cell(name)``: the cell as the command loads it, its
    deployment cut to SF0.01 with 8192-row batches (several batches a
    scan) so that a test run can hold it."""
    import presto_tpu  # noqa: F401  (64-bit types on before any array)
    import harness

    def make(name: str):
        cell = harness.Cell(
            dict(bench, workloads=bench["workloads"] + PENDING), name, ROOT)
        conn = dict(cell.config["connector"], args={"sf": SMALL_SF})
        cell.config = dict(cell.config, scale_factor=SMALL_SF,
                           connector=conn, rows_per_batch=8192)
        cell.sf = SMALL_SF
        return cell
    return make
