"""Verifier: replay queries against a control and a test runner, compare.

The role of presto-verifier (reference
presto-verifier/.../verifier/Verifier.java + Validator.java:68 — run
each query on a control and a test cluster, normalize, diff, report
MATCH / MISMATCH / failures). Runners are anything with
``execute(sql) -> QueryResult`` (LocalRunner, ClusterRunner,
StatementClient wrapper), so the same harness validates
local-vs-mesh, local-vs-cluster, or version-vs-version.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence


@dataclasses.dataclass
class VerifyResult:
    query: str
    status: str          # MATCH | MISMATCH | CONTROL_FAILED | TEST_FAILED
    detail: str = ""
    control_ms: float = 0.0
    test_ms: float = 0.0


def _normalize(rows: Sequence, precision: int) -> List:
    out = []
    for r in rows:
        vals = []
        for v in r:
            if hasattr(v, "item"):
                v = v.item()
            if isinstance(v, float):
                v = round(v, precision)
            vals.append(v)
        out.append(tuple(vals))
    # order-insensitive: the reference re-sorts deterministically too
    # (Validator resultsMatch over sorted lists)
    return sorted(out, key=repr)


class Verifier:
    def __init__(self, control, test, precision: int = 6):
        self.control = control
        self.test = test
        self.precision = precision

    def verify_one(self, sql: str) -> VerifyResult:
        t0 = time.perf_counter()
        try:
            want = self.control.execute(sql)
        except Exception as e:
            return VerifyResult(sql, "CONTROL_FAILED",
                                f"{type(e).__name__}: {e}")
        control_ms = (time.perf_counter() - t0) * 1e3
        t1 = time.perf_counter()
        try:
            got = self.test.execute(sql)
        except Exception as e:
            return VerifyResult(sql, "TEST_FAILED",
                                f"{type(e).__name__}: {e}",
                                control_ms=control_ms)
        test_ms = (time.perf_counter() - t1) * 1e3
        w = _normalize(want.rows, self.precision)
        g = _normalize(got.rows, self.precision)
        if len(w) != len(g):
            return VerifyResult(
                sql, "MISMATCH",
                f"row count: control={len(w)} test={len(g)}",
                control_ms, test_ms)
        for i, (a, b) in enumerate(zip(w, g)):
            if a != b:
                return VerifyResult(
                    sql, "MISMATCH",
                    f"first differing row {i}: control={a!r} test={b!r}",
                    control_ms, test_ms)
        return VerifyResult(sql, "MATCH", "", control_ms, test_ms)

    def run(self, queries: Sequence[str]) -> List[VerifyResult]:
        return [self.verify_one(q) for q in queries]


def main(argv: Optional[List[str]] = None) -> int:
    """CLI: verify a ;-separated query file local-control vs
    distributed-test (the in-repo analogue of the reference's
    verifier CLI)."""
    import argparse

    from .exec.runner import LocalRunner

    p = argparse.ArgumentParser(description="presto_tpu verifier")
    p.add_argument("queries", help="file of ;-separated SQL statements")
    p.add_argument("--tpch-sf", type=float, default=0.01)
    p.add_argument("--test", choices=["distributed", "local"],
                   default="distributed")
    args = p.parse_args(argv)
    with open(args.queries, encoding="utf-8") as f:
        queries = [q.strip() for q in f.read().split(";") if q.strip()]
    control = LocalRunner(tpch_sf=args.tpch_sf)
    test = LocalRunner(catalogs=control.session.catalogs)
    if args.test == "distributed":
        # the mesh over every visible device, through the same door
        test.execute("SET SESSION mesh_execution = 'on'")
    results = Verifier(control, test).run(queries)
    for r in results:
        print(f"{r.status:15s} {r.control_ms:8.1f}ms {r.test_ms:8.1f}ms  "
              f"{r.query[:80]!r}" + (f"  -- {r.detail}" if r.detail
                                     else ""))
    failed = sum(r.status != "MATCH" for r in results)
    print(f"{len(results) - failed}/{len(results)} MATCH")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
