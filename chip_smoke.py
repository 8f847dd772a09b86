#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the SQL path still starts on
the chip.

One process. Starts the normal front door in-process (``PrestoTpuServer``
over a ``LocalRunner`` over a ``TpchConnector`` at TPC-H SF10), talks to
it over HTTP with the repo's own client (``POST /v1/statement``, then
``nextUri``), answers TPC-H Q6, Q1 and Q3 cold (all three at once, as a
server that has just started meets its first requests) and then one by
one from the warm device scan cache, and holds every answer to a plain
NumPy reference (``tpch_reference.py``) over the same generated data.
Q3 runs against a second catalog at a cut scale (``Q3_SCALE``: its cold
compile at SF10 does not fit the time limit). Any exception, any
mismatch, any FAILED query is a non-zero exit; so is a JAX without a
TPU — there is no CPU fallback and no option that passes without a chip.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # ONLY the mesh path on four chips,
                                     # held to the single-device path
                                     # and the reference

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

The phases are functions tests/test_chip_smoke.py calls at SF0.01 on
the CPU; the device check lives in ``main()``.
"""
from __future__ import annotations

import argparse
import datetime
import functools
import json
import re
import sys
import time

#: relative tolerance for DOUBLE results: reduction order legitimately
#: shifts big float64 sums in the last places. Counts, integers, dates
#: and strings compare exactly.
REL_TOL = 1e-6

#: TPC-H scale the smoke loads and answers Q6 and Q1 at (lineitem ~60M
#: rows; the Q1 column set alone is ~3.4GB resident in the device scan
#: cache)
SCALE = 10.0

#: Q3's scale — a CUT, forced by the 1200 s limit: on the v5e a cold Q3
#: at SF10 took 2231 s (97 s warm): 1026 s in the first-call compiles
#: the engine counts (the sort-path group-by alone 898 s over 6
#: programs) and most of the rest in the recompiles of later capacity
#: buckets, which it does not count (my chip run, PR 23). At this scale
#: lineitem is one 2^20-row batch; the same operators compile in ~480 s.
Q3_SCALE = 0.1

#: device scan-cache limit for the run, through the cache's own call:
#: Q6's and Q1's column sets resident
#: together, under the chip's 16GB
SCAN_CACHE_BYTES = 8 << 30

QUERIES = ("q6", "q1", "q3")


class SmokeFailure(AssertionError):
    """A phase's check did not hold: wrong answer, idle device, FAILED
    query. Never caught inside the script."""


def _say(msg: str) -> None:
    print(msg, flush=True)


def tpch_sql(name: str, catalog: str = "tpch") -> str:
    """The query's text from tests/tpch_queries.py; against another
    catalog than the session's, its FROM tables fully qualified (the
    server resolves unqualified names in the runner's own catalog)."""
    from tests.tpch_queries import Q
    sql = next(sql for qn, sql, _ in Q if qn == name)
    if catalog == "tpch":
        return sql
    return re.sub(
        r"(?m)^from (.+)$",
        lambda m: "from " + ", ".join(
            f"{catalog}.default.{t.strip()}"
            for t in m.group(1).split(",")), sql)


# ---------------------------------------------------------------------------
# the front door
# ---------------------------------------------------------------------------

class Door:
    """The running front door: a PrestoTpuServer over a LocalRunner
    over one TpchConnector per scale (catalog ``tpch`` at the first
    scale, ``tpch_cut`` at a second), and the StatementClients handed
    out against it (one per statement stream: a client is
    thread-confined)."""

    def __init__(self, server, conns: dict):
        self.server, self.conns = server, conns
        self._clients: list = []

    def client(self, catalog: str = "tpch"):
        from presto_tpu.client import StatementClient
        c = StatementClient(f"http://127.0.0.1:{self.server.port}",
                            user="chip-smoke", catalog=catalog)
        self._clients.append(c)
        return c

    def close(self) -> None:
        for c in self._clients:
            c.close()
        self.server.stop()


def start_server(sf: float, scan_cache_bytes=None,
                 rows_per_batch: int = 1 << 20, cut_sf=None) -> Door:
    """PrestoTpuServer over a LocalRunner over TpchConnector(sf) — and
    a second catalog at ``cut_sf`` where that differs."""
    import presto_tpu
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.connectors.tpch import TpchConnector
    from presto_tpu.exec.runner import LocalRunner
    from presto_tpu.exec.scancache import CACHE
    from presto_tpu.server.protocol import PrestoTpuServer

    presto_tpu.enable_compile_cache()
    compile_log()
    conns = {"tpch": TpchConnector(sf=sf)}
    if cut_sf is not None and cut_sf != sf:
        conns["tpch_cut"] = TpchConnector(sf=cut_sf)
    catalogs = CatalogManager()
    for name, conn in conns.items():
        catalogs.register(name, conn)
    runner = LocalRunner(catalogs=catalogs, catalog="tpch",
                         rows_per_batch=rows_per_batch)
    if scan_cache_bytes is not None:
        CACHE.set_limit(scan_cache_bytes)
    server = PrestoTpuServer(runner, port=0)
    server.start()
    return Door(server, conns)


def run_statement(client, sql: str, properties=None):
    """(rows, seconds) of one statement through POST /v1/statement +
    nextUri. A FAILED query raises (client.QueryFailed)."""
    client.session_properties.clear()
    for k, v in (properties or {}).items():
        client.session_properties[k] = str(v).lower() \
            if isinstance(v, bool) else str(v)
    t0 = time.perf_counter()
    res = client.execute(sql)
    return res.rows, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# the engine's own counters
# ---------------------------------------------------------------------------

_PATH_COUNTERS = (
    "agg_dense_path_selected_total", "agg_sort_path_selected_total",
    "join_strategy_selected_total.direct.replicated",
    "join_strategy_selected_total.direct.partitioned",
    "join_strategy_selected_total.compare.replicated",
    "join_strategy_selected_total.compare.partitioned",
    "join_strategy_selected_total.sorted.replicated",
    "join_strategy_selected_total.sorted.partitioned",
    "join_strategy_selected_total.expand.replicated",
    "join_strategy_selected_total.expand.partitioned",
    "mesh_path_selected_total", "scan_cache_hit_total",
    "scan_cache_miss_total",
)


def path_counters() -> dict:
    from presto_tpu.obs.metrics import REGISTRY
    snap = {m["name"]: float(m.get("value", 0.0))
            for m in REGISTRY.snapshot()}
    return {n: snap.get(n, 0.0) for n in _PATH_COUNTERS}


class CompileLog:
    """Every XLA backend compile of the process, from JAX's own
    monitoring event (function name, seconds; a persistent-cache hit
    shows as its short load). The engine's
    ``system.runtime.executables.compile_seconds`` counts only the
    first call of each jit-cache entry, and a later shape bucket
    recompiles silently, so that column undercounts a cold run."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.by_name: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            a = self.by_name.setdefault(kw.get("fun_name", "?"), [0, 0.0])
            a[0] += 1
            a[1] += duration

    def seconds(self) -> float:
        return sum(s for _, s in self.by_name.values())

    def slowest(self, top: int = 12) -> list:
        """[function, compiles, seconds] — where a cold start goes."""
        rows = sorted(self.by_name.items(), key=lambda kv: -kv[1][1])
        return [[n, c, round(s, 3)] for n, (c, s) in rows[:top]]


@functools.lru_cache(maxsize=None)
def compile_log() -> CompileLog:
    """The process's one CompileLog: registered on first use and kept
    (JAX offers no way to take one listener off again)."""
    return CompileLog()


def executables(client) -> dict:
    """system.runtime.executables through the front door: per-name
    (invocations, device seconds) plus the device-seconds total."""
    rows, _ = run_statement(
        client,
        "select name, invocations, device_time_s "
        "from system.runtime.executables")
    by: dict = {}
    for name, inv, dev in rows:
        a = by.setdefault(name, [0, 0.0])
        a[0] += int(inv)
        a[1] += float(dev)
    return {"by_name": by, "device_s": sum(v[1] for v in by.values())}


def invoked(before: dict, after: dict, ignore=()) -> dict:
    """{executable name: invocations} that moved between two snapshots
    (``ignore``: names the snapshot statement itself moves)."""
    out = {}
    for name, (inv, _) in after["by_name"].items():
        d = inv - before["by_name"].get(name, (0, 0))[0]
        if d > 0 and name not in ignore:
            out[name] = d
    return out


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

_LI_UNION = ["l_orderkey", "l_returnflag", "l_linestatus", "l_quantity",
             "l_extendedprice", "l_discount", "l_tax", "l_shipdate"]


def reference_answers(conn, names=QUERIES) -> dict:
    """NumPy answers for ``names`` over the connector's generated
    chunks (one generation pass per table) + the row counts loaded."""
    import tpch_reference as R
    li, n_li, _, li_voc = R.stage_host(conn, "lineitem", _LI_UNION)
    sel = lambda cols: R.select_cols(li, _LI_UNION, cols)  # noqa: E731
    out = {"rows": {"lineitem": n_li}}
    if "q6" in names:
        out["q6"] = R.q6_numpy(sel(R.Q6_COLS))
    if "q1" in names:
        out["q1"] = R.q1_numpy_rows(
            sel(R.Q1_COLS), li_voc[_LI_UNION.index("l_returnflag")],
            li_voc[_LI_UNION.index("l_linestatus")])
    if "q3" in names:
        od, n_o, _, _ = R.stage_host(conn, "orders", R.Q3_ORDERS_COLS)
        cu, n_c, _, c_voc = R.stage_host(conn, "customer",
                                         R.Q3_CUSTOMER_COLS)
        out["rows"].update(orders=n_o, customer=n_c)
        out["q3"] = R.q3_numpy(cu, od, sel(R.Q3_LINEITEM_COLS),
                               c_voc[1].index("BUILDING"))
    return out


def _close(got, want) -> bool:
    return abs(float(got) - want) <= REL_TOL * max(abs(want), 1.0)


def _epoch_day(iso: str) -> int:
    return (datetime.date.fromisoformat(str(iso))
            - datetime.date(1970, 1, 1)).days


def check_answer(label: str, name: str, rows, want) -> None:
    """Hold one query's rows (as the HTTP client returned them) to the
    reference. Raises SmokeFailure on any difference."""
    def fail(why):
        raise SmokeFailure(f"{label}: {why}\n  got  {rows}\n  want {want}")
    if name == "q6":
        if len(rows) != 1 or rows[0][0] is None \
                or not _close(rows[0][0], want):
            fail("revenue differs from the NumPy reference")
        return
    if len(rows) != len(want):
        fail(f"{len(rows)} rows, reference has {len(want)}")
    for g, w in zip(rows, want):
        if name == "q1":
            if (str(g[0]), str(g[1])) != (w[0], w[1]):
                fail("group keys differ")
            if not all(_close(gv, wv) for gv, wv in zip(g[2:9], w[2:9])):
                fail(f"DOUBLE aggregate beyond rel {REL_TOL}")
            if int(g[9]) != w[9]:
                fail("count differs (exact)")
        else:
            if int(g[0]) != w[0] or _epoch_day(g[2]) != w[2] \
                    or int(g[3]) != w[3]:
                fail("orderkey/orderdate/shippriority differ (exact)")
            if not _close(g[1], w[1]):
                fail(f"revenue beyond rel {REL_TOL}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

class Statement:
    """One statement stream of the smoke: a TPC-H query, the catalog
    (scale) it runs against, its session properties, its own client and
    the answer it is held to."""

    def __init__(self, door: Door, name: str, want, catalog="tpch",
                 properties=None, label=None):
        self.name, self.want = name, want
        self.label = label or name
        self.sql = tpch_sql(name, catalog)
        self.properties = properties
        self.client = door.client(catalog)

    def run(self, extra=None):
        """(rows, seconds), the rows held to the reference."""
        rows, secs = run_statement(
            self.client, self.sql, {**(self.properties or {}),
                                    **(extra or {})})
        check_answer(self.label, self.name, rows, self.want)
        return rows, secs


def cold_pass(statements) -> dict:
    """Every statement once, ALL AT ONCE, as a server that has just
    started meets its first requests: each generates and stages its
    scans and compiles its programs, and the compiles of different
    statements overlap (a cold start on the v5e is compile time, and
    one after another it does not fit the time limit). Each answer is
    held to the reference; the first failure is raised. {label: seconds
    until that statement's rows were back}."""
    import concurrent.futures as cf
    k0, t0 = compile_log().seconds(), time.perf_counter()
    with cf.ThreadPoolExecutor(len(statements)) as pool:
        futs = {st.label: pool.submit(st.run) for st in statements}
        secs = {label: f.result()[1] for label, f in futs.items()}
    _say(f"[cold] {len(statements)} statements at once answered and "
         f"matched in {time.perf_counter() - t0:.3f}s wall | each "
         f"{json.dumps({k: round(v, 3) for k, v in secs.items()})} | "
         f"{compile_log().seconds() - k0:.3f}s of XLA compile summed "
         f"over threads")
    return secs


def query_phase(st: Statement) -> dict:
    """One statement twice more, alone, after its cold run — warm (device scan
    cache, compiled programs) and warm under the ``profile`` session
    property (every dispatch bracketed, so system.runtime.executables
    shows device seconds) — each answer held to the reference, and the
    device shown to have worked."""
    rec = {"query": st.label}
    client = st.client
    # what reading system.runtime.executables itself invokes
    noise = set(invoked(executables(client), executables(client)))
    c0, e0 = path_counters(), executables(client)
    k0 = compile_log().seconds()
    _, rec["warm_s"] = st.run()
    e1 = executables(client)
    rec["rows"], rec["profiled_s"] = st.run({"profile": True})
    e2, c1 = executables(client), path_counters()
    rec["compile_s"] = compile_log().seconds() - k0
    rec["device_s"] = e2["device_s"] - e1["device_s"]
    runs = [invoked(a, b, noise) for a, b in ((e0, e1), (e1, e2))]
    rec["invocations"] = [sum(r.values()) for r in runs]
    rec["executables"] = sorted(set().union(*runs))
    rec["paths"] = {k: v - c0[k] for k, v in c1.items() if v != c0[k]}
    if min(rec["invocations"]) <= 0:
        raise SmokeFailure(
            f"{st.label}: a run invoked no executable "
            f"({rec['invocations']})")
    if not rec["device_s"] > 0.0:
        raise SmokeFailure(
            f"{st.label}: profiled run charged no device seconds")
    _say(f"[{rec['query']}] matched the NumPy reference x2 | warm "
         f"{rec['warm_s']:.3f}s | profiled {rec['profiled_s']:.3f}s, "
         f"device {rec['device_s']:.3f}s | invocations "
         f"{rec['invocations']} | compile in these runs "
         f"{rec['compile_s']:.3f}s")
    _say(f"[{rec['query']}] paths {json.dumps(rec['paths'])}")
    _say(f"[{rec['query']}] executables {' '.join(rec['executables'])}")
    return rec


def resident_platforms() -> set:
    """Platforms holding the device scan cache's arrays — where the
    queries' inputs (and so their kernels) really lived."""
    from presto_tpu.exec.scancache import CACHE
    out = set()
    with CACHE._lock:
        entries = list(CACHE._entries.values())
    for e in entries:
        for b in e.batches:
            for c in b.columns:
                out.update(d.platform for d in c.data.devices())
    return out


def _open_door(sf: float, scan_cache_bytes, cut_sf, cut_names):
    """(door, {query: (catalog, reference answer)}): the front door up,
    the NumPy answers made — ``cut_names`` against the cut catalog,
    the other queries against the full one."""
    t0 = time.perf_counter()
    door = start_server(sf, scan_cache_bytes, cut_sf=cut_sf)
    try:
        _say(f"[start] PrestoTpuServer on :{door.server.port}, TPC-H "
             + ", ".join(f"{c} sf={conn.sf:g}"
                         for c, conn in door.conns.items())
             + f", {time.perf_counter() - t0:.3f}s")
        cut = "tpch_cut" if "tpch_cut" in door.conns else "tpch"
        wants = {}
        for catalog in door.conns:
            names = [q for q in QUERIES
                     if (cut if q in cut_names else "tpch") == catalog]
            t0 = time.perf_counter()
            ref = reference_answers(door.conns[catalog], names)
            _say(f"[reference] {catalog}: NumPy answers of "
                 f"{' '.join(names)} over {json.dumps(ref['rows'])} "
                 f"generated rows in {time.perf_counter() - t0:.3f}s")
            wants.update({q: (catalog, ref[q]) for q in names})
        return door, wants
    except BaseException:
        door.close()
        raise


def smoke_one_chip(sf: float, platform: str, scan_cache_bytes=None,
                   q3_sf=None) -> list:
    """Front door up, reference made, Q6/Q1/Q3 answered cold (at once)
    and warm (one by one) and checked. ``platform`` is what the scan
    cache's arrays must live on; ``q3_sf`` cuts Q3's scale."""
    from presto_tpu.exec.scancache import CACHE
    door, wants = _open_door(sf, scan_cache_bytes, q3_sf, ("q3",))
    try:
        sts = [Statement(door, q, wants[q][1], wants[q][0])
               for q in QUERIES]
        cold = cold_pass(sts)
        recs = []
        for st in sts:
            rec = query_phase(st)
            rec["cold_s"] = cold[st.label]
            recs.append(rec)
        got = resident_platforms()
        if got != {platform}:
            raise SmokeFailure(
                f"scan-cache arrays live on {sorted(got)}, not on "
                f"{platform!r} alone")
        _say(f"[residency] scan cache holds {CACHE.resident_bytes} bytes "
             f"in {len(CACHE)} entries, all on {platform!r}")
        return recs
    finally:
        door.close()


def double_probe() -> dict:
    """What DOUBLE is on this device: precision and range of an f64 sum
    (printed for the record; .claude/skills/verify/SKILL.md quotes
    it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    x = np.full(1 << 20, 1.0 + 2.0 ** -40)
    s = float(jax.jit(jnp.sum)(jnp.asarray(x)))
    want = float(np.sum(x))
    big = float(jax.jit(lambda a: jnp.sum(a * 1e300))(
        jnp.asarray(np.full(8, 1.0))))
    tiny = float(jax.jit(lambda a: jnp.sum(a * 1e-300))(
        jnp.asarray(np.full(8, 1.0))))
    eps = float(jax.jit(lambda a: (a + 2.0 ** -52) - a)(jnp.float64(1.0)))
    return {"sum_rel_err": abs(s - want) / want,
            "holds_2^-40": s != float(1 << 20),
            "one_plus_2^-52_minus_one": eps,
            "sum_8e300": big, "sum_8e-300": tiny}


def peak_device_bytes() -> dict:
    import jax
    out = {}
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out[f"{d.platform}{d.id}"] = int(ms.get("peak_bytes_in_use", 0))
    return out


# ---------------------------------------------------------------------------
# --chips N: the mesh path only
# ---------------------------------------------------------------------------

MESH_QUERIES = ("q1", "q3")


def smoke_mesh(sf: float, n: int, scan_cache_bytes=None,
               q3_sf=None) -> list:
    """Q1 and Q3 with mesh_execution=on over ``n`` devices and again
    with mesh_execution=off, the answers held to each other and to the
    reference, and the staged scan columns shown spread over ``n``
    distinct devices."""
    from presto_tpu.exec import distributed as D

    spread = []
    assemble = D.DistributedExecutor._assemble

    def spy(self, parts, schema):
        out = assemble(self, parts, schema)
        arr = out.columns[0].data
        spread.append(len({s.device for s in arr.addressable_shards}))
        return out

    door, wants = _open_door(sf, scan_cache_bytes, q3_sf, ("q3",))
    D.DistributedExecutor._assemble = spy
    try:
        on = {"mesh_execution": "on", "mesh_devices": n}
        sts = [Statement(door, q, wants[q][1], wants[q][0], props,
                         f"{q}/{label}")
               for q in MESH_QUERIES
               for label, props in ((f"mesh{n}", on),
                                    ("single", {"mesh_execution": "off"}))]
        cold = cold_pass(sts)
        recs = []
        for st in sts:
            rec = query_phase(st)
            rec["cold_s"] = cold[st.label]
            meshed = bool(rec["paths"].get("mesh_path_selected_total"))
            if meshed != (st.properties["mesh_execution"] == "on"):
                raise SmokeFailure(
                    f"{st.label}: mesh path selected={meshed}")
            recs.append(rec)
        for m, single in zip(recs[0::2], recs[1::2]):
            name = m["query"].split("/")[0]
            check_answer(f"{m['query']} vs {single['query']}", name,
                         m["rows"], _as_reference(name, single["rows"]))
        if not spread or set(spread) != {n}:
            raise SmokeFailure(
                f"staged scan columns span {sorted(set(spread))} "
                f"devices, expected {n} on every assembled batch")
        _say(f"[spread] {len(spread)} assembled scan batches, each "
             f"sharded over {n} distinct devices; mesh answers equal "
             f"the single-device answers")
        return recs
    finally:
        D.DistributedExecutor._assemble = assemble
        door.close()


def _as_reference(name: str, rows):
    """Single-device rows in the reference's shape, so the mesh answer
    is held to them by the same check."""
    if name == "q1":
        return [(str(r[0]), str(r[1])) + tuple(float(v) for v in r[2:9])
                + (int(r[9]),) for r in rows]
    return [(int(r[0]), float(r[1]), _epoch_day(r[2]), int(r[3]))
            for r in rows]


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the single-chip SQL path (default). 4: ONLY "
                         "the mesh path over four chips and the "
                         "single-device path it is compared with")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script only passes on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX shows {len(devices)}", file=sys.stderr)
        return 2
    _say(f"[device] {len(devices)} x {dev.device_kind} ({dev.platform}), "
         f"jax {jax.__version__}")

    _say(f"[cut] Q3 runs at TPC-H SF{Q3_SCALE:g}, Q6 and Q1 at "
         f"SF{SCALE:g}: a cold Q3 at SF10 took 2231s on the v5e, "
         f"nearly all of it XLA compiles (measured, PR 23), over the "
         f"1200s limit alone")
    t0 = time.perf_counter()
    if args.chips == 1:
        smoke_one_chip(SCALE, "tpu", SCAN_CACHE_BYTES, Q3_SCALE)
        _say(f"[double] {json.dumps(double_probe())}")
    else:
        smoke_mesh(SCALE, args.chips, SCAN_CACHE_BYTES, Q3_SCALE)
    from presto_tpu.config import SESSION_PROPERTIES
    from presto_tpu.ops import pallas_scan
    _say(f"[pallas] scan kernels (sort-path i64 segment sums) in use on "
         f"this backend: {pallas_scan.pallas_supported()}; probe kernel "
         f"(join_pallas_probe) on by default: "
         f"{SESSION_PROPERTIES['join_pallas_probe'].default}")
    _say(f"[peak] device peak_bytes_in_use {json.dumps(peak_device_bytes())}")
    _say(f"[compile] {compile_log().seconds():.3f}s in XLA backend "
         f"compiles; slowest {json.dumps(compile_log().slowest())}")
    _say(f"[done] {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
