"""The harness: one cell, one process, one run.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
the deployment) and a traffic mix (``traffic/<name>.json``: who sends
what, and which query template). The template (``templates/<name>.py``)
holds the SQL text with named holes, the substitution rule, the plain
reference and the bytes its scans must read. Every metric is a reader of
its own (``metrics/<name>.py``). All are found by name: a later PR adds
a cell, a template or a metric by adding files and one entry.

From the program the harness takes only the system under test (the
server, the runner, the connector, the client) and its spans and
counters. Traffic, timing, the reduction of the trace, the peaks, the
reference and the comparison that decides ``correct`` live here.
"""
from __future__ import annotations

import contextlib
import datetime
import importlib.util
import json
import math
import os
import random
import shutil
import sys
import tempfile
import time

import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))

#: a traced run traces the whole queries of its window's last seconds,
#: this many (one query at the least)
TRACE_SECONDS = 10.0

#: warm-up sends the run's statements again until a pass compiles
#: nothing, this many passes at the most
WARMUP_PASSES = 4

#: a compile this long is printed when it ends, so that a first run cut
#: at its time limit leaves its compile log behind
SLOW_COMPILE_SECONDS = 10.0


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def _module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it names loaded."""

    def __init__(self, bench: dict, workload: str, root: str):
        entry = next((w for w in bench["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
        self.bench, self.name, self.chips = bench, workload, entry["chips"]
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        self.config = _json(os.path.join(root, conf["file"]))
        self.traffic = _json(os.path.join(HERE, "traffic",
                                          entry["traffic"] + ".json"))
        check_traffic(entry["traffic"], self.traffic)
        self.template = _module("templates", self.traffic["template"])
        self.data = _module("", self.config["reference_data"])
        self.sf = float(self.config["scale_factor"])

    def metrics(self, section: str) -> list:
        """The cell's metrics of ``end_to_end`` or ``per_layer``: those
        that name no cells, and those that name this one."""
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]


def load_cell(workload: str) -> Cell:
    """The cell as the checkout's ``BENCHMARK.json`` defines it."""
    root = os.path.dirname(HERE)
    return Cell(_json(os.path.join(root, "BENCHMARK.json")), workload, root)


def peaks(device_kind: str) -> dict:
    table = _json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"benchmarks/peaks.json: add the row, with its "
                         f"source")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# the system under test (a copy of chip_smoke.py's Door and CompileLog)
# ---------------------------------------------------------------------------

class CompileLog:
    """Every XLA backend compile of the process from JAX's own
    monitoring event, with the time it ended: (function, seconds, at).
    A persistent-cache hit shows as its short load."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            name = kw.get("fun_name", "?")
            self.events.append((name, duration, time.perf_counter()))
            if duration >= SLOW_COMPILE_SECONDS:
                say(f"[compiled] {name} {duration:.3f}s")

    def between(self, t0: float, t1: float) -> list:
        return [e for e in self.events if t0 <= e[2] < t1]

    @staticmethod
    def slowest(events, top: int = 8) -> list:
        by: dict = {}
        for name, secs, _ in events:
            a = by.setdefault(name, [0, 0.0])
            a[0] += 1
            a[1] += secs
        rows = sorted(by.items(), key=lambda kv: -kv[1][1])
        return [[n, c, round(s, 3)] for n, (c, s) in rows[:top]]


class Door:
    """The front door as a deployment runs it: ``PrestoTpuServer`` (port
    0) over ``LocalRunner`` over the connector the configuration names,
    and one ``StatementClient`` (a client is thread-confined) that sends
    the configuration's session properties with every statement."""

    def __init__(self, config: dict):
        import presto_tpu
        from presto_tpu.client import StatementClient
        from presto_tpu.connectors.spi import CatalogManager
        from presto_tpu.exec.runner import LocalRunner
        from presto_tpu.exec.scancache import CACHE
        from presto_tpu.server.protocol import PrestoTpuServer

        presto_tpu.enable_compile_cache()
        conn = config["connector"]
        connector = getattr(importlib.import_module(conn["module"]),
                            conn["class"])(**conn["args"])
        catalogs = CatalogManager()
        catalogs.register(config["catalog"], connector)
        runner = LocalRunner(catalogs=catalogs, catalog=config["catalog"],
                             rows_per_batch=int(config["rows_per_batch"]))
        CACHE.set_limit(int(config["scan_cache_bytes"]))
        self.server = PrestoTpuServer(runner, port=0)
        self.server.start()
        self.client = StatementClient(
            f"http://127.0.0.1:{self.server.port}", user="benchmark",
            catalog=config["catalog"])
        for k, v in config["session_properties"].items():
            self.client.session_properties[k] = str(v)

    def query(self, sql: str):
        """(rows or None, error or None, seconds) of one statement:
        ``POST /v1/statement``, then ``nextUri`` until the last page,
        timed at the client."""
        t0 = time.perf_counter()
        try:
            rows, err = self.client.execute(sql).rows, None
        except Exception as e:      # a FAILED query or a broken transport
            rows, err = None, f"{type(e).__name__}: {e}"
        return rows, err, time.perf_counter() - t0

    def close(self) -> None:
        self.client.close()
        self.server.stop()


def counters() -> dict:
    """Every scalar of the program's metrics registry, by name."""
    from presto_tpu.obs.metrics import REGISTRY
    return {m["name"]: float(m["value"]) for m in REGISTRY.snapshot()
            if isinstance(m.get("value"), (int, float))}


# ---------------------------------------------------------------------------
# traffic: one general generator over a data file
# ---------------------------------------------------------------------------

def draw_bindings(template, traffic: dict, seed: int) -> list:
    """The run's substitution parameters: ``traffic["bindings"]``
    distinct draws of the template's rule, from ``seed`` alone."""
    rng = random.Random(seed)
    out: list = []
    while len(out) < int(traffic["bindings"]):
        b = template.draw(rng)
        if b not in out:
            out.append(b)
    return out


def check_traffic(name: str, traffic: dict) -> None:
    """Every parameter of a traffic file is read. The one generator
    there is sends a closed loop from one client, the bindings in turn:
    a file that asks for anything else ends the run before set-up,
    rather than run as something it does not say."""
    for key, known in (("loop", "closed"), ("clients", 1),
                       ("order", "in turn")):
        if traffic[key] != known:
            raise SystemExit(f"traffic {name!r}: {key} {traffic[key]!r} is "
                             f"not generated here (only {known!r})")


def closed_loop(door: Door, statements: list, seconds: float,
                trace_seconds=None) -> tuple:
    """One client, one statement at a time, the bindings in turn. The
    window starts at the first POST and ends when the query in flight
    at ``seconds`` has returned. A traced run (``trace_seconds`` given)
    runs its first queries untraced and starts the profiler
    ``trace_seconds`` before the window's end, between two queries, so
    that one window holds queries at their own speed and queries under
    the tracer. ([(binding index, rows, error, seconds, traced, the
    process's CPU seconds meanwhile)], window start, end, the
    :class:`TraceSlice` or None)."""
    done, slice_ = [], None
    t0 = time.perf_counter()
    while True:
        which = len(done) % len(statements)
        if (trace_seconds is not None and slice_ is None
                and time.perf_counter() - t0 >= seconds - trace_seconds):
            slice_ = TraceSlice()
        cpu0 = time.process_time()
        with (slice_.query() if slice_ is not None
              else contextlib.nullcontext()):
            rows, err, secs = door.query(statements[which])
        done.append((which, rows, err, secs, slice_ is not None,
                     time.process_time() - cpu0))
        if time.perf_counter() - t0 >= seconds:
            return done, t0, time.perf_counter(), slice_


# ---------------------------------------------------------------------------
# the traced slice
# ---------------------------------------------------------------------------

class TraceSlice:
    """``jax.profiler`` over whole queries, every one inside a
    ``TraceAnnotation`` so that the reduction knows the slice and can
    name the idle gaps."""

    def __init__(self):
        import jax
        self._jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # else millions of host events
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def query(self):
        return self._jax.profiler.TraceAnnotation(tracereduce.QUERY_MARK)

    def stop(self) -> None:
        self._jax.profiler.stop_trace()

    def reduce(self) -> dict:
        """The trace reduced (see tracereduce.py), its files removed."""
        try:
            files = [os.path.join(r, f) for r, _, fs in os.walk(self.dir)
                     for f in fs if f.endswith(".xplane.pb")]
            if not files:
                return {}
            t0 = time.perf_counter()
            out = tracereduce.reduce_trace(files[0])
            say(f"[trace] {os.path.getsize(files[0])} bytes reduced in "
                f"{time.perf_counter() - t0:.3f}s: {out.get('queries')} "
                f"queries traced over {out.get('window_s')}s")
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# the comparison that decides `correct`
# ---------------------------------------------------------------------------

def _cell(kind: str, value):
    if value is None:
        return None
    if kind == "int":
        return int(value)
    if kind == "date":      # ISO from the client, epoch days from a reference
        if isinstance(value, int):
            return value
        return (datetime.date.fromisoformat(str(value))
                - datetime.date(1970, 1, 1)).days
    if kind == "double":
        return float(value)
    return str(value)


def compare_rows(kinds, got, want) -> tuple:
    """(widest relative gap of a DOUBLE cell, cells that differ where
    they must be equal) of one answer against the reference's. A row
    too many or too few counts each of its cells as differing; so does
    a NULL or a cell that does not parse."""
    gap, wrong = 0.0, abs(len(got) - len(want)) * len(kinds)
    for g, w in zip(got, want):
        if len(g) != len(kinds):
            wrong += len(kinds)
            continue
        for kind, gv, wv in zip(kinds, g, w):
            try:
                gv = _cell(kind, gv)
            except (TypeError, ValueError):
                gv = None
            if kind != "double" or gv is None or wv is None:
                wrong += gv != wv
            elif not math.isfinite(gv):
                wrong += 1
            else:
                gap = max(gap, abs(gv - wv) / max(abs(wv), 1e-300))
    return gap, wrong


def judge(cell: Cell, bindings: list, done: list, answers: list) -> dict:
    """The numbers compared, each beside its limit, over EVERY query the
    window completed."""
    gap, wrong, failed = 0.0, 0, 0
    limit, shown = cell.template.DOUBLE_REL_LIMIT, False
    for which, rows, err, *_ in done:
        if err is not None:
            failed += 1
            g, w = 0.0, 0
        else:
            g, w = compare_rows(cell.template.KINDS, rows, answers[which])
            gap, wrong = max(gap, g), wrong + w
        if (err is not None or g > limit or w) and not shown:
            shown = True        # the first answer at fault, for the record
            print(f"[fault] binding {json.dumps(bindings[which])}: "
                  f"{err or f'gap {g!r}, {w} exact cells wrong'}\n"
                  f"  got  {str(rows)[:600]}\n"
                  f"  want {str(answers[which])[:600]}",
                  file=sys.stderr, flush=True)
    return {
        "double_rel_gap": {"value": gap, "limit": limit},
        "exact_cells_wrong": {"value": wrong, "limit": 0},
        "queries_failed": {"value": failed, "limit": 0},
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """Set up, warm up, measure, check; the result line as a dict."""
    import jax
    from presto_tpu.obs.trace import TRACER

    log = CompileLog()
    devices = jax.devices()
    bindings = draw_bindings(cell.template, cell.traffic, seed)
    statements = [cell.template.SQL.format(**b) for b in bindings]
    say(f"[cell] {cell.name}: template {cell.traffic['template']} at "
        f"SF{cell.sf:g}, seed {seed}, bindings {json.dumps(bindings)}")

    door = Door(cell.config)
    try:
        # warm-up: every statement of the run, until a pass compiles
        # nothing (the first stages the scans and compiles; a new
        # literal is a new XLA program, so each binding compiles)
        for p in range(WARMUP_PASSES):
            t0 = time.perf_counter()
            for i, sql in enumerate(statements):
                t1 = time.perf_counter()
                _, err, secs = door.query(sql)
                if err is not None:
                    raise SystemExit(f"warm-up statement failed: {err}")
                evs = log.between(t1, time.perf_counter())
                say(f"[warm-up {p}.{i}] {secs:.3f}s, {len(evs)} compiles "
                    f"{sum(e[1] for e in evs):.3f}s "
                    f"{json.dumps(log.slowest(evs, 4))}")
            if not log.between(t0, time.perf_counter()):
                break
        TRACER.clear()
        TRACER.enable(trace)
        c0 = counters()
        setup_events = list(log.events)

        setup_s = time.perf_counter() - t_start
        done, w0, w1, slice_ = closed_loop(
            door, statements, seconds, TRACE_SECONDS if trace else None)
        c1 = counters()
        if slice_ is not None:
            slice_.stop()       # after the window: it takes many seconds
        spans = TRACER.export() if trace else []
        TRACER.enable(False)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices[:cell.chips])
    finally:
        door.close()

    reduction = slice_.reduce() if slice_ is not None else {}

    # the reference, after the window and outside set-up: plain NumPy
    # on the host over the benchmark's own data
    t0 = time.perf_counter()
    answers = cell.template.reference(cell.data, cell.sf, bindings)
    say(f"[reference] {len(bindings)} answers in "
        f"{time.perf_counter() - t0:.3f}s")
    checked = judge(cell, bindings, done, answers)

    dev = devices[0]
    run = {
        "cell": cell, "seconds": [d[3] for d in done],
        "untraced_seconds": [d[3] for d in done if not d[4]],
        "window_s": w1 - w0, "setup_s": setup_s, "spans": spans,
        "counters": {k: v - c0.get(k, 0.0) for k, v in c1.items()},
        "compiles_setup": setup_events,
        "compiles_window": log.between(w0, w1),
        "trace": reduction, "peak_bytes": peak,
        "peaks": peaks(dev.device_kind) if dev.platform == "tpu" else None,
    }
    metrics = {}
    for m in cell.metrics("per_layer" if trace else "end_to_end"):
        value = _module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    secs = sorted(run["seconds"])
    paths = {k: v for k, v in run["counters"].items()
             if v and "_selected_total" in k}
    slowest = max(done, key=lambda d: d[3])
    others = [d[5] for d in done if d is not slowest] or [0.0]
    say(f"[window] {len(done)} queries in {w1 - w0:.3f}s, each "
        f"{secs[0]:.3f}/{secs[len(secs) // 2]:.3f}/{secs[-1]:.3f}s "
        f"(min/median/max), {len(done) - len(run['untraced_seconds'])} of "
        f"them under the tracer; the slowest, number {done.index(slowest)}, "
        f"had {slowest[5]:.3f}s of the process's CPU (all threads), the "
        f"others {sum(others) / len(others):.3f}s; paths {json.dumps(paths)}")
    say(f"[compile] set-up {sum(e[1] for e in setup_events):.3f}s in "
        f"{len(setup_events)} programs, slowest "
        f"{json.dumps(log.slowest(setup_events))}; in the window "
        f"{json.dumps(log.slowest(run['compiles_window']))}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checked.values()),
        "attempted": len(done),
        "failed": checked["queries_failed"]["value"],
        "metrics": metrics, "device": device,
    }
    if trace and reduction:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["checked"] = checked
    for name, c in checked.items():
        print(f"[checked] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return result
