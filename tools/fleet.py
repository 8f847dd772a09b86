"""Coordinator-fleet launcher: N statement servers, one worker pool.

The horizontal-serving topology (docs/serving.md "Fleet"): every
coordinator is a full ``PrestoTpuServer`` over a ``ClusterRunner`` —
same caches, same resource groups, same SLO plane — joined into a
fleet via :meth:`PrestoTpuServer.enable_fleet`. Workers announce to
EVERY coordinator (multi-URI ``Announcer``), so the fleet shares one
elastic worker pool through the discovery plane while clients spread
statements across coordinators with ``presto_tpu.client.FleetClient``.

Because coordinator caches are per-process, real horizontal scale
needs real processes (the GIL caps in-process coordinator threads at
~1x): this module is both the subprocess entrypoint and the parent-side
launcher.

Child modes (one process each, stdin-tethered — EOF on stdin is the
orphan kill switch)::

    python -m tools.fleet --serve-coordinator --port P --node-id c0 \
        --peers http://127.0.0.1:P1,http://127.0.0.1:P2 \
        --sf 0.01 --sqlite /tmp/fleet.db --heartbeat-s 0.5
    python -m tools.fleet --serve-worker --port P \
        --coordinators http://127.0.0.1:P0,... --sf 0.01 \
        --sqlite /tmp/fleet.db

Parent API::

    fleet = launch_fleet(n_coordinators=3, sf=0.01, workers=1)
    fleet.urls               # coordinator base URLs
    fleet.metrics(1)         # GET /v1/metrics of coordinator 1
    fleet.slo(1)             # GET /v1/slo of coordinator 1
    fleet.kill_coordinator(0)  # SIGKILL — chaos, no drain
    fleet.stop()

The fleet chaos drill (tools/chaos_smoke.py ``--fleet``) rides this
module.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional

#: fleet children's default resource-group config: two weighted
#: tenants, both under SLO
_SLO_SPEC = {"latencyTargetMs": 2000, "latencyObjective": 0.95,
             "availabilityObjective": 0.99}
SERVING_GROUPS = {
    "rootGroups": [
        {"name": "serving", "hardConcurrencyLimit": 8,
         "maxQueued": 10_000,
         "subGroups": [
             {"name": "dash", "hardConcurrencyLimit": 8,
              "schedulingWeight": 2, "slo": dict(_SLO_SPEC)},
             {"name": "adhoc", "hardConcurrencyLimit": 8,
              "schedulingWeight": 1, "slo": dict(_SLO_SPEC)}]}],
    "selectors": [{"user": "dash-.*", "group": "serving.dash"},
                  {"group": "serving.adhoc"}]}


def _enable_compile_cache() -> None:
    """jax.config is per-process — children must opt in themselves."""
    from presto_tpu import enable_compile_cache
    enable_compile_cache()


def _build_catalogs(sf: float, sqlite_path: Optional[str]):
    from presto_tpu.connectors.memory import MemoryConnector
    from presto_tpu.connectors.spi import CatalogManager
    from presto_tpu.connectors.system import SystemConnector
    from presto_tpu.connectors.tpch import TpchConnector

    catalogs = CatalogManager()
    catalogs.register("tpch", TpchConnector(sf=sf))
    catalogs.register("memory", MemoryConnector())
    if sqlite_path:
        # the fleet's shared WRITABLE catalog: one database file, every
        # coordinator (and worker) a connection over it. Writes through
        # any coordinator bump its local data_version AND broadcast a
        # fleet bump; sqlite's own PRAGMA data_version backstops missed
        # broadcasts at revalidation time (foreign commits bump it)
        from presto_tpu.connectors.sqlite import SqliteConnector
        catalogs.register("fleetdb", SqliteConnector(sqlite_path))
    catalogs.register("system", SystemConnector(catalogs))
    return catalogs


def _stdin_tether(cleanup) -> None:
    """Block until stdin EOF (parent exit/stop), then clean up. The
    tether makes orphaned children self-terminate instead of leaking
    JAX processes when the parent is SIGKILLed."""
    try:
        while sys.stdin.buffer.read(4096):
            pass
    except OSError:
        pass
    cleanup()


def serve_coordinator(args) -> None:
    _enable_compile_cache()
    from presto_tpu.exec.cluster import ClusterRunner
    from presto_tpu.exec.discovery import DiscoveryNodeManager
    from presto_tpu.obs.timeseries import TIMESERIES
    from presto_tpu.server.protocol import PrestoTpuServer

    catalogs = _build_catalogs(args.sf, args.sqlite)
    discovery = DiscoveryNodeManager()
    runner = ClusterRunner(catalogs=catalogs, discovery=discovery,
                           tpch_sf=args.sf)
    runner.session.properties.update({"plan_template_cache": True,
                                      "result_cache": True})
    groups = (json.loads(args.groups_json) if args.groups_json
              else SERVING_GROUPS)
    # dense sampling: fleet runs are short-walled; the SLO timeline
    # needs real windowed points per phase
    TIMESERIES.configure(sample_interval_s=0.2)
    srv = PrestoTpuServer(runner, port=args.port,
                          resource_groups=groups, discovery=discovery)
    srv.start()
    peers = [u.strip() for u in (args.peers or "").split(",")
             if u.strip()]
    srv.enable_fleet(args.node_id, peers=peers,
                     heartbeat_s=args.heartbeat_s,
                     staleness_grace_s=args.staleness_grace_s or None)
    print(json.dumps({"ok": True, "role": "coordinator",
                      "nodeId": args.node_id,
                      "url": f"http://127.0.0.1:{srv.port}"}),
          flush=True)
    _stdin_tether(srv.stop)


def serve_worker(args) -> None:
    _enable_compile_cache()
    from presto_tpu.server.worker import WorkerServer

    catalogs = _build_catalogs(args.sf, args.sqlite)
    w = WorkerServer(catalogs=catalogs, port=args.port,
                     node_id=args.node_id or None)
    w.start()
    uris = [u.strip() for u in (args.coordinators or "").split(",")
            if u.strip()]
    # announce to EVERY coordinator: one worker pool, fleet-wide. The
    # 1s beat keeps membership fresh well inside discovery's TTL even
    # while coordinators churn
    w.start_announcing(uris, interval_s=1.0)
    print(json.dumps({"ok": True, "role": "worker",
                      "nodeId": w.node_id,
                      "url": f"http://127.0.0.1:{w.port}"}),
          flush=True)
    _stdin_tether(w.stop)


# ---------------------------------------------------------------------------
# parent side
# ---------------------------------------------------------------------------

def _free_ports(n: int) -> List[int]:
    """Reserve n distinct ephemeral ports (bind, record, close). The
    close-to-spawn window is racy in principle; in practice the
    container's ephemeral allocator doesn't re-issue a just-closed port
    before the child binds it."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _get_json(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode("utf-8"))


class FleetHandle:
    """A running fleet: coordinator/worker subprocess records plus the
    scrape and chaos surface the bench and tests drive."""

    def __init__(self, coordinators: List[dict], workers: List[dict],
                 sqlite_path: Optional[str],
                 spawn_cfg: Optional[dict] = None):
        self.coordinators = coordinators   # {proc, url, node_id, port}
        self.workers = workers
        self.sqlite_path = sqlite_path
        #: launch parameters, kept so the coordinator tier can scale
        #: up after launch (autoscaler scale_coordinator decisions)
        self.spawn_cfg = dict(spawn_cfg or {})
        self._coord_seq = len(coordinators)

    @property
    def urls(self) -> List[str]:
        return [c["url"] for c in self.coordinators]

    def live_urls(self) -> List[str]:
        return [c["url"] for c in self.coordinators
                if c["proc"].poll() is None]

    def metrics(self, i: int) -> Dict[str, float]:
        """Scrape coordinator ``i``'s /v1/metrics (Prometheus text) back
        into the registry's dotted-name map: ``fam{key="sub"}`` →
        ``fam.sub``. Samples with structural labels (le/quantile/node)
        are dropped — the fleet bench reads counters."""
        from presto_tpu.obs.exposition import parse_exposition
        url = self.coordinators[i]["url"] + "/v1/metrics"
        with urllib.request.urlopen(url, timeout=10) as r:
            text = r.read().decode("utf-8")
        samples, _types = parse_exposition(text)
        out: Dict[str, float] = {}
        for (name, labels), value in samples.items():
            labels = dict(labels)
            key = labels.pop("key", "")
            if labels:
                continue
            out[f"{name}.{key}" if key else name] = value
        return out

    def slo(self, i: int) -> dict:
        return _get_json(self.coordinators[i]["url"] + "/v1/slo")

    def fleet_status(self, i: int) -> dict:
        return _get_json(self.coordinators[i]["url"] + "/v1/fleet")

    def add_coordinator(self) -> dict:
        """Scale the coordinator tier UP: spawn one more fleet member
        peered with the current live coordinators. Its first heartbeat
        teaches every incumbent its url (dynamic peering,
        serving/fleet.fold_heartbeat), so the newcomer joins the
        broadcast/federation fabric without restarting anyone."""
        cfg = self.spawn_cfg
        node_id = f"coord-{self._coord_seq}"
        self._coord_seq += 1
        (port,) = _free_ports(1)
        argv = ["--serve-coordinator", "--port", str(port),
                "--node-id", node_id,
                "--peers", ",".join(self.live_urls()),
                "--sf", str(cfg.get("sf", 0.01)),
                "--heartbeat-s", str(cfg.get("heartbeat_s", 0.5))]
        if self.sqlite_path:
            argv += ["--sqlite", self.sqlite_path]
        if cfg.get("staleness_grace_s"):
            argv += ["--staleness-grace-s",
                     str(cfg["staleness_grace_s"])]
        if cfg.get("groups"):
            argv += ["--groups-json", json.dumps(cfg["groups"])]
        rec = {"proc": _spawn(argv), "node_id": node_id, "port": port,
               "url": f"http://127.0.0.1:{port}"}
        _await_ready(rec, cfg.get("ready_timeout_s", 300.0))
        self.coordinators.append(rec)
        return rec

    def drain_coordinator(self, i: int, timeout_s: float = 60.0) -> bool:
        """Scale the coordinator tier DOWN the polite way:
        ``PUT /v1/info/state SHUTTING_DOWN`` — the member sends its
        ``leaving`` farewell (peers drop its federated counts AND its
        peer-list entry immediately: explicit deregister, not the
        staleness grace), running queries page out, then the process
        exits. Never a kill."""
        rec = self.coordinators[i]
        p = rec["proc"]
        if p.poll() is not None:
            return False
        req = urllib.request.Request(
            rec["url"] + "/v1/info/state", data=b'"SHUTTING_DOWN"',
            method="PUT",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=10) as r:
                r.read()
        except OSError:
            return False
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                _get_json(rec["url"] + "/v1/info", timeout=2)
            except urllib.error.HTTPError:
                pass
            except OSError:
                break                  # socket refused: drained
            time.sleep(0.1)
        if p.stdin:
            try:
                p.stdin.close()
            except OSError:
                pass
        try:
            p.wait(timeout=max(1.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            return False
        return True

    # -- coordinator_scaler duck (exec/autoscale.AutoscaleController) --------
    def scale_up(self, reason: str = "") -> bool:
        """Admission-bound: one more coordinator = one more set of
        hard-concurrency slots over the same shared worker pool."""
        self.add_coordinator()
        return True

    def scale_down(self, reason: str = "") -> bool:
        live = [i for i, c in enumerate(self.coordinators)
                if c["proc"].poll() is None]
        if len(live) <= 2:             # a fleet needs >= 2 members
            return False
        return self.drain_coordinator(live[-1])

    def kill_coordinator(self, i: int) -> None:
        """SIGKILL — the real chaos primitive: no drain, no farewell
        heartbeat; peers learn via the staleness grace, clients via
        transport errors (FleetClient fails over)."""
        p = self.coordinators[i]["proc"]
        if p.poll() is None:
            p.kill()
            p.wait(timeout=30)

    def stop(self) -> None:
        procs = ([c["proc"] for c in self.coordinators]
                 + [w["proc"] for w in self.workers])
        for p in procs:
            if p.poll() is None and p.stdin:
                try:
                    p.stdin.close()   # tether EOF → clean child stop
                except OSError:
                    pass
        deadline = time.monotonic() + 20
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(0.1,
                                       deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=10)


def _spawn(argv: List[str]) -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-m", "tools.fleet"] + argv,
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        start_new_session=True)


def _await_ready(rec: dict, timeout_s: float) -> None:
    """Read the child's one-line ready doc (emitted after JAX import +
    data generation — the slow part), enforcing a wall deadline."""
    p = rec["proc"]

    def alarm(signum, frame):
        raise TimeoutError(
            f"fleet child {rec['node_id']} not ready in {timeout_s}s")

    old = signal.signal(signal.SIGALRM, alarm)
    signal.alarm(int(timeout_s))
    try:
        line = p.stdout.readline()
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
    if not line:
        raise RuntimeError(
            f"fleet child {rec['node_id']} died before ready "
            f"(rc={p.poll()})")
    doc = json.loads(line)
    assert doc.get("ok"), doc
    rec["url"] = doc["url"]


def check_children_can_hold_devices(n_children: int) -> None:
    """Refuse a spawn that could only mislead. Every fleet child
    imports JAX and takes whatever devices its environment shows it,
    and a chip belongs to ONE process: a parent that has initialised a
    JAX backend holds the chip its children would need, and N
    device-holding children need N chips, which this launcher does not
    hand out. Either the caller puts the children on the CPU,
    explicitly (``JAX_PLATFORMS=cpu`` in the environment they inherit),
    or there is one child and a parent that stayed off JAX. The fleet
    on real chips is a later issue (ROADMAP.md)."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "this process has initialised a JAX backend and holds its "
            "devices; children that need them would fail or hang. "
            "Launch from a parent that stays off JAX, or set "
            "JAX_PLATFORMS=cpu for the children")
    if n_children > 1:
        raise RuntimeError(
            f"{n_children} device-holding children need {n_children} "
            "chips and this launcher assigns none: set "
            "JAX_PLATFORMS=cpu to run the fleet's processes on the CPU")


def launch_fleet(n_coordinators: int = 3, sf: float = 0.01,
                 workers: int = 1, sqlite_path: Optional[str] = None,
                 heartbeat_s: float = 0.5,
                 staleness_grace_s: Optional[float] = None,
                 groups: Optional[dict] = None,
                 ready_timeout_s: float = 300.0) -> FleetHandle:
    """Spawn the fleet: ``n_coordinators`` statement servers (each a
    fleet member, peered all-to-all) and ``workers`` worker processes
    announcing to every coordinator. Blocks until every child printed
    its ready line.

    Every child is a process that imports JAX: see
    :func:`check_children_can_hold_devices` — the caller sets
    ``JAX_PLATFORMS=cpu``, or the spawn is refused."""
    if n_coordinators < 2:
        raise ValueError("a fleet needs >= 2 coordinators")
    check_children_can_hold_devices(n_coordinators + workers)
    ports = _free_ports(n_coordinators + workers)
    coord_ports = ports[:n_coordinators]
    urls = [f"http://127.0.0.1:{p}" for p in coord_ports]
    coords: List[dict] = []
    for i, port in enumerate(coord_ports):
        node_id = f"coord-{i}"
        peers = ",".join(u for j, u in enumerate(urls) if j != i)
        argv = ["--serve-coordinator", "--port", str(port),
                "--node-id", node_id, "--peers", peers,
                "--sf", str(sf), "--heartbeat-s", str(heartbeat_s)]
        if sqlite_path:
            argv += ["--sqlite", sqlite_path]
        if staleness_grace_s:
            argv += ["--staleness-grace-s", str(staleness_grace_s)]
        if groups:
            argv += ["--groups-json", json.dumps(groups)]
        coords.append({"proc": _spawn(argv), "node_id": node_id,
                       "port": port, "url": f"http://127.0.0.1:{port}"})
    wrecs: List[dict] = []
    for i, port in enumerate(ports[n_coordinators:]):
        node_id = f"fleet-worker-{i}"
        argv = ["--serve-worker", "--port", str(port),
                "--node-id", node_id,
                "--coordinators", ",".join(urls), "--sf", str(sf)]
        if sqlite_path:
            argv += ["--sqlite", sqlite_path]
        wrecs.append({"proc": _spawn(argv), "node_id": node_id,
                      "port": port, "url": f"http://127.0.0.1:{port}"})
    handle = FleetHandle(
        coords, wrecs, sqlite_path,
        spawn_cfg={"sf": sf, "heartbeat_s": heartbeat_s,
                   "staleness_grace_s": staleness_grace_s,
                   "groups": groups,
                   "ready_timeout_s": ready_timeout_s})
    try:
        for rec in coords + wrecs:
            _await_ready(rec, ready_timeout_s)
        # a coordinator with ZERO visible workers fails SELECTs
        # ("no active workers") — hold the ready barrier until every
        # coordinator's discovery has the full worker pool
        deadline = time.monotonic() + ready_timeout_s
        for i in range(len(coords)):
            while True:
                seen = len(handle.fleet_status(i).get("workers", ()))
                if seen >= workers:
                    break
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"coordinator {coords[i]['node_id']} sees "
                        f"{seen}/{workers} workers")
                time.sleep(0.1)
    except BaseException:
        handle.stop()
        raise
    return handle


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tools.fleet", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--serve-coordinator", action="store_true")
    ap.add_argument("--serve-worker", action="store_true")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--node-id", default="")
    ap.add_argument("--peers", default="")
    ap.add_argument("--coordinators", default="")
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--sqlite", default="")
    ap.add_argument("--heartbeat-s", type=float, default=0.5)
    ap.add_argument("--staleness-grace-s", type=float, default=0.0)
    ap.add_argument("--groups-json", default="")
    args = ap.parse_args(argv)
    if args.serve_coordinator:
        serve_coordinator(args)
        return 0
    if args.serve_worker:
        serve_worker(args)
        return 0
    ap.error("pick one of --serve-coordinator / --serve-worker")
    return 2


if __name__ == "__main__":
    sys.exit(main())
