"""Config-file system: etc/config.properties + etc/catalog/*.properties.

The role of the reference's airlift bootstrap config binding (reference
server/PrestoServer.java:86 Bootstrap over @Config classes like
ServerConfig/TaskManagerConfig; StaticCatalogStore loading
etc/catalog/*.properties into ConnectorManager.createConnection, and
spi/Plugin.java ConnectorFactories resolved by 'connector.name').

Layout:

    etc/
      config.properties          node.id, coordinator, discovery.uri,
                                 http-server.http.port, session defaults
                                 (session.<name>=<value>)
      catalog/
        tpch.properties          connector.name=tpch
                                 tpch.scale-factor=1
        warehouse.properties     connector.name=orc
                                 orc.root=/data/warehouse

Connector factories are a plain registry keyed by ``connector.name`` —
the plugin SPI's loading half (PluginManager.java:121's role without
classloader isolation, which Python does not need).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, Optional

from .connectors.spi import CatalogManager


# -- session-property registry -----------------------------------------------
# The single declaration point for every session property the engine
# reads (the reference's SystemSessionProperties.java role): name ->
# type/default/doc plus an optional extra validator. SET SESSION on an
# unknown or type-mismatched name raises a user-facing error instead of
# silently latching a string no read site will ever consult, and the
# static registry lint (tools/analyze/registries.py) cross-checks every
# ``session.properties.get("...")``/``bool_property(...)`` literal in
# the tree against this table — a typo'd property name fails CI, not a
# user's dashboard.

@dataclasses.dataclass(frozen=True)
class SessionProperty:
    name: str
    type: str           # boolean | integer | double | varchar | duration
    default: object     # documentation only; read sites supply defaults
    doc: str
    validator: Optional[Callable[[object], object]] = None


class SessionPropertyError(ValueError):
    """User-facing SET SESSION rejection (unknown name / bad type)."""

    name = "INVALID_SESSION_PROPERTY"


SESSION_PROPERTIES: Dict[str, SessionProperty] = {}


def _sp(name: str, type_: str, default, doc: str,
        validator: Optional[Callable] = None) -> None:
    SESSION_PROPERTIES[name] = SessionProperty(name, type_, default, doc,
                                               validator)


def _valid_retry_policy(v):
    p = str(v).upper()
    if p not in ("TASK", "QUERY", "NONE"):
        raise SessionPropertyError(
            f"retry_policy must be TASK, QUERY or NONE, got {v!r}")
    return p


def _valid_duration(v):
    from .exec.cluster import parse_duration_s
    try:
        parse_duration_s(v)
    except ValueError as e:
        raise SessionPropertyError(str(e)) from None
    return v


_sp("broadcast_join_row_limit", "integer", 4_000_000,
    "build sides at or under this many estimated rows broadcast; "
    "larger ones hash-partition")
_sp("cluster_memory_limit", "integer", None,
    "cluster-wide reservation cap in bytes; the coordinator memory "
    "manager kills the largest query above it")
_sp("dense_grouping", "boolean", True,
    "allow the stats-bounded dense (scatter-path) GROUP BY plan")
_sp("enable_dynamic_filtering", "boolean", True,
    "build-side key bounds prune probe-side scans at runtime")
_sp("exchange_failure_timeout_s", "double", 45.0,
    "seconds an exchange client retries transport loss before failing "
    "the upstream task")
_sp("fair_scheduling", "boolean", True,
    "time-slice concurrent queries through the device scheduler")
_sp("fused_compact_floor", "integer", 1 << 17,
    "skip fused-chain compaction below this batch capacity")
_sp("fused_compact_window", "integer", 4,
    "fused-chain liveness readbacks amortize over this many batches")
_sp("fused_pipeline", "boolean", True,
    "fuse filter->project->join chains into one jitted pipeline")
_sp("grouped_execution", "boolean", True,
    "run bucketed scans one lifespan at a time")
_sp("join_dense_path", "boolean", True,
    "stats-driven dense-key direct-address join builds: the planner "
    "attaches hard build-key bounds (JoinNode.key_bounds) and the "
    "executor answers bounded key tuples in one gather")
_sp("join_pallas_probe", "boolean", False,
    "fuse direct-join probe lookup + liveness + payload gathers into "
    "the Pallas ragged-gather kernel on TPU backends. Off by default: "
    "the kernel does not lower for a TPU v5e on the installed JAX "
    "(ops/pallas_join docstring); switched on, a kernel compile "
    "failure fails the query")


def _valid_mesh_execution(v):
    m = str(v).lower()
    if m not in ("auto", "on", "off"):
        raise SessionPropertyError(
            f"mesh_execution must be auto, on or off, got {v!r}")
    return m


_sp("mesh_execution", "varchar", "auto",
    "multi-chip SPMD execution substrate: auto runs SQL on the device "
    "mesh whenever more than one device is visible and the plan "
    "fragments into mesh stages, on forces it, off pins the "
    "single-device path (PRESTO_TPU_MESH_EXECUTION overrides the "
    "unset default)", _valid_mesh_execution)
_sp("mesh_devices", "integer", 0,
    "devices in the execution mesh (0 = every visible device); 1 "
    "behaves like mesh_execution=off under auto")
_sp("mesh_fused_exchange", "boolean", True,
    "fused SPMD exchange (exec/distributed.py): compute + bucket-count "
    "+ ship collapse into one shard_map program per round, "
    "stats-bounded aggregation stages batch multiple rounds into a "
    "single lax.fori_loop dispatch with donated shard buffers, and "
    "control scalars are fetched once per stage; off is the escape "
    "hatch back to the per-round host control plane")
_sp("mesh_fused_loop_rounds", "integer", 32,
    "cap on chunks one fused lax.fori_loop dispatch may stack "
    "(bounds resident memory: the stacked wave holds every chunk of "
    "the wave on device at once); minimum 1")
_sp("mesh_flight", "boolean", True,
    "mesh flight recorder (obs/flight.py): record every exchange "
    "round of a mesh-path query (dispatch, staging, control sync, "
    "repartition, stall) for the post-query wall-clock attribution "
    "surfaced in EXPLAIN ANALYZE, system.runtime.mesh_rounds and the "
    "mesh_attr_* metric families; off skips recording entirely")
_sp("plan_template_cache", "boolean", False,
    "fingerprint the PARAMETERIZED statement shape (literals "
    "hole-punched) so a fleet of bindings shares one optimized plan + "
    "one warm executable set; optimizer decisions that consulted a "
    "literal record equality guards and fall back to per-binding "
    "fingerprints when a binding flips them (serving/template.py)")
_sp("plan_cache", "boolean", True,
    "serve repeated statements from the compiled-plan cache "
    "(fingerprinted bound AST; skips parse/plan/optimize)")
_sp("probe_prefetch", "boolean", True,
    "overlap probe-side host staging with device dispatch")
_sp("profile", "boolean", False,
    "bracket every jit dispatch and attribute device time per operator")
_sp("push_partial_aggregation_through_join", "boolean", True,
    "eager aggregation below joins when the grouping key covers the "
    "probe join key")
_sp("query_max_memory", "integer", None,
    "per-query memory pool limit in bytes (spill beyond it)")
_sp("query_max_run_time", "duration", None,
    "wall-clock deadline (e.g. 30s, 500ms); the query aborts past it",
    _valid_duration)
_sp("query_queued_timeout", "duration", None,
    "admission deadline (e.g. 5s): a query still queued in its "
    "resource group past it fails with QUERY_QUEUED_TIMEOUT",
    _valid_duration)
_sp("query_retry_attempts", "integer", 1,
    "whole-query re-runs under retry_policy=QUERY")
_sp("result_cache", "boolean", False,
    "serve repeated statements from the versioned result cache "
    "(serving/resultcache.py): stored host rows when every scanned "
    "table's data_version matches, changed-split delta recompute + "
    "distributive merge when a filebase table grew append-only")
_sp("retry_policy", "varchar", "TASK",
    "fault-tolerance mode: TASK, QUERY or NONE", _valid_retry_policy)
_sp("role", "varchar", None,
    "active role for access-control checks (SET ROLE)")
_sp("scan_cache", "boolean", True,
    "serve repeated scans from the device-resident scan cache")
_sp("scan_pad_batches", "boolean", True,
    "pad ragged final split chunks to the stream's capacity bucket")
_sp("scan_prefetch", "boolean", True,
    "decode+stage splits on background threads ahead of the consumer")
_sp("scan_prefetch_depth", "integer", 4,
    "buffered batches per split in the prefetch pipeline")
_sp("scan_threads", "integer", 2,
    "background decode threads per scan")
_sp("shared_scan", "boolean", True,
    "attach concurrent identical-split scan misses to one in-flight "
    "decode instead of racing duplicates")
_sp("speculative_execution", "boolean", True,
    "duplicate straggler tasks on another node, first finished wins")
_sp("speculative_spool_reads", "boolean", True,
    "on an exchange transport failure with a committed spool copy, "
    "race the spool replay against a resumed live pull (first "
    "complete remainder wins, loser cancelled) instead of committing "
    "to the replay — pays off when the spool is a latency-modeled "
    "object store and the worker was merely restarting")
_sp("spill_partitions", "integer", 16,
    "hash partitions for spill-to-host aggregation")
_sp("spool_exchange", "boolean", True,
    "write exchange pages through to the durable page-addressed spool "
    "under retry_policy=TASK (false = PR 5 retained in-memory buffers)")
_sp("spill_path", "varchar", None,
    "directory for second-tier disk spill pages")
_sp("spill_to_disk_bytes", "integer", 4 << 30,
    "staged host bytes beyond this flush to compressed disk pages")
_sp("stats_bounded_grouping", "boolean", True,
    "attach hard per-key bounds from connector stats to aggregations")
_sp("task_concurrency", "integer", 1,
    "parallel driver threads per local pipeline")
_sp("task_retry_attempts", "integer", 2,
    "per-task retry budget under retry_policy=TASK")
_sp("task_retry_backoff_s", "double", 0.05,
    "base backoff between task retry attempts (exponential)")

_TRUE = ("true", "1", "on", "yes")
_FALSE = ("false", "0", "off", "no")


def validate_session_property(name: str, value):
    """Coerced canonical value for ``SET SESSION name = value``; raises
    :class:`SessionPropertyError` on an unknown name or a value that
    does not parse as the declared type."""
    sp = SESSION_PROPERTIES.get(name)
    if sp is None:
        raise SessionPropertyError(
            f"unknown session property {name!r} "
            f"(known: {', '.join(sorted(SESSION_PROPERTIES))})")

    def bad(detail: str = ""):
        return SessionPropertyError(
            f"session property {name!r} expects a {sp.type}, "
            f"got {value!r}" + (f" ({detail})" if detail else ""))

    out = value
    if sp.type == "boolean":
        if isinstance(value, bool):
            out = value
        elif isinstance(value, str) \
                and value.strip().lower() in _TRUE + _FALSE:
            out = value.strip().lower() in _TRUE
        else:
            raise bad()
    elif sp.type == "integer":
        if isinstance(value, bool):
            raise bad()
        elif isinstance(value, int):
            out = value
        elif isinstance(value, str):
            try:
                out = int(value.strip())
            except ValueError:
                raise bad() from None
        else:
            raise bad()
    elif sp.type == "double":
        if isinstance(value, bool):
            raise bad()
        elif isinstance(value, (int, float)):
            out = float(value)
        elif isinstance(value, str):
            try:
                out = float(value.strip())
            except ValueError:
                raise bad() from None
        else:
            raise bad()
    elif sp.type == "varchar":
        if not isinstance(value, str):
            raise bad()
    elif sp.type == "duration":
        if not isinstance(value, (str, int, float)) \
                or isinstance(value, bool):
            raise bad()
    if sp.validator is not None:
        out = sp.validator(out)
    return out


# -- config-file key registry ------------------------------------------------
# Every literal read off a parsed *.properties dict (NodeConfig,
# catalog/connector factories, plugin loader) must appear here — the
# static registry lint (tools/analyze/registries.py) cross-checks the
# ``props.get("...")`` call sites, so a typo'd key in code fails CI
# instead of silently reading the default forever. Globs cover
# namespaced families (``session.*`` defaults).

CONFIG_KEYS: Dict[str, str] = {
    "node.id": "stable node identity (defaults to worker-<port>)",
    "coordinator": "true/false — run the coordinator role",
    "http-server.http.port": "statement/worker HTTP port (0 = ephemeral)",
    "discovery.uri": "coordinator discovery endpoint workers announce to",
    "session.catalog": "default catalog for new sessions",
    "session.schema": "default schema for new sessions",
    "session.*": "session-property defaults (validated against "
                 "SESSION_PROPERTIES at boot)",
    "scan-cache.max-bytes": "process-wide device scan-cache resident "
                            "limit (deliberately not a session prop)",
    "result-cache.max-bytes": "process-wide result-cache host-row "
                              "budget (serving/resultcache.py; "
                              "deliberately not a session prop)",
    "spool.dir": "exchange spool directory (exec/spool.py); point "
                 "every node at shared storage for cross-node replay",
    "spool.max-bytes": "spool disk budget; appends past it fail the "
                       "writing task (default 4GiB)",
    "spool.backend": "which SpoolStore backend serves new queries: "
                     "local (append-only page logs, default) or "
                     "object (content-addressed emulated bucket — "
                     "exec/spool.py ObjectSpoolStore)",
    "spool.object.dir": "object-backend bucket directory; point every "
                        "node at common storage so shuffle state "
                        "survives the worker set scaling to zero",
    "spool.object.put-latency-ms": "modeled per-put object-store "
                                   "round-trip latency (emulates "
                                   "GCS/S3; default 0)",
    "spool.object.get-latency-ms": "modeled per-get object-store "
                                   "round-trip latency (default 0)",
    "spool.object.bandwidth-mbps": "modeled object-store transfer "
                                   "bandwidth in megabits/s "
                                   "(0 = latency-only model)",
    "autoscale.enabled": "run the elasticity control loop "
                         "(exec/autoscale.py) on this coordinator",
    "autoscale.min-workers": "autoscaler floor for the worker set "
                             "(default 1)",
    "autoscale.max-workers": "autoscaler ceiling for the worker set "
                             "(default 8)",
    "autoscale.scale-step": "max workers launched/drained per control "
                            "decision (bounded scale steps; default 1)",
    "autoscale.cooldown-s": "minimum seconds between applied scale "
                            "actions (default 30)",
    "autoscale.interval-s": "control-loop evaluation cadence in "
                            "seconds (default 5)",
    "failpoints": "deterministic fault-injection spec "
                  "(exec/failpoints.py grammar)",
    "timeseries.sample-interval-s": "health-plane sampler cadence in "
                                    "seconds (obs/timeseries.py; "
                                    "default 5)",
    "timeseries.retention-points": "bounded ring size per series "
                                   "(default 360 = 30 min at the "
                                   "default cadence)",
    # resource-groups.json group keys (server/resource_groups.py; not
    # *.properties keys, registered here so tools/analyze round-trips
    # the serving-plane configuration surface)
    "softMemoryLimit": "resource-groups.json: group device-memory bytes "
                       "beyond which new queries queue",
    "hardMemoryLimit": "resource-groups.json: group device-memory bytes "
                       "beyond which a growing query is killed",
    "queryQueuedTimeout": "resource-groups.json: admission deadline for "
                          "queries queued in the group (duration)",
    "slo": "resource-groups.json: per-group SLO block (obs/slo.py) — "
           "latencyTargetMs/latencyObjective/availabilityObjective/"
           "windows",
    "latencyTargetMs": "resource-groups.json slo block: latency "
                       "threshold in milliseconds (snaps up to the "
                       "histogram bucket ladder)",
    "latencyObjective": "resource-groups.json slo block: fraction of "
                        "queries that must finish under the threshold "
                        "(e.g. 0.95)",
    "availabilityObjective": "resource-groups.json slo block: fraction "
                             "of queries that must succeed "
                             "(e.g. 0.999)",
    "windows": "resource-groups.json slo block: burn-rate windows in "
               "seconds (default [300, 3600])",
    "connector.name": "catalog properties: which connector factory",
    "tpch.scale-factor": "tpch catalog scale factor",
    "tpcds.scale-factor": "tpcds catalog scale factor",
    "orc.root": "orc catalog data directory",
    "parquet.root": "parquet catalog data directory",
    "sqlite.path": "sqlite catalog database file",
    "path": "sqlite catalog database file (legacy alias)",
    "plugin.modules": "comma-separated plugin modules to import",
    "plugin.dir": "directory of plugin modules to load",
}

#: declared environment variables — the same two-way contract as the
#: other string-keyed registries (tools/analyze/registries.py): every
#: ``PRESTO_TPU_*`` / ``BENCH_*`` read in the tree must resolve to an
#: entry here, every entry must have a read site, and the table in
#: docs/static_analysis.md round-trips both ways. Foreign variables
#: (XLA_FLAGS, JAX_PLATFORMS) are deliberately NOT declared: they
#: belong to other projects' registries.
ENV_VARS: Dict[str, str] = {
    "PRESTO_TPU_LOCKCHECK": "force the runtime lock-order validator "
                            "on/off (default: on under pytest only)",
    "PRESTO_TPU_LOG": "structured JSON-lines log destination "
                      "(obs/log.py; empty = disabled)",
    "PRESTO_TPU_TRACE": "enable the span tracer outside explicit "
                        "--trace-out runs (obs/trace.py)",
    "PRESTO_TPU_MESH_EXECUTION": "environment default for the "
                                 "mesh_execution session property "
                                 "(auto/on/off; tests pin off)",
    "PRESTO_TPU_MESH_FLIGHT": "environment default for the "
                              "mesh_flight session property "
                              "(on/off; default on)",
    "PRESTO_TPU_FAILPOINTS": "failpoint arming spec applied at import "
                             "(exec/failpoints.py grammar)",
    "PRESTO_TPU_TIMESERIES": "set to 'off' to disable the background "
                             "health-plane sampler (obs/timeseries.py)",
    "SERVING_INLINE_LANE": "set to 0 to disable the statement POST "
                           "inline lane (proven-fast statements "
                           "executing in the handler thread); default "
                           "on",
}


def parse_properties(path: str) -> Dict[str, str]:
    """key=value lines; '#' comments; whitespace-tolerant (the reference
    uses java.util.Properties semantics)."""
    out: Dict[str, str] = {}
    with open(path) as f:
        for raw in f:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            if "=" not in line:
                raise ValueError(f"{path}: malformed line {line!r}")
            k, v = line.split("=", 1)
            out[k.strip()] = v.strip()
    return out


# -- connector factory registry (the Plugin/ConnectorFactory role) ----------

def _tpch_factory(props):
    from .connectors.tpch import TpchConnector
    return TpchConnector(sf=float(props.get("tpch.scale-factor", "1")))


def _tpcds_factory(props):
    from .connectors.tpcds import TpcdsConnector
    return TpcdsConnector(sf=float(props.get("tpcds.scale-factor", "1")))


def _memory_factory(props):
    from .connectors.memory import MemoryConnector
    return MemoryConnector()


def _orc_factory(props):
    from .connectors.orc import OrcConnector
    return OrcConnector(props["orc.root"])


def _parquet_factory(props):
    from .connectors.parquet import ParquetConnector
    return ParquetConnector(props["parquet.root"])


def _sqlite_factory(props):
    from .connectors.sqlite import connector_factory
    return connector_factory(props)


CONNECTOR_FACTORIES: Dict[str, Callable] = {
    "tpch": _tpch_factory,
    "tpcds": _tpcds_factory,
    "memory": _memory_factory,
    "orc": _orc_factory,
    "parquet": _parquet_factory,
    "sqlite": _sqlite_factory,
}


def register_connector_factory(name: str, factory: Callable) -> None:
    """Third-party connector registration (the Plugin.getConnectorFactories
    surface)."""
    CONNECTOR_FACTORIES[name] = factory


def load_catalogs(etc_dir: str,
                  catalogs: Optional[CatalogManager] = None
                  ) -> CatalogManager:
    """etc/catalog/*.properties -> mounted connectors (reference
    StaticCatalogStore.loadCatalogs)."""
    catalogs = catalogs or CatalogManager()
    cat_dir = os.path.join(etc_dir, "catalog")
    if not os.path.isdir(cat_dir):
        return catalogs
    for entry in sorted(os.listdir(cat_dir)):
        if not entry.endswith(".properties"):
            continue
        props = parse_properties(os.path.join(cat_dir, entry))
        name = entry[:-len(".properties")]
        kind = props.get("connector.name")
        if kind is None:
            raise ValueError(f"{entry}: missing connector.name")
        factory = CONNECTOR_FACTORIES.get(kind)
        if factory is None:
            raise ValueError(
                f"{entry}: unknown connector.name {kind!r} "
                f"(registered: {sorted(CONNECTOR_FACTORIES)})")
        catalogs.register(name, factory(props))
    # the system catalog reflects over everything mounted so far
    from .connectors.system import SystemConnector
    if "system" not in catalogs.names():
        catalogs.register("system", SystemConnector(catalogs))
    return catalogs


class NodeConfig:
    """Parsed etc/config.properties (reference ServerConfig +
    NodeConfig + the session-default slice of SystemSessionProperties)."""

    def __init__(self, props: Dict[str, str]):
        self.props = props
        self.node_id: Optional[str] = props.get("node.id")
        self.coordinator = props.get("coordinator", "true") \
            .lower() == "true"
        self.http_port = int(props.get("http-server.http.port", "0"))
        self.discovery_uri = props.get("discovery.uri")
        self.catalog = props.get("session.catalog", "tpch")
        self.schema = props.get("session.schema", "default")
        #: process-wide device scan-cache resident limit
        #: (exec/scancache.py); None keeps the built-in default
        raw_sc = props.get("scan-cache.max-bytes")
        self.scan_cache_bytes = int(raw_sc) if raw_sc else None
        #: process-wide result-cache host-row budget
        #: (serving/resultcache.py); None keeps the built-in default
        raw_rc = props.get("result-cache.max-bytes")
        self.result_cache_bytes = int(raw_rc) if raw_rc else None
        #: exchange-spool backend config (exec/spool.py SPOOL)
        self.spool_dir = props.get("spool.dir")
        raw_sp = props.get("spool.max-bytes")
        self.spool_max_bytes = int(raw_sp) if raw_sp else None
        #: which SpoolStore backend serves new queries (local/object)
        #: plus the object backend's bucket + latency/bandwidth model
        self.spool_backend = props.get("spool.backend")
        self.spool_object_dir = props.get("spool.object.dir")
        raw_pl = props.get("spool.object.put-latency-ms")
        self.spool_object_put_latency_s = \
            float(raw_pl) / 1e3 if raw_pl else None
        raw_gl = props.get("spool.object.get-latency-ms")
        self.spool_object_get_latency_s = \
            float(raw_gl) / 1e3 if raw_gl else None
        raw_bw = props.get("spool.object.bandwidth-mbps")
        self.spool_object_bandwidth_mbps = \
            float(raw_bw) if raw_bw else None
        #: elasticity control loop (exec/autoscale.py)
        self.autoscale_enabled = props.get(
            "autoscale.enabled", "false").lower() == "true"
        raw_min = props.get("autoscale.min-workers")
        self.autoscale_min_workers = int(raw_min) if raw_min else 1
        raw_max = props.get("autoscale.max-workers")
        self.autoscale_max_workers = int(raw_max) if raw_max else 8
        raw_step = props.get("autoscale.scale-step")
        self.autoscale_scale_step = int(raw_step) if raw_step else 1
        raw_cd = props.get("autoscale.cooldown-s")
        self.autoscale_cooldown_s = float(raw_cd) if raw_cd else 30.0
        raw_iv = props.get("autoscale.interval-s")
        self.autoscale_interval_s = float(raw_iv) if raw_iv else 5.0
        #: deterministic fault-injection spec (exec/failpoints.py
        #: grammar, ';'-separated) — chaos/soak runs arm failpoints
        #: straight from config.properties, same as the
        #: PRESTO_TPU_FAILPOINTS env var
        self.failpoints = props.get("failpoints")
        #: health-plane sampler cadence / per-series ring size
        #: (obs/timeseries.py); None keeps the built-in defaults
        raw_ts = props.get("timeseries.sample-interval-s")
        self.timeseries_interval_s = float(raw_ts) if raw_ts else None
        raw_tr = props.get("timeseries.retention-points")
        self.timeseries_retention = int(raw_tr) if raw_tr else None
        #: session property defaults: session.<name>=<value>
        self.session_defaults = {
            k[len("session."):]: v for k, v in props.items()
            if k.startswith("session.")
            and k not in ("session.catalog", "session.schema")}


def load_node_config(etc_dir: str) -> NodeConfig:
    path = os.path.join(etc_dir, "config.properties")
    return NodeConfig(parse_properties(path) if os.path.isfile(path)
                      else {})


def load_resource_groups(etc_dir: str):
    """etc/resource-groups.json -> ResourceGroupManager config dict
    (the file-backed half of reference
    presto-resource-group-managers/.../FileResourceGroupConfigurationManager
    .java; selectors/limits keep this engine's JSON shape)."""
    import json as _json
    path = os.path.join(etc_dir, "resource-groups.json")
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return _json.load(f)


def configure_spool(cfg: NodeConfig,
                    directory: Optional[str] = None) -> None:
    """Apply a NodeConfig's ``spool.*`` block to the process-wide
    store (both the coordinator and worker boot paths route here)."""
    if not (directory or cfg.spool_dir or cfg.spool_max_bytes is not None
            or cfg.spool_backend or cfg.spool_object_dir
            or cfg.spool_object_put_latency_s is not None
            or cfg.spool_object_get_latency_s is not None
            or cfg.spool_object_bandwidth_mbps is not None):
        return
    from .exec.spool import SPOOL
    SPOOL.configure(
        directory=directory or cfg.spool_dir,
        max_bytes=cfg.spool_max_bytes,
        backend=cfg.spool_backend,
        object_dir=cfg.spool_object_dir,
        object_put_latency_s=cfg.spool_object_put_latency_s,
        object_get_latency_s=cfg.spool_object_get_latency_s,
        object_bandwidth_mbps=cfg.spool_object_bandwidth_mbps)


def server_from_etc(etc_dir: str, host: str = "127.0.0.1",
                    port: Optional[int] = None):
    """Boot a statement server from a config directory — the
    PrestoServer.run analogue (reference server/PrestoServer.java:86:
    config binding, catalog store, resource groups, announce)."""
    from .exec.runner import LocalRunner
    from .server.protocol import PrestoTpuServer
    cfg = load_node_config(etc_dir)
    # plugins install connector factories / functions BEFORE catalogs
    # mount (reference PrestoServer.run: loadPlugins then catalog store)
    from .plugin import load_plugins_from_config
    load_plugins_from_config(cfg.props)
    catalogs = load_catalogs(etc_dir)
    if cfg.scan_cache_bytes is not None:
        from .exec.scancache import CACHE
        CACHE.set_limit(cfg.scan_cache_bytes)
    if cfg.result_cache_bytes is not None:
        from .serving.resultcache import RESULTS
        RESULTS.set_limit(cfg.result_cache_bytes)
    configure_spool(cfg)
    if cfg.failpoints:
        from .exec.failpoints import FAILPOINTS
        FAILPOINTS.configure_from_spec(cfg.failpoints)
    if cfg.timeseries_interval_s is not None \
            or cfg.timeseries_retention is not None:
        from .obs.timeseries import TIMESERIES
        TIMESERIES.configure(
            sample_interval_s=cfg.timeseries_interval_s,
            retention_points=cfg.timeseries_retention)
    runner = LocalRunner(catalogs=catalogs, catalog=cfg.catalog,
                         schema=cfg.schema)
    # session.<name> defaults go through the same registry gate as SET
    # SESSION: a typo'd default fails the boot, not a dashboard
    runner.session.properties.update(
        {k: validate_session_property(k, v)
         for k, v in cfg.session_defaults.items()})
    srv = PrestoTpuServer(
        runner=runner, host=host,
        port=cfg.http_port if port is None else port,
        resource_groups=load_resource_groups(etc_dir))
    if cfg.autoscale_enabled:
        # close the elasticity loop: signals feed -> rules -> local
        # subprocess workers announcing back to this coordinator. The
        # controller starts with the server (PrestoTpuServer.start is
        # not hooked — the loop thread is harmless pre-start) and
        # stops with it (protocol.stop()).
        from .exec.autoscale import (AutoscaleController,
                                     AutoscalePolicy,
                                     LocalProcessProvider)
        policy = AutoscalePolicy(
            min_workers=cfg.autoscale_min_workers,
            max_workers=cfg.autoscale_max_workers,
            scale_step=cfg.autoscale_scale_step,
            cooldown_s=cfg.autoscale_cooldown_s,
            interval_s=cfg.autoscale_interval_s)
        provider = LocalProcessProvider(
            [f"http://{host}:{srv.port}"],
            spool_dir=cfg.spool_dir, etc_dir=etc_dir)
        srv.autoscaler = AutoscaleController(provider, policy=policy)
        srv.autoscaler.start()
    return srv, cfg
