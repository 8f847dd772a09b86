#!/usr/bin/env python
"""Chaos smoke: an elastic in-process cluster under seeded failpoints.

Drives every recovery path of the fault-tolerance + spooled-exchange
layers (presto_tpu/exec/cluster.py, exec/spool.py, exec/failpoints.py)
without a real multi-host TPU cluster, and asserts ROW-EXACT parity
with the fault-free run after each injected fault:

- ``task_failure``   — one task FAILs at start (``worker.task_run``
  error); the coordinator re-creates it on a healthy worker.
- ``exchange_drop``  — one exchange pull dies mid-stream
  (``exchange.pull`` error); the ExchangeFailedError names the upstream
  attempt and the retry layer replaces exactly that producer.
- ``straggler``      — one source task sleeps 15s (``worker.task_run``
  sleep); the StageMonitor flags it, a speculative duplicate launches
  on another node and wins, the loser is aborted.
- ``retry_none``     — same task fault under ``retry_policy=NONE``
  fails fast (the pre-fault-tolerance behavior, still available).
- ``worker_death``   — a failpoint callback kills one worker's HTTP
  server mid-query; its tasks (same deterministic splits) reschedule
  onto the survivors.
- ``spool_replay``   — a worker is killed AFTER its source task
  committed its spool, mid-shuffle: consumers replay the pages from
  the durable spool and the source task is NOT re-executed (asserted
  via the task-attempt/retry events — the spooled-exchange headline).
- ``spool_corrupt``  — one spooled page is corrupted on disk
  (``spool.corrupt``) and its worker killed: the checksum catches it,
  the consumer's failure names the upstream, and the retry layer
  re-runs exactly that producer; results stay row-exact.
- ``worker_join``    — a FRESH worker boots and announces mid-query
  while another dies: the re-created tasks land on the late joiner
  (elastic scale-out under the discovery + recovery machinery).
- ``drain_exit``     — a worker is put into SHUTTING_DOWN mid-query
  while the root is still reading its output: it exits within its
  drain grace (no lingering until downstream completion) and the
  consumer finishes from the spool, with zero task retries.

Recovery is asserted observable: ``task_retry_total``,
``speculative_won_total``, ``spool_replayed_task_total``,
``exchange_spool_fallback_total`` and ``node_joined_total`` move, via
``system.runtime.metrics`` over plain SQL; at the end the spool
directory must hold ZERO orphaned per-query directories.

Run directly (prints a JSON summary) or from the tier-1 suite
(tests/test_chaos.py):

    JAX_PLATFORMS=cpu python tools/chaos_smoke.py [--sf 0.01]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

QUERY = ("select l_returnflag, l_linestatus, count(*) c, "
         "sum(l_quantity) q, sum(l_extendedprice) e from lineitem "
         "where l_shipdate <= date '1998-09-02' "
         "group by 1, 2 order by 1, 2")


#: seconds the fleet drill's clients get for their statements, all told
CLIENTS_JOIN_S = 120.0


def _metric_sql(runner, name: str) -> float:
    res = runner.local.execute(
        "select value from system.runtime.metrics "
        f"where name = '{name}'")
    return float(res.rows[0][0]) if res.rows else 0.0


def _assert_rows_equal(got, want, scenario: str) -> None:
    assert len(got) == len(want), \
        f"{scenario}: {len(got)} rows vs {len(want)}"
    for gr, wr in zip(got, want):
        for gv, wv in zip(gr, wr):
            if isinstance(wv, float):
                # partial-agg pages merge in arrival order; float sums
                # are reproducible only to rounding, like test_cluster
                assert abs(gv - wv) <= max(abs(wv), 1.0) * 1e-6, \
                    (scenario, gr, wr)
            else:
                assert gv == wv, (scenario, gr, wr)


def run_chaos(sf: float = 0.01, query: str = QUERY,
              verbose: bool = False) -> dict:
    from presto_tpu.exec.cluster import ClusterRunner, QueryFailedError
    from presto_tpu.exec.discovery import DiscoveryNodeManager
    from presto_tpu.exec.failpoints import FAILPOINTS
    from presto_tpu.exec.spool import SPOOL
    from presto_tpu.server.worker import WorkerServer

    def log(msg: str) -> None:
        if verbose:
            print(msg, file=sys.stderr, flush=True)

    # discovery-fed membership (not a static URL list): workers may
    # join or leave mid-query — the elastic half of the smoke
    discovery = DiscoveryNodeManager(ttl_s=3600.0)
    workers = []

    def add_worker() -> WorkerServer:
        w = WorkerServer(tpch_sf=sf, drain_grace_s=2.0)
        w.start()
        workers.append(w)
        discovery.announce(w.node_id, f"http://127.0.0.1:{w.port}")
        return w

    def kill_worker(w: WorkerServer) -> None:
        """In-process stand-in for a worker process death: the network
        surface goes away AND its task threads stop burning the shared
        device scheduler."""
        w.httpd.shutdown()
        w.httpd.server_close()
        for t in list(w.tasks.values()):
            t.abort()

    for _ in range(3):
        add_worker()
    runner = ClusterRunner(tpch_sf=sf, heartbeat=False,
                           discovery=discovery)
    summary: dict = {"sf": sf, "scenarios": {}}
    FAILPOINTS.clear()
    try:
        # fault-free reference (first run also warms the jit caches so
        # fault-run timings measure recovery, not compilation)
        t0 = time.perf_counter()
        want = runner.execute(query).rows
        runner.execute(query)
        summary["baseline_s"] = round(time.perf_counter() - t0, 3)
        log(f"baseline: {len(want)} rows in {summary['baseline_s']}s")

        def scenario(name: str):
            t = time.perf_counter()

            def finish(**extra):
                FAILPOINTS.clear()
                summary["scenarios"][name] = {
                    "elapsed_s": round(time.perf_counter() - t, 3),
                    **extra}
                log(f"{name}: ok {summary['scenarios'][name]}")
            return finish

        # -- (a) one task failure -> task-level retry ---------------------
        finish = scenario("task_failure")
        before = _metric_sql(runner, "task_retry_total")
        FAILPOINTS.configure("worker.task_run", action="error",
                             message="chaos: task failure", times=1)
        _assert_rows_equal(runner.execute(query).rows, want,
                           "task_failure")
        retries = _metric_sql(runner, "task_retry_total") - before
        assert retries >= 1, "task failure did not trigger a retry"
        finish(task_retries=retries)

        # -- (b) exchange drop mid-stream -> upstream replaced ------------
        finish = scenario("exchange_drop")
        before = _metric_sql(runner, "task_retry_total")
        FAILPOINTS.configure("exchange.pull", action="error",
                             message="chaos: exchange drop", times=1)
        _assert_rows_equal(runner.execute(query).rows, want,
                           "exchange_drop")
        retries = _metric_sql(runner, "task_retry_total") - before
        assert retries >= 1, "exchange drop did not trigger a retry"
        finish(task_retries=retries)

        # -- (c) 10x straggler -> speculative attempt wins ----------------
        finish = scenario("straggler")
        before = _metric_sql(runner, "speculative_won_total")
        # partition 0 of the source stage sleeps far past the stage
        # median; attempt suffixes keep the duplicate out of the rule.
        # The sleep must also outlast a COLD duplicate: on a loaded
        # 1-core host the speculative attempt may land on a worker
        # that never compiled this fragment (~9s JIT) — 15s let the
        # original occasionally wake first and steal the win
        FAILPOINTS.configure("worker.task_run", action="sleep",
                             sleep_s=30.0, match=r"\.0\.0@", times=1)
        # ... and the SIBLING source tasks must clear the monitor's
        # straggler median floor (min_elapsed_ms): with the scan cache
        # primed by the earlier scenarios they finish in a few ms, the
        # stage median lands under the floor, and the straggler is
        # never flagged — the exact warm-cluster shape that made this
        # scenario order-dependent inside the full test suite
        FAILPOINTS.configure("worker.task_run", action="sleep",
                             sleep_s=0.1, match=r"\.0\.[1-9]\d*@",
                             times=None)
        _assert_rows_equal(runner.execute(query).rows, want,
                           "straggler")
        FAILPOINTS.clear()      # the sibling pad rule is unbounded
        won = _metric_sql(runner, "speculative_won_total") - before
        assert won >= 1, "straggler did not produce a speculative win"
        finish(speculative_won=won)

        # -- (d) retry_policy=NONE fails fast -----------------------------
        finish = scenario("retry_none")
        FAILPOINTS.configure("worker.task_run", action="error",
                             message="chaos: fail fast", times=1)
        runner.session.properties["retry_policy"] = "NONE"
        try:
            failed = False
            try:
                runner.execute(query)
            except QueryFailedError as e:
                failed = True
                assert "chaos: fail fast" in str(e), str(e)
            assert failed, "retry_policy=NONE still recovered"
        finally:
            del runner.session.properties["retry_policy"]
        finish()

        # -- (e) worker death mid-query -> reschedule on survivors --------
        finish = scenario("worker_death")
        before = _metric_sql(runner, "task_retry_total")
        victim = workers[-1]

        def kill(key="", **ctx):
            kill_worker(victim)

        FAILPOINTS.configure("worker.task_run", action="callback",
                             callback=kill, times=1,
                             match=f"@{victim.node_id}$")
        _assert_rows_equal(runner.execute(query).rows, want,
                           "worker_death")
        retries = _metric_sql(runner, "task_retry_total") - before
        assert retries >= 1, "worker death did not trigger a retry"
        # the dead node must be out of the schedulable set now
        assert f"http://127.0.0.1:{victim.port}" \
            not in runner._schedulable_workers()
        finish(task_retries=retries)
        add_worker()               # replenish the pool to 3 live nodes

        # fragment ids of the smoke query (the scenarios below target
        # the source stage's tasks / the stage the root consumes)
        from presto_tpu.planner.fragmenter import fragment_plan
        from presto_tpu.planner.plan import RemoteSourceNode
        fp = fragment_plan(runner.local.plan(query).root)
        source_fid = next(f.id for f in fp.fragments
                          if f.partitioning == "source")

        def _nodes(n):
            yield n
            for c in n.children:
                yield from _nodes(c)
        feed_fid = next(fid for node in _nodes(fp.root.root)
                        if isinstance(node, RemoteSourceNode)
                        for fid in node.fragment_ids)

        def live_workers():
            return [w for w in workers if w.httpd.socket.fileno() != -1
                    and not w.shutting_down]

        def pick_victim():
            # the single (root) fragment lands on the first worker of
            # the schedulable sweep (sorted by URL): the max-URL live
            # worker can never host the root, which keeps the
            # drain/kill scenarios' retry accounting deterministic
            return max(live_workers(),
                       key=lambda w: f"http://127.0.0.1:{w.port}")

        def wait_stage_finished(w: WorkerServer, fid: int,
                                timeout_s: float = 30.0) -> None:
            deadline = time.time() + timeout_s
            while time.time() < deadline:
                ts = [t for t in list(w.tasks.values())
                      if t.task_id.split(".")[1] == str(fid)]
                if ts and all(t.state == "FINISHED" for t in ts):
                    return
                time.sleep(0.05)
            raise AssertionError(
                f"stage {fid} on {w.node_id} never finished")

        # -- (f) spool replay: kill a worker AFTER its source task ------
        # committed its spool, mid-shuffle. Consumers replay the pages
        # from the durable spool; the source task is NOT re-executed.
        finish = scenario("spool_replay")
        before = _metric_sql(runner, "task_retry_total")
        before_replay = _metric_sql(runner, "spool_replayed_task_total")
        before_fb = _metric_sql(runner,
                                "exchange_spool_fallback_total")
        victim2 = pick_victim()
        killed = threading.Event()
        kill_lock = threading.Lock()

        def kill_after_spool(key="", **ctx):
            # EVERY pull of the victim's source task funnels through
            # here (times unlimited): no page is ever served live, so
            # every consumer must replay from the spool — and the kill
            # only lands once the spool is committed
            with kill_lock:
                if not killed.is_set():
                    wait_stage_finished(victim2, source_fid)
                    kill_worker(victim2)
                    killed.set()

        FAILPOINTS.configure(
            "exchange.pull", action="callback",
            callback=kill_after_spool, times=None,
            match=rf":{victim2.port}/v1/task/[^/]*\.{source_fid}\.\d+$")
        _assert_rows_equal(runner.execute(query).rows, want,
                           "spool_replay")
        FAILPOINTS.clear()
        replays = _metric_sql(
            runner, "spool_replayed_task_total") - before_replay
        fallbacks = _metric_sql(
            runner, "exchange_spool_fallback_total") - before_fb
        retries = _metric_sql(runner, "task_retry_total") - before
        assert replays >= 1, \
            "lost-but-spooled task was not preserved"
        assert fallbacks >= 1, \
            "no consumer replayed from the spool"
        # the headline assertion: NO source-stage task was re-executed
        # (retries are the victim's other tasks — never the producer
        # whose output lives in the spool)
        events = runner._last_run_info.get("events") or []
        source_retries = [
            ev for ev in events if ev.get("kind") == "task_retry"
            and str(ev.get("task", "")).split(".")[1]
            == str(source_fid)]
        assert not source_retries, \
            f"spooled source task was re-executed: {source_retries}"
        finish(spool_replays=replays, spool_fallbacks=fallbacks,
               task_retries=retries)
        add_worker()

        # -- (g) spool corruption: checksum -> retry from upstream ------
        finish = scenario("spool_corrupt")
        before = _metric_sql(runner, "task_retry_total")
        before_cor = _metric_sql(runner, "spool_corruption_total")
        victim3 = pick_victim()
        killed3 = threading.Event()
        kill3_lock = threading.Lock()
        corrupt_armed = threading.Event()

        def arm_corrupt(key="", task_id="", **ctx):
            # corrupt the first spooled page of a source task ON THE
            # VICTIM (the task id is only known once the worker starts
            # it): the frame keeps the original checksum, the payload
            # flips one byte on disk. Arming by exact task id matters:
            # a survivor's corrupted page would be served from the
            # clean in-memory fast path and never detected.
            import re as _re
            if task_id.split(".")[1] == str(source_fid) \
                    and not corrupt_armed.is_set():
                corrupt_armed.set()
                FAILPOINTS.configure(
                    "spool.corrupt", action="error", times=1,
                    match=rf"^{_re.escape(task_id)}/")

        FAILPOINTS.configure("worker.task_run", action="callback",
                             callback=arm_corrupt, times=None,
                             match=f"@{victim3.node_id}$")

        def kill_after_corrupt(key="", **ctx):
            with kill3_lock:
                if not killed3.is_set():
                    wait_stage_finished(victim3, source_fid)
                    kill_worker(victim3)
                    killed3.set()

        FAILPOINTS.configure(
            "exchange.pull", action="callback",
            callback=kill_after_corrupt, times=None,
            match=rf":{victim3.port}/v1/task/[^/]*\.{source_fid}\.\d+$")
        _assert_rows_equal(runner.execute(query).rows, want,
                           "spool_corrupt")
        FAILPOINTS.clear()
        corruptions = _metric_sql(
            runner, "spool_corruption_total") - before_cor
        retries = _metric_sql(runner, "task_retry_total") - before
        assert corrupt_armed.is_set(), \
            "victim never ran a source task to corrupt"
        assert corruptions >= 1, \
            "corrupted spool page was served without detection"
        assert retries >= 1, \
            "spool corruption did not re-run the producer"
        finish(corruptions=corruptions, task_retries=retries)
        add_worker()

        # -- (h) elastic join: a fresh worker boots + announces -------
        # mid-query while another dies; the re-created tasks land on
        # the late joiner
        finish = scenario("worker_join")
        before = _metric_sql(runner, "task_retry_total")
        before_join = _metric_sql(runner, "node_joined_total")
        victim4 = pick_victim()
        joiner: dict = {}

        def kill_and_join(key="", **ctx):
            kill_worker(victim4)
            joiner["w"] = add_worker()

        FAILPOINTS.configure("worker.task_run", action="callback",
                             callback=kill_and_join, times=1,
                             match=f"@{victim4.node_id}$")
        _assert_rows_equal(runner.execute(query).rows, want,
                           "worker_join")
        FAILPOINTS.clear()
        retries = _metric_sql(runner, "task_retry_total") - before
        joined = _metric_sql(runner, "node_joined_total") - before_join
        assert retries >= 1, "worker death did not trigger a retry"
        assert joined >= 1, "the late joiner was never federated"
        joiner_url = f"http://127.0.0.1:{joiner['w'].port}"
        events = runner._last_run_info.get("events") or []
        landed = [ev for ev in events
                  if ev.get("kind") == "task_retry"
                  and ev.get("to") == joiner_url]
        assert landed, \
            f"no re-created task landed on the late joiner: {events}"
        finish(task_retries=retries, joined=joined,
               landed_on_joiner=len(landed))

        # -- (i) drain-and-exit: SHUTTING_DOWN mid-read ----------------
        # the worker exits within its drain grace while the root is
        # still consuming its output; the root finishes from the spool
        # with ZERO task retries
        finish = scenario("drain_exit")
        before = _metric_sql(runner, "task_retry_total")
        before_fb = _metric_sql(runner,
                                "exchange_spool_fallback_total")
        victim5 = pick_victim()
        drained = threading.Event()
        drain_lock = threading.Lock()

        def drain_after_finish(key="", **ctx):
            with drain_lock:
                if not drained.is_set():
                    wait_stage_finished(victim5, feed_fid)
                    victim5.begin_shutdown()
                    drained.set()

        # the root's pulls of the victim's feed-stage task trigger the
        # drain (once that task finished), then slow to one page per
        # second — guaranteeing the worker is GONE before the root
        # drains the buffer, so the tail must come from the spool
        FAILPOINTS.configure(
            "exchange.pull", action="callback",
            callback=drain_after_finish, times=None,
            match=rf":{victim5.port}/v1/task/[^/]*\.{feed_fid}\.\d+$")
        FAILPOINTS.configure(
            "exchange.pull", action="sleep", sleep_s=1.0, times=None,
            match=rf":{victim5.port}/v1/task/[^/]*\.{feed_fid}\.\d+$")
        _assert_rows_equal(runner.execute(query).rows, want,
                           "drain_exit")
        FAILPOINTS.clear()
        retries = _metric_sql(runner, "task_retry_total") - before
        fallbacks = _metric_sql(
            runner, "exchange_spool_fallback_total") - before_fb
        assert retries == 0, \
            f"drain caused {retries} retries (spool should replay)"
        assert fallbacks >= 1, \
            "root never replayed the drained worker's output"
        # the drained worker's process actually EXITED within its
        # grace (no lingering until downstream completion): its socket
        # must refuse within a short post-query window
        exit_deadline = time.time() + 5.0
        gone = False
        while time.time() < exit_deadline:
            try:
                import urllib.request
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{victim5.port}/v1/info",
                        timeout=1):
                    pass
            except Exception:
                gone = True
                break
            time.sleep(0.1)
        assert gone, "drained worker lingered past its grace"
        assert f"http://127.0.0.1:{victim5.port}" \
            not in runner._schedulable_workers()
        finish(task_retries=retries, spool_fallbacks=fallbacks)

        # -- (j) preemption storm: workers are preemptible BY DESIGN ---
        # Poisson-cadence preemptions (seeded — replayable) under
        # sustained query load: every preemption is a drain notice
        # (begin_shutdown → active tasks commit their spool → process
        # exit), a replacement joins, and ZERO queries fail. The first
        # preemption is deterministic (the drain_exit recipe) so at
        # least one coordinator-side spool replay is guaranteed
        # regardless of storm timing.
        import random as _random
        finish = scenario("preemption_storm")
        # drain_exit left the pool at two live workers; the storm
        # needs three so its >=2-live preemption guard has headroom
        # after the deterministic first drain
        while len(live_workers()) < 3:
            add_worker()
        before_replay = _metric_sql(runner, "spool_replayed_task_total")
        before_fb = _metric_sql(runner,
                                "exchange_spool_fallback_total")
        victim6 = pick_victim()
        preempted = threading.Event()
        pre_lock = threading.Lock()

        def preempt_after_finish(key="", **ctx):
            with pre_lock:
                if not preempted.is_set():
                    wait_stage_finished(victim6, feed_fid)
                    victim6.begin_shutdown()
                    preempted.set()

        FAILPOINTS.configure(
            "exchange.pull", action="callback",
            callback=preempt_after_finish, times=None,
            match=rf":{victim6.port}/v1/task/[^/]*\.{feed_fid}\.\d+$")
        FAILPOINTS.configure(
            "exchange.pull", action="sleep", sleep_s=1.0, times=None,
            match=rf":{victim6.port}/v1/task/[^/]*\.{feed_fid}\.\d+$")
        _assert_rows_equal(runner.execute(query).rows, want,
                           "preemption_storm")
        FAILPOINTS.clear()
        preemptions = [1]
        storm_stop = threading.Event()
        rng = _random.Random(0xE1A57)

        def storm() -> None:
            # expovariate inter-arrivals = Poisson preemption process;
            # never preempt below two live workers (a real preemptible
            # pool has a floor too — the autoscaler's min_workers)
            while not storm_stop.wait(rng.expovariate(1 / 0.5)):
                lw = live_workers()
                if len(lw) < 2:
                    continue
                v = max(lw, key=lambda w: f"http://127.0.0.1:{w.port}")
                v.begin_shutdown()
                preemptions[0] += 1
                add_worker()

        st = threading.Thread(target=storm, daemon=True)
        st.start()
        storm_queries = 1
        storm_deadline = time.time() + 60.0
        try:
            # preemption-bounded, not query-bounded: on a fully warm
            # cluster a fixed query budget can drain before 3 Poisson
            # arrivals land — keep the load going until the storm has
            # actually stormed (the wall-clock cap guards a wedged
            # storm thread, ~1.5s expected at the 0.5s mean cadence)
            while (storm_queries < 5 or preemptions[0] < 3) \
                    and time.time() < storm_deadline:
                _assert_rows_equal(runner.execute(query).rows, want,
                                   "preemption_storm")
                storm_queries += 1
        finally:
            storm_stop.set()
            st.join(timeout=5)
        while len(live_workers()) < 3:
            add_worker()
        replays = _metric_sql(
            runner, "spool_replayed_task_total") - before_replay
        fallbacks = _metric_sql(
            runner, "exchange_spool_fallback_total") - before_fb
        assert preemptions[0] >= 3, \
            f"storm landed only {preemptions[0]} preemptions"
        assert replays >= 1, \
            "no preempted worker's output was replayed from the spool"
        finish(queries=storm_queries, preemptions=preemptions[0],
               spool_replays=replays, spool_fallbacks=fallbacks)

        # -- (k) scale to zero: the worker set vanishes ENTIRELY -------
        # mid-shuffle with the spool on the OBJECT-STORE backend
        # (latency-modeled GCS/S3 stand-in): every worker is killed
        # after the source stage committed, two FRESH workers join,
        # and the query completes row-exact — shuffle state outlived
        # the entire worker set because it lives in the object store,
        # not on any worker's disk
        import atexit as _atexit
        import shutil as _shutil
        import tempfile as _tempfile
        finish = scenario("scale_to_zero")
        obj_dir = _tempfile.mkdtemp(prefix="chaos-objspool-")
        # the object store makes its directory anew whenever it is
        # touched, and every later query's release touches it: what the
        # ``finally`` below empties is removed for good at exit
        _atexit.register(_shutil.rmtree, obj_dir, ignore_errors=True)
        SPOOL.configure(backend="object", object_dir=obj_dir,
                        object_put_latency_s=0.002,
                        object_get_latency_s=0.002)
        try:
            before = _metric_sql(runner, "task_retry_total")
            before_replay = _metric_sql(runner,
                                        "spool_replayed_task_total")
            before_put = _metric_sql(runner, "spool_object_put_total")
            before_get = _metric_sql(runner, "spool_object_get_total")
            wiped = threading.Event()
            wipe_lock = threading.Lock()

            def wipe(key="", **ctx):
                with wipe_lock:
                    if wiped.is_set():
                        return
                    lw = live_workers()
                    deadline = time.time() + 30.0
                    while time.time() < deadline:
                        src = [t for w in lw
                               for t in list(w.tasks.values())
                               if t.task_id.split(".")[1]
                               == str(source_fid)]
                        if src and all(t.state == "FINISHED"
                                       for t in src):
                            break
                        time.sleep(0.05)
                    else:
                        raise AssertionError(
                            "source stage never committed before "
                            "the wipe")
                    for w in lw:
                        kill_worker(w)
                    add_worker()
                    add_worker()
                    wiped.set()

            FAILPOINTS.configure(
                "exchange.pull", action="callback", callback=wipe,
                times=None,
                match=rf"/v1/task/[^/]*\.{source_fid}\.\d+$")
            _assert_rows_equal(runner.execute(query).rows, want,
                               "scale_to_zero")
            FAILPOINTS.clear()
            assert wiped.is_set(), \
                "the wipe callback never fired"
            replays = _metric_sql(
                runner, "spool_replayed_task_total") - before_replay
            retries = _metric_sql(runner, "task_retry_total") - before
            puts = _metric_sql(
                runner, "spool_object_put_total") - before_put
            gets = _metric_sql(
                runner, "spool_object_get_total") - before_get
            assert replays >= 1, \
                "no source task was preserved across the wipe"
            assert retries >= 1, \
                "no downstream task was re-created on fresh workers"
            assert puts >= 1 and gets >= 1, \
                f"object-store spool never moved (puts={puts}, " \
                f"gets={gets})"
            # per-query GC held across the wipe: zero orphaned objects
            obj_orphans = SPOOL.object_store.query_dirs()
            assert not obj_orphans, \
                f"orphaned object-spool queries: {obj_orphans}"
            finish(spool_replays=replays, task_retries=retries,
                   object_puts=puts, object_gets=gets)
        finally:
            FAILPOINTS.clear()
            SPOOL.configure(backend="local")
            _shutil.rmtree(obj_dir, ignore_errors=True)
        while len(live_workers()) < 3:
            add_worker()

        # the retry count is part of the query history record
        res = runner.local.execute(
            "select retries from system.runtime.completed_queries "
            "where mode = 'cluster' order by create_time")
        assert res.rows and any(int(r[0]) >= 1 for r in res.rows), \
            "no completed_queries record carries a retry count"

        # spool GC: after every scenario (successes, kills, drains and
        # fail-fast aborts alike) no per-query spool directory may
        # survive — disk is accounted and returned
        orphans = SPOOL.query_dirs()
        assert not orphans, f"orphaned spool directories: {orphans}"

        # -- (f) typo'd spec rejected at parse time -----------------------
        # a chaos config naming an unregistered site would inject
        # nothing and "pass" every scenario above — the registry must
        # refuse to arm it (exec/failpoints.py SITES validation)
        finish = scenario("failpoint_validation")
        rejected = False
        try:
            FAILPOINTS.configure_from_spec("worker.task_ruin=error")
        except ValueError as e:
            rejected = "unknown failpoint site" in str(e)
        assert rejected, "typo'd failpoint spec was silently accepted"
        finish(rejected=True)

        # per-scenario recovery times of the elastic scenarios
        elastic_scenarios = ("worker_death", "spool_replay",
                             "spool_corrupt", "worker_join",
                             "drain_exit", "preemption_storm",
                             "scale_to_zero")
        summary["elastic"] = {
            "metric": "elastic_recovery_ms",
            "value": round(sum(
                summary["scenarios"][s]["elapsed_s"]
                for s in elastic_scenarios) * 1e3, 1),
            "sub_metrics": [
                {"metric": f"{s}_ms",
                 "value": round(
                     summary["scenarios"][s]["elapsed_s"] * 1e3, 1)}
                for s in elastic_scenarios],
        }
        summary["ok"] = True
        return summary
    finally:
        FAILPOINTS.clear()
        for w in workers:
            try:
                w.stop()
            except Exception:
                pass


def run_fleet_chaos(sf: float = 0.01, coordinators: int = 3,
                    clients: int = 2, per_client: int = 3,
                    verbose: bool = False) -> dict:
    """Coordinator-death drill (ISSUE 19): an in-process fleet of
    ``coordinators`` statement servers over ONE shared worker pool,
    killed down to survivors mid-run.

    Asserts the fleet contract end to end: ZERO failed queries (the
    FleetClient re-dispatches around the corpse), the survivors drop
    the dead coordinator's federated resource-group counts once its
    heartbeats age past the staleness grace, and the loss is
    observable — ``coordinator_lost_total`` read back over plain SQL
    from a survivor."""
    from presto_tpu.client import FleetClient
    from presto_tpu.exec.cluster import ClusterRunner
    from presto_tpu.exec.discovery import DiscoveryNodeManager
    from presto_tpu.exec.failpoints import FAILPOINTS
    from presto_tpu.server.protocol import PrestoTpuServer
    from presto_tpu.server.worker import WorkerServer

    def log(msg: str) -> None:
        if verbose:
            print(msg, file=sys.stderr, flush=True)

    groups = {
        "rootGroups": [
            {"name": "serving", "hardConcurrencyLimit": 8,
             "maxQueued": 1000}],
        "selectors": [{"group": "serving"}]}

    # one shared discovery plane = one shared worker pool: every
    # coordinator's scheduler reads the same membership
    discovery = DiscoveryNodeManager(ttl_s=3600.0)
    worker = WorkerServer(tpch_sf=sf)
    worker.start()
    discovery.announce(worker.node_id,
                       f"http://127.0.0.1:{worker.port}")

    servers = []
    summary: dict = {"sf": sf, "coordinators": coordinators,
                     "scenarios": {}}
    FAILPOINTS.clear()
    try:
        for i in range(coordinators):
            runner = ClusterRunner(tpch_sf=sf, heartbeat=False,
                                   discovery=discovery)
            srv = PrestoTpuServer(runner, resource_groups=groups,
                                  discovery=discovery)
            srv.start()
            servers.append(srv)
        urls = [f"http://127.0.0.1:{s.port}" for s in servers]
        for i, srv in enumerate(servers):
            srv.enable_fleet(
                f"coord-{i}",
                peers=[u for j, u in enumerate(urls) if j != i],
                heartbeat_s=0.2, staleness_grace_s=0.6)
        victim_idx = coordinators - 1
        victim_id = f"coord-{victim_idx}"

        # the kill only means something once the victim's heartbeats
        # are IN every survivor's federated admission view
        deadline = time.time() + 10.0
        while time.time() < deadline:
            if all(victim_id in s.fleet.status()["remote"]
                   for s in servers[:victim_idx]):
                break
            time.sleep(0.05)
        else:
            raise AssertionError(
                "victim heartbeats never reached the survivors")

        # warm every coordinator once (round-robin covers the fleet)
        # and take the fault-free reference rows
        warm = FleetClient(urls, user="fleet-chaos")
        want = warm.execute(QUERY).rows
        for _ in range(coordinators - 1):
            _assert_rows_equal(warm.execute(QUERY).rows, want,
                               "fleet_warmup")
        warm.close()
        log(f"fleet warm: {len(want)} rows via {coordinators} "
            f"coordinators")

        t0 = time.perf_counter()
        total = clients * per_client
        kill_after = max(1, total // 3)
        done = [0]
        count_lock = threading.Lock()
        killed = threading.Event()
        errors: list = []
        fleet_clients = []

        kill_gate = threading.Lock()

        def ensure_killed() -> None:
            # inline, checked by every client BEFORE each dispatch:
            # once the statement count passes the threshold, the kill
            # happens-before every remaining dispatch — and the ring
            # rotation guarantees at least one of those dispatches
            # lands on the victim's slot, so a failover is observed in
            # EVERY interleaving. (A polling killer thread can lose
            # the race outright on a loaded host: a handful of warm
            # statements finish inside its sleep quantum and the kill
            # arrives after the last query.)
            if killed.is_set():
                return
            with count_lock:
                due = done[0] >= kill_after
            if due:
                with kill_gate:
                    if not killed.is_set():
                        log(f"killing {victim_id} after {done[0]} "
                            f"statements")
                        servers[victim_idx].kill()
                        killed.set()

        def client_run(ci: int) -> None:
            fc = FleetClient(urls, user="fleet-chaos")
            fleet_clients.append(fc)
            for _ in range(per_client):
                ensure_killed()
                try:
                    res = fc.execute(QUERY)
                    _assert_rows_equal(res.rows, want,
                                       "coordinator_kill")
                except Exception as e:        # noqa: BLE001
                    errors.append(f"client {ci}: {e!r}")
                with count_lock:
                    done[0] += 1

        threads = [threading.Thread(target=client_run, args=(ci,),
                                    name=f"fleet-client-{ci}",
                                    daemon=True)
                   for ci in range(clients)]
        for t in threads:
            t.start()
        # a client whose statement never comes back is an error with a
        # name, not a wait (a warm statement takes well under a second)
        join_by = time.monotonic() + CLIENTS_JOIN_S
        for t in threads:
            t.join(timeout=max(0.0, join_by - time.monotonic()))
        waiting = [t.name for t in threads if t.is_alive()]
        assert not waiting, \
            f"clients still in a statement after {CLIENTS_JOIN_S} s: " \
            f"{waiting} ({done[0]} of {total} statements done, " \
            f"errors so far: {errors})"
        assert killed.is_set(), "the kill threshold was never reached"
        assert not errors, f"queries failed across the kill: {errors}"

        # deterministic failover probe: one more statement whose ring
        # STARTS at the corpse. The concurrent phase proves zero
        # failed queries, but its clients may all have drawn their
        # victim-slot visit BEFORE the kill (the rotation is staggered
        # per client, not per statement outcome) — this probe pins the
        # re-dispatch-around-a-dead-coordinator path in every run.
        probe = FleetClient(urls, user="fleet-chaos")
        probe._rr = victim_idx
        fleet_clients.append(probe)
        _assert_rows_equal(probe.execute(QUERY).rows, want,
                           "failover_probe")
        probe.close()
        total += 1

        # survivors absorb the loss: the dead coordinator ages out of
        # the federated admission view after the staleness grace and
        # lands in the lost ledger; the counter is SQL-visible
        deadline = time.time() + 10.0
        absorbed = False
        lost_seen = 0.0
        views = []
        while time.time() < deadline:
            views = [s.fleet.status()
                     for s in servers[:victim_idx]]
            absorbed = all(
                victim_id in v["lost"]
                and victim_id not in v["remote"] for v in views)
            lost_seen = _metric_sql(servers[0].runner,
                                    "coordinator_lost_total")
            if absorbed and lost_seen >= 1.0:
                break
            time.sleep(0.1)
        assert absorbed, \
            f"survivors still count the dead coordinator: {views}"
        assert lost_seen >= 1.0, \
            "coordinator_lost_total never moved"

        summary["scenarios"]["coordinator_kill"] = {
            "elapsed_s": round(time.perf_counter() - t0, 3),
            "queries": total,
            "failed": len(errors),
            "failovers": sum(fc.failovers_total
                             for fc in fleet_clients),
            "retries": sum(fc.retries_total for fc in fleet_clients),
            "coordinator_lost_total": lost_seen,
            "survivor_lost_view": sorted(views[0]["lost"]),
        }
        log(f"coordinator_kill: "
            f"{summary['scenarios']['coordinator_kill']}")
        summary["ok"] = True
        return summary
    finally:
        FAILPOINTS.clear()
        for srv in servers:
            try:
                srv.kill()
            except Exception:
                pass
        try:
            worker.stop()
        except Exception:
            pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sf", type=float, default=0.01,
                    help="TPC-H scale factor (default 0.01)")
    ap.add_argument("--fleet", action="store_true",
                    help="run the coordinator-fleet death drill "
                         "instead of the worker chaos suite")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)
    if args.fleet:
        summary = run_fleet_chaos(sf=args.sf, verbose=not args.quiet)
        print(json.dumps(summary, indent=2))
        return 0 if summary.get("ok") else 1
    summary = run_chaos(sf=args.sf, verbose=not args.quiet)
    print(json.dumps(summary, indent=2))
    return 0 if summary.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
