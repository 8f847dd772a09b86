"""Fair device scheduling across concurrent queries (the reference's
TaskExecutor / MultilevelSplitQueue role, execution/executor/
TaskExecutor.java:79, MultilevelSplitQueue.java:43)."""
import threading
import time

import pytest

from presto_tpu.exec.taskexec import DeviceScheduler, LEVEL_THRESHOLDS


def test_levels_by_cumulative_time():
    s = DeviceScheduler()
    h = s.task("t")
    assert h.level == 0
    h.device_seconds = 2.0
    assert h.level == 1
    h.device_seconds = 400.0
    assert h.level == len(LEVEL_THRESHOLDS) - 1


def test_low_usage_task_preempts_between_quanta():
    """A fresh task is granted the device ahead of a task that has
    accumulated more device time, at every quantum boundary."""
    s = DeviceScheduler()
    heavy = s.task("heavy")
    light = s.task("light")
    order = []
    stop = threading.Event()

    def heavy_loop():
        while not stop.is_set():
            s.run_quantum(heavy, lambda: (order.append("heavy"),
                                          time.sleep(0.02)))

    t = threading.Thread(target=heavy_loop, daemon=True)
    t.start()
    time.sleep(0.08)        # heavy accumulates usage
    for _ in range(5):
        s.run_quantum(light, lambda: order.append("light"))
    stop.set()
    t.join(timeout=5)
    # all 5 light quanta were granted while heavy kept requesting
    lights = [i for i, x in enumerate(order) if x == "light"]
    assert len(lights) == 5
    assert heavy.device_seconds > light.device_seconds
    # light never waited behind more than one heavy quantum: its grants
    # are consecutive-ish (no long heavy runs interleaved)
    gaps = [b - a for a, b in zip(lights, lights[1:])]
    assert max(gaps) <= 2


def test_concurrent_queries_interleave():
    """A short query against a busy runner completes while a long query
    is still executing (reference simulator-style check)."""
    from presto_tpu.exec.runner import LocalRunner
    runner = LocalRunner(tpch_sf=0.05, rows_per_batch=1 << 12)
    runner.execute("select 1")      # warm caches

    long_done = threading.Event()
    short_done_at = []
    long_done_at = []
    t0 = time.perf_counter()

    def long_query():
        runner.execute(
            "select l_suppkey, count(*), sum(l_extendedprice) "
            "from lineitem group by 1")
        long_done_at.append(time.perf_counter() - t0)
        long_done.set()

    def short_query():
        time.sleep(0.05)   # start after the long query is underway
        runner.execute("select count(*) from nation")
        short_done_at.append(time.perf_counter() - t0)

    tl = threading.Thread(target=long_query)
    ts = threading.Thread(target=short_query)
    tl.start()
    ts.start()
    tl.join(timeout=120)
    ts.join(timeout=120)
    assert short_done_at and long_done_at
    # the short query must not have been serialized behind the whole
    # long query
    assert short_done_at[0] <= long_done_at[0] + 0.5


def test_lock_discipline_clean_after_scheduler_exercise():
    """The fair scheduler's locks fed the runtime lock-order validator
    through every test above: no observed inversion cycles, and no jit
    dispatch ever ran under an engine lock (ISSUE 7 runtime checker)."""
    from presto_tpu._devtools import lockcheck
    assert lockcheck.ENABLED
    assert lockcheck.GRAPH.check() == [], lockcheck.GRAPH.check()


def test_stalled_releases_device_then_restores_bookkeeping():
    """Regression for the cross-worker exchange deadlock: a consumer
    blocked on remote pages inside its quantum must RELEASE the device
    (another query's quantum runs meanwhile), then re-acquire on exit
    with the nesting depth exactly restored — an unbalanced depth
    either wedges the scheduler or lets two quanta run at once."""
    from presto_tpu.obs.metrics import REGISTRY
    s = DeviceScheduler()
    a = s.task("stall-a")
    b = s.task("other-b")
    stalled_now = threading.Event()
    release = threading.Event()
    order = []

    def a_quantum():
        order.append("a-enter")
        with s.stalled(a):
            stalled_now.set()
            assert release.wait(timeout=5)
        order.append("a-resume")

    before = REGISTRY.counter("device_stall_release_total").value
    t = threading.Thread(
        target=lambda: s.run_quantum(a, a_quantum), daemon=True)
    t.start()
    assert stalled_now.wait(timeout=5)
    # the device is free while A waits on input: B's quantum runs NOW
    s.run_quantum(b, lambda: order.append("b-ran"))
    release.set()
    t.join(timeout=5)
    assert not t.is_alive()
    assert order == ["a-enter", "b-ran", "a-resume"]
    assert REGISTRY.counter(
        "device_stall_release_total").value == before + 1
    # bookkeeping balanced: scheduler idle, depth zero
    assert s._running is None
    assert s._running_depth == 0


def test_stalled_inside_nested_quantum_keeps_reentrancy():
    """stalled() gives back ONE nesting level. Inside a re-entrant
    (nested same-handle) quantum the outer level still holds the
    device, and the exit path must rebuild depth to exactly 2 before
    unwinding — off-by-one here frees the device while the outer
    quantum is mid-flight."""
    s = DeviceScheduler()
    a = s.task("nested")

    def inner():
        with s.stalled(a):
            # one level released, the outer one still held
            assert s._running is a
            assert s._running_depth == 1
        assert s._running_depth == 2

    def outer():
        s.run_quantum(a, inner)

    s.run_quantum(a, outer)
    assert s._running is None
    assert s._running_depth == 0


def test_stalled_without_held_quantum_is_a_noop():
    """Outside any quantum (fair_scheduling off, init paths) stalled()
    must not touch scheduler state or the release counter."""
    from presto_tpu.obs.metrics import REGISTRY
    s = DeviceScheduler()
    a = s.task("free")
    before = REGISTRY.counter("device_stall_release_total").value
    with s.stalled(a):
        pass
    with s.stalled(None):
        pass
    assert REGISTRY.counter(
        "device_stall_release_total").value == before
    assert s._running is None and s._running_depth == 0
