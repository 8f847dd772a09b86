"""When the program left the device with nothing to do (PR 37).

- a readback (``obs/trace.device_sync``) with the tracer off builds no
  span and waits for nothing: the same ``device_get``, one counter a
  kind; with it on, its ``device-sync`` span splits into the wait for
  the device (``wait_s``) and the fetch, and says whether the read
  left the device ``drained``;
- a launch (``dispatch`` span) says whether it found the device
  ``starved``: ``device_drained()``, a flag read of the last launch's
  output, unknown (None) where there is none to ask;
- the mesh's ``input-drain`` is the same primitive with nothing to
  fetch: all wait.
"""
import jax
import jax.numpy as jnp
import pytest

from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs import trace as obs_trace
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.obs.trace import (TRACER, device_drained, device_sync,
                                  note_launch)

Q3 = """\
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
  o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey
  and o_orderdate < date '1995-03-15' and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate, l_orderkey
limit 10"""

Q6 = """\
select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01'
  and l_shipdate < date '1994-01-01' + interval '1' year
  and l_discount between 0.06 - 0.01 and 0.06 + 0.01
  and l_quantity < 24"""

MESH_GROUP_BY = (
    "select l_returnflag, sum(l_quantity) from lineitem "
    "where l_shipdate <= date '1998-09-02' group by 1")


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=0.01, rows_per_batch=8192)


@pytest.fixture
def tracer_on():
    TRACER.clear()
    TRACER.enable(True)
    try:
        yield TRACER
    finally:
        TRACER.enable(False)
        TRACER.clear()


def _family(prefix):
    return {m["name"][len(prefix):]: m["value"]
            for m in REGISTRY.snapshot() if m["name"].startswith(prefix)}


def test_tracer_off_a_readback_is_the_fetch_and_one_counter(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("block_until_ready with the tracer off")
    monkeypatch.setattr(jax, "block_until_ready", refuse)
    made = []
    monkeypatch.setattr(obs_trace, "_annotation",
                        lambda *a: made.append(a))
    assert not TRACER.enabled
    counts = _family("device_sync_total.")
    seconds = _family("device_sync_seconds_total.")
    value = jnp.arange(5) * 2
    host = device_sync("feed-test", (value, [value + 1]))
    assert host[0].tolist() == [0, 2, 4, 6, 8]
    assert host[1][0].tolist() == [1, 3, 5, 7, 9]
    assert made == [] and TRACER.export() == []
    moved = {k: v - counts.get(k, 0.0)
             for k, v in _family("device_sync_total.").items()}
    assert {k: v for k, v in moved.items() if v} == {"feed-test": 1.0}
    assert _family("device_sync_seconds_total.")["feed-test"] \
        > seconds.get("feed-test", 0.0)
    # the unlabelled pair is gone: the family's sum says what it said
    assert REGISTRY.value("device_sync_total", default=-1.0) == -1.0
    assert REGISTRY.value("device_sync_seconds_total", default=-1.0) == -1.0
    # and no launch keeps a reference to its output
    assert obs_trace._LAST_LAUNCH is None and device_drained() is None


@pytest.mark.parametrize("sql", [Q3, Q6], ids=["q3", "q6"])
def test_every_sync_splits_and_every_launch_says_starved(
        runner, tracer_on, sql):
    props = {"mesh_execution": "off"}
    runner.execute(sql, properties=props)   # compiles; the first launch
    TRACER.clear()                          # has nothing before it to ask
    before = _family("device_sync_total.")
    rows = runner.execute(sql, properties=props).rows
    after = _family("device_sync_total.")
    assert rows
    spans = TRACER.export()
    syncs = [s for s in spans if s["name"] == "device-sync"]
    launches = [s for s in spans if s["name"] == "dispatch"]
    assert syncs and launches
    for s in syncs:
        assert 0.0 <= s["attrs"]["wait_s"] <= s["end"] - s["start"], s
        assert isinstance(s["attrs"]["drained"], bool), s
    for s in launches:
        assert isinstance(s["attrs"]["starved"], bool), s
    moved = {k: after[k] - before.get(k, 0.0) for k in after}
    by_what = {}
    for s in syncs:
        by_what[s["attrs"]["what"]] = by_what.get(s["attrs"]["what"], 0) + 1
    assert {k: v for k, v in moved.items() if v} == by_what
    assert sum(moved.values()) == len(syncs)


class _Leaf:
    """What the tracer asks of a launch's output: a handle on a shard
    and its ``is_ready()``."""

    def __init__(self, ready):
        self.ready = ready

    def addressable_data(self, index):
        return self

    def is_ready(self):
        if isinstance(self.ready, Exception):
            raise self.ready
        return self.ready


def test_device_drained_on_stub_leaves(tracer_on):
    assert device_drained() is None             # none launched yet
    out = {"a": _Leaf(False), "b": [_Leaf(True)]}
    assert note_launch(out) is out
    assert device_drained() is True             # the last leaf's flag
    note_launch(_Leaf(False))
    assert device_drained() is False
    note_launch(_Leaf(RuntimeError("Array has been deleted.")))
    assert device_drained() is None             # unknown, no exception
    note_launch(())                             # no leaf: the last stays
    assert device_drained() is None
    x = jnp.arange(8) + 1
    note_launch(x)
    x.block_until_ready()
    assert device_drained() is True
    x.delete()
    assert device_drained() is None
    note_launch(_Leaf(True))
    TRACER.enable(False)                        # off: the reference goes
    assert obs_trace._LAST_LAUNCH is None and device_drained() is None


def test_a_drained_read_and_a_late_read(tracer_on):
    """The value read is the last launch's: drained. A launch made
    after the value's producer and still running: not drained."""
    value = note_launch(jnp.arange(4) + 1)
    assert device_sync("feed-test", value).tolist() == [1, 2, 3, 4]
    note_launch(_Leaf(False))
    device_sync("feed-test", value)
    first, second = [s["attrs"] for s in TRACER.export()
                     if s["name"] == "device-sync"]
    assert first["drained"] is True and second["drained"] is False


def test_a_mesh_input_drain_is_all_wait(runner, tracer_on):
    # the classic exchange reads its quotas back: two control fetches
    props = {"mesh_execution": "on", "mesh_devices": "4",
             "mesh_fused_exchange": "false"}
    runner.execute(MESH_GROUP_BY, properties=props)
    spans = TRACER.export()
    drains = [s for s in spans if s["name"] == "device-sync"
              and s["attrs"]["what"] == "input-drain"]
    assert drains
    for s in drains:
        assert s["attrs"]["wait_s"] == s["end"] - s["start"], s
        assert s["attrs"]["drained"] in (True, False, None)
    # every other sync of the statement fetches: its wait is a part
    for s in spans:
        if s["name"] == "device-sync" and s not in drains:
            assert 0.0 <= s["attrs"]["wait_s"] <= s["end"] - s["start"]
