"""Fused SPMD exchange (PR 15): one compiled program per stage.

The tentpole contract: with ``mesh_fused_exchange`` on (the default),
repartition fuses into the producer's shard_map program (compute +
bucket-count + ship is ONE dispatch ending in device collectives),
stats-bounded aggregation stages batch their rounds into a single
``lax.fori_loop`` dispatch over donated shard buffers, and the host
fetches control scalars once per stage instead of once per round.
``mesh_fused_exchange=off`` is the escape hatch back to the per-round
host control plane — and the oracle these tests compare against:
fused and unfused must be row-exact across NULL-heavy, skewed and
empty-shard inputs, including a forced mid-query re-split.
"""
import jax
import jax.numpy as jnp
import pytest

from presto_tpu.exec.runner import LocalRunner
from presto_tpu.obs.metrics import REGISTRY

from test_mesh_default import _check_parity

SF = 0.005

ON = {"mesh_execution": "on"}

#: the fused-vs-unfused sweep: each shape stresses one failure mode of
#: a fused exchange — NULL groups crossing shards, skewed bucket loads,
#: shards that receive zero rows after partitioning
SHAPES = [
    ("null-heavy", "select n_name, count(c_custkey), sum(c_acctbal) "
                   "from nation left join customer "
                   "on n_nationkey = c_nationkey and c_acctbal < 0 "
                   "group by 1 order by 1"),
    ("skewed", "select o_orderstatus, count(*), sum(o_totalprice), "
               "min(o_orderdate) from orders group by 1 order by 1"),
    ("empty-shard", "select c_mktsegment, count(*) from customer "
                    "where c_custkey < 5 group by 1 order by 1"),
]


def _metric(name: str) -> float:
    return REGISTRY.value(name)


@pytest.fixture(scope="module")
def runner():
    return LocalRunner(tpch_sf=SF, rows_per_batch=1 << 11)


@pytest.fixture(scope="module")
def small_runner():
    # small batches -> many chunks per stage: the shape where the
    # per-round dispatch tax is visible at suite scale
    return LocalRunner(tpch_sf=SF, rows_per_batch=1 << 9)


def _fused_vs_unfused(runner, sql, n, extra=None):
    base = {**ON, "mesh_devices": n, **(extra or {})}
    want = runner.execute(
        sql, properties={**base, "mesh_fused_exchange": False})
    got = runner.execute(
        sql, properties={**base, "mesh_fused_exchange": True})
    _check_parity(want, got, "order by" in sql.lower())
    return got


@pytest.mark.parametrize("name,sql", SHAPES, ids=[s[0] for s in SHAPES])
@pytest.mark.parametrize("n", [2, 4])
def test_fused_parity(runner, name, sql, n):
    _fused_vs_unfused(runner, sql, n)


@pytest.mark.slow
@pytest.mark.parametrize("name,sql", SHAPES, ids=[s[0] for s in SHAPES])
@pytest.mark.parametrize("n", [1, 8])
def test_fused_parity_edge_widths(runner, name, sql, n):
    """n=1 (fused loop with no real exchange) and the full 8-wide mesh
    ride the slow tier — same contract, pricier compiles."""
    _fused_vs_unfused(runner, sql, n)


def test_fused_parity_small_loop_rounds(runner):
    """mesh_fused_loop_rounds=2 forces multi-wave draining: the second
    wave re-enters with the carried (donated) state batch, the shape
    the single-wave tests never exercise."""
    _fused_vs_unfused(runner, SHAPES[1][1], 2,
                      extra={"mesh_fused_loop_rounds": 2})


def test_fused_parity_under_forced_resplit(runner, monkeypatch):
    """A mid-query adaptive re-split is the fused path's rarer
    loop-exit-and-rebuild branch: with the skew threshold forced low, a
    partitioned join re-splits its bucket assignment while the fused
    probe stream is in flight, the build side re-ships under the new
    epoch, and fused still matches unfused row-for-row."""
    from presto_tpu.exec import distributed as D
    monkeypatch.setattr(D, "_skew_ratio", lambda: 1.01)
    sql = ("select c_name, sum(o_totalprice) from customer join orders "
           "on c_custkey = o_custkey group by 1 order by 2 desc, 1 "
           "limit 5")
    before = _metric("mesh_repartition_resplit_total")
    _fused_vs_unfused(runner, sql, 2,
                      extra={"broadcast_join_row_limit": 1})
    assert _metric("mesh_repartition_resplit_total") > before


def test_fused_slashes_host_dispatches(small_runner):
    """The dispatch-tax claim at suite scale: the same grouped
    aggregation costs at most half the host dispatches fused vs
    unfused (in-suite the guard is a conservative 2x). Warm runs
    are compared so plan/compile effects cancel."""
    sql = SHAPES[1][1]
    base = {**ON, "mesh_devices": 4}
    small_runner.execute(
        sql, properties={**base, "mesh_fused_exchange": False})
    b0 = _metric("mesh_dispatches_total")
    small_runner.execute(
        sql, properties={**base, "mesh_fused_exchange": False})
    unfused = _metric("mesh_dispatches_total") - b0
    small_runner.execute(sql, properties=base)
    b1 = _metric("mesh_dispatches_total")
    small_runner.execute(sql, properties=base)
    fused = _metric("mesh_dispatches_total") - b1
    assert fused > 0
    assert fused * 2 <= unfused, (fused, unfused)


def test_fused_wave_donates_carried_state(small_runner, monkeypatch):
    """The carried state batch of a multi-wave fused drain is DONATED:
    the executor builds the wave program with donate_argnums on the
    carry position, so round N's output aliases round N-1's buffers
    instead of churning HBM."""
    from presto_tpu.exec.distributed import DistributedExecutor
    donated = []
    orig = DistributedExecutor._smap

    def spy(self, fn, n_in, *args, **kwargs):
        if kwargs.get("donate"):
            donated.append(tuple(kwargs["donate"]))
        return orig(self, fn, n_in, *args, **kwargs)

    monkeypatch.setattr(DistributedExecutor, "_smap", spy)
    small_runner.execute(SHAPES[1][1],
                         properties={**ON, "mesh_devices": 2,
                                     "mesh_fused_loop_rounds": 2})
    assert (0,) in donated


def test_donated_buffer_is_invalidated():
    """Donation semantics the fused loops rely on, pinned at the JAX
    level: a donated input is deleted on dispatch (reuse raises), and
    the compiled program reports the aliased bytes — if either stops
    holding, the carry-donation above silently degrades to a copy."""
    from presto_tpu.ops.jitcache import _TimedEntry
    entry = _TimedEntry(
        "test:donate",
        jax.jit(lambda a, b: (a + b, a - b), donate_argnums=(0,)),
        key=("test_donate",), donate=(0,))
    assert entry.donate == (0,)
    x = jnp.arange(1 << 10, dtype=jnp.float32)
    y = jnp.ones(1 << 10, dtype=jnp.float32)
    out, _ = entry(x, y)
    out.block_until_ready()
    assert x.is_deleted()
    with pytest.raises(RuntimeError):
        _ = x + 1.0
    lowered = jax.jit(
        lambda a, b: (a + b, a - b), donate_argnums=(0,)
    ).lower(y, y).compile()
    mem = lowered.memory_analysis()
    if mem is not None and hasattr(mem, "alias_size_in_bytes"):
        assert mem.alias_size_in_bytes >= y.nbytes
