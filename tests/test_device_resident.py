"""Distributed operators must not stage batches through the host.

The exchange contract (SURVEY.md §2d): all data movement between shards
rides XLA collectives over the mesh; the host sees only deliberate sizing
scalars (explicit jax.device_get) and the final client result. Wrapping
execution in jax.transfer_guard_device_to_host("disallow") rejects any
IMPLICIT device-to-host transfer — the first half of every host bounce —
which pins down round 4's sort/top-n/window/unnest/broadcast-build paths
gathering whole batches into numpy (reference contract: exchange-only
data movement, operator/ExchangeClient.java:55). Host-to-device stays
unguarded: eager jnp ops legitimately ship Python scalar constants.
"""
import jax
import pytest

# tier-1 budget: excluded from `pytest -m 'not slow'` — transfer-guard mesh runs are compile-bound
# (see tools/check_tier1_time.py; ~77s)
pytestmark = pytest.mark.slow

from presto_tpu.exec.runner import LocalRunner

SF = 0.01

#: join + top-n + sort + window + unnest + semi-join shapes — one per
#: operator family the round-4 review flagged as host-bouncing
GUARDED_QUERIES = [
    # broadcast-build join + group-by + top-n
    """select o_orderpriority, count(*) c from orders
       join lineitem on o_orderkey = l_orderkey
       group by o_orderpriority order by c desc limit 3""",
    # distributed sort (range exchange)
    """select l_orderkey, l_extendedprice from lineitem
       where l_quantity > 49 order by l_extendedprice desc, l_orderkey""",
    # window over partitions (hash exchange) and global window
    """select o_custkey, rank() over (partition by o_custkey
       order by o_totalprice desc) r from orders where o_custkey < 100""",
    """select o_orderkey, sum(o_totalprice) over (order by o_orderkey)
       from orders where o_orderkey < 64""",
    # unnest
    """select u from unnest(sequence(1, 5)) as t(u)""",
    # semi join
    """select count(*) from orders where o_orderkey in
       (select l_orderkey from lineitem where l_quantity > 49)""",
]


@pytest.fixture(scope="module")
def local():
    return LocalRunner(tpch_sf=SF)


@pytest.fixture(scope="module")
def dist(local, mesh_runner):
    return mesh_runner(catalogs=local.session.catalogs,
                       rows_per_batch=1 << 13)


@pytest.mark.parametrize("sql", GUARDED_QUERIES)
def test_no_implicit_host_transfers(local, dist, sql):
    want = sorted(map(repr, local.execute(sql).rows))
    with jax.transfer_guard_device_to_host("disallow"):
        got = dist.execute(sql)
    assert sorted(map(repr, got.rows)) == want
