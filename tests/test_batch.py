import datetime
import re
from decimal import Decimal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.batch import (
    Batch, Column, Schema, bucket_capacity, concat_batches)


def test_bucket_capacity():
    assert bucket_capacity(1) == 128
    assert bucket_capacity(128) == 128
    assert bucket_capacity(129) == 256
    assert bucket_capacity(100_000) == 131072


def test_type_parse_roundtrip():
    for s in ["bigint", "integer", "double", "boolean", "date",
              "decimal(12,2)", "varchar(25)", "char(1)", "varchar"]:
        t = T.parse_type(s)
        assert T.parse_type(t.display()) == t


def test_decimal_storage():
    d = T.decimal(12, 2)
    assert d.to_storage("1.005") == 101  # round half up
    assert d.to_storage(3) == 300
    assert d.from_storage(12345) == Decimal("123.45")


def test_date_storage():
    assert T.DATE.to_storage("1970-01-02") == 1
    assert T.DATE.from_storage(0) == datetime.date(1970, 1, 1)
    assert T.DATE.to_storage(datetime.date(1994, 1, 1)) == 8766


def test_batch_pydict_roundtrip():
    b = Batch.from_pydict({
        "a": (T.BIGINT, [1, 2, None, 4]),
        "b": (T.DOUBLE, [1.5, None, 3.5, 4.5]),
        "s": (T.varchar(10), ["x", "y", "x", None]),
        "d": (T.DATE, ["1994-01-01", None, "1995-06-15", "1992-02-02"]),
    })
    assert b.capacity == 128
    assert b.host_count() == 4
    rows = b.to_pylist()
    assert rows[0] == (1, 1.5, "x", datetime.date(1994, 1, 1))
    assert rows[1][1] is None
    assert rows[2][2] == "x"
    assert rows[3][2] is None


def test_batch_is_pytree():
    b = Batch.from_pydict({"a": (T.BIGINT, [1, 2, 3])})

    @jax.jit
    def double(batch):
        col = batch.column("a")
        new = type(col)(col.type, col.data * 2, col.validity, col.dictionary)
        return batch.with_columns(batch.schema, [new])

    out = double(b)
    assert [r[0] for r in out.to_pylist()] == [2, 4, 6]


def test_compact():
    b = Batch.from_pydict({"a": (T.BIGINT, [10, 20, 30, 40, 50])})
    # kill rows 1 and 3
    mask = np.asarray(b.row_mask).copy()
    mask[1] = False
    mask[3] = False
    b2 = Batch(b.schema, b.columns, jnp.asarray(mask))
    c = b2.compact()
    assert c.host_count() == 3
    assert [r[0] for r in c.to_pylist()] == [10, 30, 50]


def test_concat_unifies_dictionaries():
    b1 = Batch.from_pydict({"s": (T.VARCHAR, ["a", "b"])}, capacity=128)
    b2 = Batch.from_pydict({"s": (T.VARCHAR, ["b", "c", None])}, capacity=128)
    out = concat_batches([b1, b2])
    vals = [r[0] for r in out.to_pylist()]
    assert vals == ["a", "b", "b", "c", None]
    assert out.column("s").dictionary == ("a", "b", "c")


def test_select():
    b = Batch.from_pydict({
        "a": (T.BIGINT, [1]), "b": (T.DOUBLE, [2.0]), "c": (T.INTEGER, [3]),
    })
    s = b.select(["c", "a"])
    assert s.schema.names == ["c", "a"]
    assert s.to_pylist() == [(3, 1)]


# -- Batch.compact against a NumPy reference ---------------------------------
# (PR 30: the indices come from batch.live_indices, a compress network
# with no scatter in it, where jnp.nonzero's bincount was one)

#: name -> (live rows of a 4096-lane batch as a function of the
#: generator, the capacity asked for; None = the batch's own)
_MASKS = {
    "empty": (lambda rng, n: np.zeros(n, bool), 128),
    "one_row": (lambda rng, n: np.arange(n) == 2917, 128),
    "sparse_1.9pct": (lambda rng, n: rng.random(n) < 0.019, 128),
    "exactly_cap": (lambda rng, n: rng.permutation(n) < 128, 128),
    "more_than_cap": (lambda rng, n: rng.permutation(n) < 300, 128),
    "full": (lambda rng, n: np.ones(n, bool), None),
    "grows": (lambda rng, n: rng.random(n) < 0.3, 8192),
}


def _compactable(rng, capacity, mask):
    """A batch with every kind of column: nulls, dictionary codes,
    ARRAY, MAP (its data a tuple of arrays, two of them 2-D)."""
    n = capacity
    vocab = ("ash", "birch", "cedar", "elm")
    keys = jnp.asarray(rng.integers(0, 3, (n, 3)), jnp.int32)
    cols = [
        Column(T.BIGINT, jnp.asarray(rng.integers(-9, 9, n)),
               jnp.asarray(rng.random(n) < 0.8), None),
        Column(T.DOUBLE, jnp.asarray(rng.random(n)),
               jnp.ones(n, bool), None),
        Column(T.VARCHAR, jnp.asarray(rng.integers(0, 4, n), jnp.int32),
               jnp.asarray(rng.random(n) < 0.9), vocab),
        Column(T.ArrayType(element=T.BIGINT),
               (jnp.asarray(rng.integers(0, 99, (n, 4))),
                jnp.asarray(rng.integers(0, 5, n), jnp.int32),
                jnp.asarray(rng.random((n, 4)) < 0.7)),
               jnp.asarray(rng.random(n) < 0.95), None),
        Column(T.MapType(key=T.VARCHAR, value=T.BIGINT),
               (keys, jnp.asarray(rng.integers(0, 99, (n, 3))),
                jnp.asarray(rng.integers(0, 4, n), jnp.int32),
                jnp.asarray(rng.random((n, 3)) < 0.6)),
               jnp.ones(n, bool), (vocab[:3], None)),
    ]
    schema = Schema([(f"c{i}", c.type) for i, c in enumerate(cols)])
    return Batch(schema, cols, jnp.asarray(mask))


def _compact_by_hand(batch, cap):
    """(mask, [(data leaves, validity)]) of the compacted batch: live
    rows in order, the dead tail pointing at the last lane."""
    mask = np.asarray(batch.row_mask)
    live = np.flatnonzero(mask)[:cap]
    idx = np.full(cap, batch.capacity - 1)
    idx[:len(live)] = live
    new_mask = np.arange(cap) < mask.sum()
    return new_mask, [
        ([np.asarray(a)[idx] for a in jax.tree_util.tree_leaves(c.data)],
         np.asarray(c.validity)[idx] & new_mask) for c in batch.columns]


def _assert_compacted(got, want, schema_of):
    mask, cols = want
    np.testing.assert_array_equal(np.asarray(got.row_mask), mask)
    assert got.schema.names == schema_of.schema.names
    for g, src, (leaves, validity) in zip(got.columns, schema_of.columns,
                                          cols):
        assert g.type == src.type and g.dictionary == src.dictionary
        np.testing.assert_array_equal(np.asarray(g.validity), validity)
        got_leaves = jax.tree_util.tree_leaves(g.data)
        assert len(got_leaves) == len(leaves)
        for a, b in zip(got_leaves, leaves):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), b)


@pytest.mark.parametrize("how", ["eager", "compact_jit", "shard_map"])
@pytest.mark.parametrize("mask_name", list(_MASKS))
def test_compact_equals_the_numpy_reference(mask_name, how):
    from presto_tpu.ops.jitcache import compact_jit
    make_mask, cap = _MASKS[mask_name]
    rng = np.random.default_rng(sorted(_MASKS).index(mask_name))
    if how != "shard_map":
        b = _compactable(rng, 4096, make_mask(rng, 4096))
        out_cap = cap or b.capacity
        got = (b.compact(cap, check=False) if how == "eager"
               else compact_jit(b, out_cap))
        _assert_compacted(got, _compact_by_hand(b, out_cap), b)
        assert got.capacity == out_cap
        return
    # two shards of 4096 lanes, each compacted inside one program
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    halves = [_compactable(rng, 4096, make_mask(rng, 4096))
              for _ in range(2)]
    out_cap = cap or 4096
    b = jax.tree_util.tree_map(lambda x, y: jnp.concatenate([x, y]),
                               *halves)
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    got = jax.jit(shard_map(lambda s: s.compact(cap, check=False),
                            mesh=mesh, in_specs=P("x"),
                            out_specs=P("x")))(b)
    wants = [_compact_by_hand(h, out_cap) for h in halves]
    want = (np.concatenate([w[0] for w in wants]),
            [([np.concatenate(pair) for pair in zip(l0, l1)],
              np.concatenate([v0, v1]))
             for (l0, v0), (l1, v1) in zip(wants[0][1], wants[1][1])])
    _assert_compacted(got, want, b)


def test_compact_checks_the_count_unless_told_not_to():
    rng = np.random.default_rng(5)
    b = _compactable(rng, 4096, rng.permutation(4096) < 300)
    with pytest.raises(ValueError, match="300"):
        b.compact(128)
    assert b.compact(128, check=False).host_count() == 128
    assert b.compact(512).host_count() == 300


@pytest.mark.parametrize("capacity,cap", [(4096, 128), (1 << 17, 1 << 12),
                                          (1000, 128), (128, 1024)])
def test_the_compaction_program_holds_no_scatter(capacity, cap):
    """``jnp.nonzero(size=)`` lowered to a scatter-add of one update a
    lane (~100 ms a 2^20-lane batch on the v5e); the program that
    replaced it holds none, before XLA's passes or after."""
    rng = np.random.default_rng(6)
    b = _compactable(rng, capacity, rng.random(capacity) < 0.019)
    def scatters(text):      # the op, not a name that holds the word
        return re.findall(r"stablehlo\.scatter|\sscatter\(", text)
    lowered = jax.jit(lambda x: x.compact(cap, check=False)).lower(b)
    assert not scatters(lowered.as_text())
    assert not scatters(lowered.compile().as_text())
    old = jax.jit(lambda m: jnp.nonzero(m, size=cap)[0]).lower(b.row_mask)
    assert scatters(old.as_text()) and scatters(old.compile().as_text())


@pytest.mark.parametrize("capacity", [128, 1000, 1024, 4096, 5 * 1024])
def test_live_indices_are_jnp_nonzeros(capacity):
    from presto_tpu.batch import live_indices
    rng = np.random.default_rng(capacity)
    for share in (0.0, 0.019, 0.5, 1.0):
        mask = jnp.asarray(rng.random(capacity) < share)
        for size in (128, capacity, 2 * capacity):
            idx, n = live_indices(mask, size)
            want = jnp.nonzero(mask, size=size, fill_value=capacity - 1)[0]
            np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
            assert idx.dtype == jnp.int32 and int(n) == int(mask.sum())
