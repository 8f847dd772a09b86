"""ARRAY/MAP types, UNNEST, and higher-order functions.

The padded dense representation (reference spi/block/ArrayBlock.java
offsets+values, re-designed as [cap, L] tiles + lengths — types.py
ArrayType) and the array function surface (reference
operator/scalar/Array*.java, UnnestOperator.java,
LambdaBytecodeGenerator.java).
"""
import pytest


@pytest.fixture(scope="module")
def runner():
    from presto_tpu.exec.runner import LocalRunner
    return LocalRunner(tpch_sf=0.001)


def one(runner, sql):
    rows = runner.execute("select " + sql).rows
    assert len(rows) == 1
    return rows[0]


def test_array_literal_roundtrip(runner):
    assert one(runner, "array[1, 2, 3]") == ([1, 2, 3],)
    assert one(runner, "array['a', 'b']") == (["a", "b"],)
    assert one(runner, "array[1, null, 3]") == ([1, None, 3],)


def test_subscript(runner):
    assert one(runner, "array[10, 20, 30][2]") == (20,)


def test_subscript_out_of_bounds_errors(runner):
    from presto_tpu.errors import QueryError
    with pytest.raises(QueryError, match="INVALID_FUNCTION_ARGUMENT"):
        runner.execute("select array[1, 2][5]")


def test_element_at(runner):
    r = one(runner, "element_at(array[10, 20, 30], 2), "
                    "element_at(array[10, 20, 30], -1), "
                    "element_at(array[10, 20], 5)")
    assert r == (20, 30, None)


def test_cardinality_contains_position(runner):
    r = one(runner, "cardinality(array[1, 2, 3]), "
                    "contains(array[1, 2], 2), contains(array['x'], 'z'), "
                    "array_position(array[5, 6, 7], 6), "
                    "array_position(array[5], 9)")
    assert r == (3, True, False, 2, 0)


def test_min_max_sort_distinct(runner):
    r = one(runner, "array_max(array[3, 1, 2]), array_min(array[3, 1, 2]), "
                    "array_min(array['b', 'a']), "
                    "array_sort(array[3, 1, 2]), "
                    "array_distinct(array[1, 2, 1, 3, 2])")
    assert r == (3, 1, "a", [1, 2, 3], [1, 2, 3])


def test_array_min_null_element(runner):
    assert one(runner, "array_min(array[1, null, 3])") == (None,)


def test_concat_operator(runner):
    assert one(runner, "array[1, 2] || array[3]") == ([1, 2, 3],)
    assert one(runner, "array['a'] || array['b', 'a']") == (["a", "b", "a"],)


def test_repeat_sequence(runner):
    r = one(runner, "repeat(7, 3), sequence(1, 4), sequence(5, 1, -2)")
    assert r == ([7, 7, 7], [1, 2, 3, 4], [5, 3, 1])


def test_split(runner):
    assert one(runner, "split('a,b,c', ',')") == (["a", "b", "c"],)
    assert one(runner, "split('a:b:c', ':', 2)") == (["a", "b:c"],)


def test_transform(runner):
    assert one(runner, "transform(array[1, 2, 3], x -> x * 10)") \
        == ([10, 20, 30],)
    assert one(runner, "transform(array['a', 'b'], s -> upper(s))") \
        == (["A", "B"],)


def test_transform_capture(runner):
    rows = runner.execute(
        "select transform(array[1, 2], x -> x + n_regionkey) "
        "from nation where n_nationkey = 1").rows
    assert rows == [([2, 3],)]


def test_filter_lambda(runner):
    assert one(runner, "filter(array[1, -2, 3, -4], x -> x > 0)") \
        == ([1, 3],)


def test_reduce(runner):
    assert one(runner, "reduce(array[1, 2, 3, 4], 0, "
                       "(s, x) -> s + x, s -> s)") == (10,)
    assert one(runner, "reduce(array[2, 3], 1, (s, x) -> s * x, "
                       "s -> s * 10)") == (60,)


def test_match_functions(runner):
    r = one(runner, "any_match(array[1, 2], x -> x > 1), "
                    "all_match(array[1, 2], x -> x > 0), "
                    "none_match(array[1, 2], x -> x > 5)")
    assert r == (True, True, True)


def test_map_functions(runner):
    r = one(runner, "map(array['a', 'b'], array[1, 2])['b'], "
                    "element_at(map(array[1, 2], array['x', 'y']), 3), "
                    "cardinality(map(array['a'], array[1]))")
    assert r == (2, None, 1)
    r = one(runner, "map_keys(map(array['a', 'b'], array[1, 2])), "
                    "map_values(map(array['a', 'b'], array[1, 2]))")
    assert r == (["a", "b"], [1, 2])


def test_map_to_pylist(runner):
    assert one(runner, "map(array['k'], array[9])") == ({"k": 9},)


def test_unnest_standalone(runner):
    rows = runner.execute(
        "select x, o from unnest(array[10, 20, 30]) "
        "with ordinality as t(x, o)").rows
    assert rows == [(10, 1), (20, 2), (30, 3)]


def test_unnest_lateral(runner):
    rows = runner.execute(
        "select n_name, x from nation, "
        "unnest(array[n_nationkey, n_regionkey]) as u(x) "
        "where n_nationkey = 1").rows
    assert rows == [("ARGENTINA", 1), ("ARGENTINA", 1)]


def test_unnest_aggregate(runner):
    want = runner.execute(
        "select sum(n_nationkey) + sum(n_regionkey) from nation").rows
    got = runner.execute(
        "select sum(x) from nation, "
        "unnest(array[n_nationkey, n_regionkey]) as u(x)").rows
    assert got == want


def test_unnest_group_by(runner):
    rows = runner.execute(
        "select x, count(*) from nation, "
        "unnest(array[n_regionkey, n_regionkey]) as u(x) "
        "group by 1 order by 1").rows
    assert all(c == 10 for _, c in rows) and len(rows) == 5


def test_array_in_where(runner):
    rows = runner.execute(
        "select n_name from nation "
        "where contains(array[1, 3], n_nationkey) order by 1").rows
    assert [r[0] for r in rows] == ["ARGENTINA", "CANADA"]


def test_array_agg_on_split_column(runner):
    rows = runner.execute(
        "select cardinality(split(n_name, 'A')) from nation "
        "where n_nationkey = 0").rows
    assert rows == [(3,)]     # ALGERIA -> ['', 'LGERI', '']


def test_null_array(runner):
    assert one(runner, "cardinality(cast(null as array(bigint)))") == (None,)


def test_nested_transform_filter(runner):
    assert one(runner, "transform(filter(array[1, 2, 3, 4], x -> x % 2 = 0), "
                       "y -> y * y)") == ([4, 16],)


def test_nested_lambda_outer_param(runner):
    # inner lambda referencing the OUTER lambda's parameter
    assert one(runner, "filter(array[1, 2, 3], "
                       "x -> any_match(array[10, 20], y -> y = x * 10))") \
        == ([1, 2],)


def test_contains_null_three_valued(runner):
    r = one(runner, "contains(array[1, null], 2), "
                    "contains(array[1, null], 1), "
                    "contains(array[1, 2], 3)")
    assert r == (None, True, False)


def test_variadic_array_concat(runner):
    assert one(runner, "concat(array[1], array[2], array[3])") \
        == ([1, 2, 3],)


def test_map_duplicate_keys_error(runner):
    from presto_tpu.errors import QueryError
    with pytest.raises(QueryError, match="INVALID_FUNCTION_ARGUMENT"):
        runner.execute("select map(array[1, 1], array[10, 20])")


def test_element_at_index_zero_errors(runner):
    from presto_tpu.errors import QueryError
    with pytest.raises(QueryError, match="INVALID_FUNCTION_ARGUMENT"):
        runner.execute("select element_at(array[1, 2], 0)")


def test_distributed_unnest(mesh_runner):
    d = mesh_runner(tpch_sf=0.001, n_devices=8)
    rows = d.execute(
        "select sum(x) from nation, "
        "unnest(array[n_nationkey, n_regionkey]) as u(x)").rows
    assert rows == [(350,)]
