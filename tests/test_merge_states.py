"""``ops.aggregation.merge_states`` (ISSUE 38): two normalized states
whose key ranges do not overlap are appended, any other pair goes
through the merge network; either way the rows are those of
``grouped_aggregate(concat, mode="merge")`` and a flag says which ran."""

import numpy as np
import pytest

import jax

from presto_tpu import types as T
from presto_tpu.batch import Batch, Schema, concat_batches
from presto_tpu.exec.spill import AggSpillBuffer
from presto_tpu.memory import QueryMemoryPool
from presto_tpu.obs.metrics import REGISTRY
from presto_tpu.ops.aggregation import (
    AggSpec, grouped_aggregate, merge_states)
from presto_tpu.ops.jitcache import merge_states_jit

SUM = (AggSpec("sum", 1, T.BIGINT, "s"),)
#: TPC-H Q18's subquery state: an order key, a DOUBLE sum and its count
Q18 = (AggSpec("sum", 1, T.DOUBLE, "q"), AggSpec("count_star", None,
                                                 T.BIGINT, "n"))
#: TPC-H Q21's summaries: min and max of a supplier key, a count each
Q21 = (AggSpec("min", 1, T.BIGINT, "lo"), AggSpec("max", 1, T.BIGINT, "hi"))

N = None   # a NULL, in the cases below


def _partial(keys, aggs, n_keys=1, values=None, value_type=T.BIGINT,
             capacity=64):
    """The sort path's partial state over rows of ``keys`` (a list a
    key column; None a NULL) and one value column."""
    keys = [list(k) for k in keys]
    n = len(keys[0])
    values = list(range(1, n + 1)) if values is None else list(values)
    cols = keys + [values]
    arrays = [np.array([0 if v is None else v for v in c]) for c in cols]
    valid = [np.array([v is not None for v in c], bool) for c in cols]
    schema = Schema([(f"k{j}", T.BIGINT) for j in range(n_keys)]
                    + [("v", value_type)])
    aggs = tuple(AggSpec(a.fn, None if a.input is None else n_keys,
                         a.output_type, a.name) for a in aggs)
    batch = Batch.from_arrays(schema, arrays, validity=valid, num_rows=n,
                              capacity=capacity)
    return grouped_aggregate(batch, list(range(n_keys)), aggs,
                             mode="partial"), aggs


CASES = {
    # name: (a's key columns, b's, aggs, value type, a's values, b's, flag)
    "a_before_b": ([[1, 2, 3]], [[4, 5, 5, 9]], SUM, T.BIGINT, None, None, 1),
    "b_before_a": ([[7, 8, 8]], [[1, 2, 6]], SUM, T.BIGINT, None, None, 1),
    "equal_seam_key_combines": ([[1, 2, 3]], [[3, 4]], SUM, T.BIGINT,
                                None, None, 0),
    "interleaved": ([[1, 3, 5, 7]], [[2, 4, 6]], SUM, T.BIGINT,
                    None, None, 0),
    "nested": ([[1, 9]], [[3, 4, 5]], SUM, T.BIGINT, None, None, 0),
    "a_empty": ([[]], [[2, 4, 6]], SUM, T.BIGINT, None, None, 1),
    "b_empty": ([[2, 4, 6]], [[]], SUM, T.BIGINT, None, None, 1),
    "both_empty": ([[]], [[]], SUM, T.BIGINT, None, None, 1),
    # a NULL key's group stands behind every other key
    "null_key_group_last": ([[1, 2]], [[3, N, N]], SUM, T.BIGINT,
                            None, None, 1),
    "null_key_group_over_the_other": ([[1, N]], [[2, 3]], SUM, T.BIGINT,
                                      None, None, 0),
    "null_key_in_both": ([[1, N]], [[N, N]], SUM, T.BIGINT, None, None, 0),
    "two_keys_disjoint": ([[1, 1, 2], [5, 9, 0]], [[2, 2, 3], [1, 7, 0]],
                          SUM, T.BIGINT, None, None, 1),
    "two_keys_seam_in_the_second": ([[1, 1, 2], [5, 9, 3]],
                                    [[2, 2, 3], [1, 7, 0]], SUM, T.BIGINT,
                                    None, None, 0),
    "two_keys_equal_seam": ([[1, 2], [5, 3]], [[2, 3], [3, 0]], SUM,
                            T.BIGINT, None, None, 0),
    "q18_state_appended": ([[1, 1, 2, 3]], [[4, 4, 4, 6]], Q18, T.DOUBLE,
                           [0.1, 0.2, 0.3, 44.25], [0.125, 2.5, N, 7.0], 1),
    "q18_state_merged": ([[1, 1, 2, 4]], [[4, 4, 4, 6]], Q18, T.DOUBLE,
                         [0.1, 0.2, 0.3, 0.7], [0.1, 2.5, N, 7.0], 0),
    # a group of NULL inputs only: its min and max are NULL (count 0)
    "q21_state_appended": ([[1, 1, 2]], [[3, 5, 5]], Q21, T.BIGINT,
                           [7, 3, N], [N, 9, -4], 1),
    "q21_state_merged": ([[1, 1, 3]], [[3, 5, 5]], Q21, T.BIGINT,
                         [7, 3, N], [N, 9, -4], 0),
}


def _assert_same_state(got: Batch, want: Batch):
    """Leaf for leaf: the mask and every validity whole, the data on the
    live lanes (what stands behind them is not defined)."""
    assert got.schema.names == want.schema.names
    assert got.capacity == want.capacity
    live = np.asarray(want.row_mask)
    np.testing.assert_array_equal(np.asarray(got.row_mask), live)
    for g, w in zip(got.columns, want.columns):
        assert g.type == w.type and g.data.dtype == w.data.dtype
        np.testing.assert_array_equal(np.asarray(g.validity),
                                      np.asarray(w.validity))
        np.testing.assert_array_equal(np.asarray(g.data)[live],
                                      np.asarray(w.data)[live])


@pytest.mark.parametrize("case", sorted(CASES))
def test_merge_states_equals_the_group_by_over_the_concatenation(case):
    keys_a, keys_b, aggs, vtype, vals_a, vals_b, flag = CASES[case]
    n_keys = len(keys_a)
    a, state_aggs = _partial(keys_a, aggs, n_keys, vals_a, vtype)
    b, _ = _partial(keys_b, aggs, n_keys, vals_b, vtype)
    got, appended = merge_states_jit(a, b, n_keys, state_aggs)
    want = grouped_aggregate(concat_batches([a, b]), list(range(n_keys)),
                             state_aggs, mode="merge")
    assert int(appended) == flag
    _assert_same_state(got, want)
    # and the other way round: the same rows, the same branch
    got, appended = merge_states_jit(b, a, n_keys, state_aggs)
    assert int(appended) == flag
    _assert_same_state(got, want)


def test_the_branch_is_chosen_inside_one_program():
    """One conditional, no readback: the flag is a traced value."""
    a, aggs = _partial([[1, 2]], SUM)
    b, _ = _partial([[3, 4]], SUM)
    jaxpr = jax.make_jaxpr(lambda x, y: merge_states(x, y, 1, aggs))(a, b)
    assert sum(e.primitive.name == "cond" for e in jaxpr.eqns) == 1


def _ordered_partials(n, groups=4000, capacity=4096):
    """``n`` partial states of ``groups`` order keys each, one after the
    other in the key's order (a table clustered by its key)."""
    out = []
    for i in range(n):
        keys = np.arange(i * groups, (i + 1) * groups)
        p, aggs = _partial([keys], Q18, 1, keys * 0.25 + i, T.DOUBLE,
                           capacity=capacity)
        out.append(p)
    return out, aggs


def _through_the_buffer(partials, aggs):
    counts = {how: REGISTRY.value(f"agg_merge_selected_total.{how}")
              for how in ("append", "network")}
    syncs = REGISTRY.value("device_sync_total.agg-state-groups")
    buf = AggSpillBuffer(QueryMemoryPool(), "agg", [0], aggs, 4)
    try:
        for p in partials:
            buf.add_partial(p, unique=True, normalized=True)
        rows = [r for b in buf.results() for r in b.to_pylist()]
    finally:
        buf.close()
    return (sorted(rows),
            {how: REGISTRY.value(f"agg_merge_selected_total.{how}") - was
             for how, was in counts.items()},
            REGISTRY.value("device_sync_total.agg-state-groups") - syncs)


def test_eight_ordered_partials_are_appended_seven_times():
    """Through ``AggSpillBuffer``: partials that arrive in the key's
    order never reach the network, the same partials out of order do,
    and the rows are the same; the flag costs no readback of its own."""
    partials, aggs = _ordered_partials(8)
    rows, selected, syncs = _through_the_buffer(partials, aggs)
    assert selected == {"append": 7, "network": 0}
    assert len(rows) == 8 * 4000
    # (0,4) (1,5) (2,6) (3,7) are disjoint pairs; what they merge into
    # overlaps
    shuffled = [partials[i] for i in (0, 4, 1, 5, 2, 6, 3, 7)]
    rows2, selected2, syncs2 = _through_the_buffer(shuffled, aggs)
    assert selected2 == {"append": 4, "network": 3}
    assert rows2 == rows
    assert syncs2 == syncs
