"""Prefix-sum helper that sidesteps XLA's cumsum compile blowup.

``jnp.cumsum`` lowers through reduce-window, whose compile time explodes
with array size on both backends used here (measured: 252s to compile a
single f64 cumsum at 2^17 on XLA:CPU; 528s cold for i64 at 2^26 on the
TPU backend — docs/perf.md). ``jax.lax.associative_scan`` lowers to a
O(log n) slice/add ladder instead and compiles in seconds at the same
shapes, with identical results for integer dtypes (integer addition is
associative) and a reassociated-but-order-independent sum for floats —
SQL aggregate semantics define no evaluation order, and every consumer
here (group ids, run boundaries, window running sums, coverage counts)
either uses integers or tolerates float reassociation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


#: lanes a row of the two-level form; a 1-D scan this long or longer is
#: split. Compiled for a described v5e at 2^20 lanes (PR 33): one ladder
#: ~100 s, an int64 or a float64 alike; rows of 1024 and then their
#: totals 2 to 3 s, where a float's rows take the ladder (jnp.cumsum:
#: 156 s) and an integer's jnp.cumsum (the ladder: 101 s)
_ROW = 1024


def prefix_sum(x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Inclusive prefix sum along ``axis`` (drop-in for jnp.cumsum). A
    long 1-D array is summed in two levels, along rows of 1024 lanes and
    then over the rows' totals: the same numbers for integers, another
    association for floats."""
    if x.ndim == 1 and x.shape[0] >= 2 * _ROW and x.shape[0] % _ROW == 0:
        rows = x.reshape(-1, _ROW)
        within = (jnp.cumsum(rows, axis=1)
                  if jnp.issubdtype(x.dtype, jnp.integer)
                  else jax.lax.associative_scan(jnp.add, rows, axis=1))
        total = within[:, -1]
        return (within + (prefix_sum(total) - total)[:, None]).reshape(-1)
    return jax.lax.associative_scan(jnp.add, x, axis=axis)
