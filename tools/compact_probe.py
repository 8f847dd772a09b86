#!/usr/bin/env python3
"""What a compaction costs the device, by how its indices are computed.

`Batch.compact` gathers a sparse batch's live rows to the front: it needs
the indices of the mask's first `cap` set lanes, `jnp.nonzero(mask,
size=cap, fill_value=capacity - 1)[0]`. This times the candidates for
that index computation over `capacity` 2^17..2^20 and `cap` 2^10..2^20:
device ms a call (the mean of 30 launches behind one
`block_until_ready`) and cold compile seconds (JAX's compile cache off),
each checked element for element against the first.

  nonzero   `jnp.nonzero(size=)` as JAX 0.9.0 has it: cumsum, a
            scatter-add (`bincount`), cumsum; int64 under `jax_enable_x64`
  search    `cumsum` (int32), then `searchsorted` of 1..cap in it:
            log2(capacity) gathers of cap lanes
  search2   the same over a two-level cumsum (rows of 1024, then the
            rows' totals): `jnp.cumsum` of 2^20 lanes compiles for ~19 s
  sort2     the first cap lanes of a stable `lax.sort((~mask, iota))`
  sort1     the first cap lanes of `sort(where(mask, iota, capacity))`:
            one operand, no stability needed
  shift     `batch.live_indices`, what `Batch.compact` runs since PR 30
            (it won at every shape): a compress network, every live
            lane moves left by the dead lanes before it (a two-level
            cumsum), by that distance's bits, lowest first, one
            elementwise pass over the lanes a bit: no scatter, gather
            or sort

Then, through the engine's own programs and over a batch shaped like
TPC-H Q6's filter output (one DATE, three DOUBLE columns), what the
`_compactor`'s two constants cost (`exec/local.py`: no batch of 2^17
lanes or fewer is looked at; one that does not shrink fourfold is left
as it is): `op_compact`, the liveness readback, and an ungrouped sum
(`op_global_aggregate`, partial) over the lanes before and after.

    chiprun -- python3 tools/compact_probe.py

The table is in PERF.md section 5 (PR 30). On a CPU the numbers are the
CPU's and say nothing of the chip."""
from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import presto_tpu  # noqa: E402  (64-bit types on before any array)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from presto_tpu.batch import _rows_cumsum, live_indices  # noqa: E402

LAUNCHES = 30


def _fit(idx, cap, capacity):
    idx = jnp.minimum(idx, capacity - 1)
    if cap <= capacity:
        return idx[:cap]
    return jnp.pad(idx, (0, cap - capacity), constant_values=capacity - 1)


def nonzero(mask, cap):
    return jnp.nonzero(mask, size=cap, fill_value=mask.shape[0] - 1)[0]


def _search(cs, cap, capacity):
    idx = jnp.searchsorted(cs, jnp.arange(1, cap + 1, dtype=jnp.int32),
                           side="left").astype(jnp.int32)
    return jnp.where(jnp.arange(cap, dtype=jnp.int32) < cs[-1], idx,
                     capacity - 1)


def search(mask, cap):
    return _search(jnp.cumsum(mask.astype(jnp.int32)), cap, mask.shape[0])


def search2(mask, cap):
    return _search(_rows_cumsum(mask.astype(jnp.int32)), cap, mask.shape[0])


def sort2(mask, cap):
    capacity = mask.shape[0]
    dead, idx = lax.sort((~mask, jnp.arange(capacity, dtype=jnp.int32)),
                         num_keys=1, is_stable=True)
    return _fit(jnp.where(dead, capacity - 1, idx), cap, capacity)


def sort1(mask, cap):
    capacity = mask.shape[0]
    iota = jnp.arange(capacity, dtype=jnp.int32)
    return _fit(jnp.sort(jnp.where(mask, iota, capacity)), cap, capacity)


def shift(mask, cap):
    """What `Batch.compact` runs."""
    return live_indices(mask, cap)[0]


CANDIDATES = (nonzero, search, search2, sort2, sort1, shift)
#: the slow compiles (a 1-D cumsum of 2^20 lanes) at these shapes only
FEW = {"nonzero", "search"}
FEW_SHAPES = {(17, 10), (18, 15), (20, 15), (20, 20)}


def launch_ms(call):
    """Mean ms of LAUNCHES calls behind one `block_until_ready`."""
    jax.block_until_ready(call())
    t = time.perf_counter()
    for _ in range(LAUNCHES):
        out = call()
    jax.block_until_ready(out)
    return round(1e3 * (time.perf_counter() - t) / LAUNCHES, 4)


def timed(fn, *args):
    """(device ms a call, cold compile seconds, the result)."""
    t = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t
    return launch_ms(lambda: exe(*args)), compile_s, exe(*args)


def index_table(shapes, live_share=0.019):
    rows = []
    rng = np.random.default_rng(30)
    for lc, lk in shapes:
        capacity, cap = 1 << lc, 1 << lk
        mask = jnp.asarray(rng.random(capacity) < min(
            live_share, 0.9 * cap / capacity))
        want = None
        for fn in CANDIDATES:
            if fn.__name__ in FEW and (lc, lk) not in FEW_SHAPES:
                continue
            ms, compile_s, got = timed(lambda m, _f=fn: _f(m, cap), mask)
            got = np.asarray(got)
            want = got if want is None else want
            row = {"capacity": f"2^{lc}", "cap": f"2^{lk}",
                   "variant": fn.__name__, "device_ms": ms,
                   "compile_s": round(compile_s, 2),
                   "equal": bool((got == want).all())}
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def constants_table():
    """`op_compact`, the readback and a sum downstream, at the
    `_compactor`'s floor and at its fourfold rule."""
    from presto_tpu import types as T
    from presto_tpu.batch import (
        Batch, Column, Field, Schema, bucket_capacity)
    from presto_tpu.ops.aggregation import AggSpec
    from presto_tpu.ops.jitcache import compact_jit, global_aggregate_jit

    schema = Schema([Field("l_shipdate", T.DATE),
                     Field("l_extendedprice", T.DOUBLE),
                     Field("l_discount", T.DOUBLE),
                     Field("l_quantity", T.DOUBLE)])
    aggs = (AggSpec("sum", 1, T.DOUBLE, "revenue"),)
    rng = np.random.default_rng(31)
    rows = []

    for lc, share in ((17, 0.019), (18, 0.019), (20, 0.019),
                      (18, 0.24), (20, 0.24), (20, 0.26)):
        capacity = 1 << lc
        valid = jnp.ones(capacity, bool)
        cols = [Column(T.DATE, jnp.asarray(
            rng.integers(8000, 10000, capacity), jnp.int32), valid, None)]
        cols += [Column(T.DOUBLE, jnp.asarray(rng.random(capacity)),
                        valid, None) for _ in range(3)]
        b = Batch(schema, cols, jnp.asarray(rng.random(capacity) < share))
        jax.block_until_ready(b)
        live = b.host_count()
        tgt = bucket_capacity(live)
        small = jax.block_until_ready(compact_jit(b, tgt))
        t = time.perf_counter()
        for _ in range(LAUNCHES):
            b.host_count("compaction-liveness")
        sync_ms = round(1e3 * (time.perf_counter() - t) / LAUNCHES, 4)
        row = {"capacity": f"2^{lc}", "live": live, "cap": tgt,
               "shrinks_4x": tgt * 4 <= capacity,
               "op_compact_ms": launch_ms(lambda: compact_jit(b, tgt)),
               "liveness_readback_ms": sync_ms,
               "sum_before_ms": launch_ms(
                   lambda: global_aggregate_jit(b, aggs, "partial")),
               "sum_after_ms": launch_ms(
                   lambda: global_aggregate_jit(small, aggs, "partial"))}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    print(f"[device] {dev.platform} {dev.device_kind}", flush=True)
    small = dev.platform == "cpu"      # a rehearsal: the shapes cut
    shapes = [(lc, lk) for lc in ((12, 13) if small else (17, 18, 19, 20))
              for lk in ((7, 10, 13) if small else (10, 15, 17, 20))
              if lk <= lc]
    if small:
        FEW_SHAPES.update(shapes)
    out = {"device": f"{dev.platform} {dev.device_kind}",
           "indices": index_table(shapes),
           "constants": constants_table()}
    os.makedirs(os.path.join(_REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_REPO, "chiprun_out", "compact_probe.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0 if all(r["equal"] for r in out["indices"]) else 1


if __name__ == "__main__":
    sys.exit(main())
