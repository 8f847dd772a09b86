#!/usr/bin/env python3
"""What a merge of two grouped states costs the device, by what the
program finds in them.

`exec/spill.py` `AggSpillBuffer` keeps a grouped aggregation's state as
a binary counter: two normalized states of one capacity become one of
twice the capacity in `jit_op_grouped_aggregate_merge`
(`ops/aggregation.merge_states`). The program looks at the two states'
first and last keys: where one ends before the other begins it writes
the later behind the earlier (`append`), else a bitonic merge network,
the pair reducers and a compress network run (`network`). This times
both through the engine's own program, at the six capacities a side
that TPC-H Q18's counter uses at SF10 (2^18 to 2^23) and over the two
states the benchmark's cells carry:

  q18   key BIGINT, sum DOUBLE and its count (three columns)
  q21   key BIGINT, min and max BIGINT, a count each (five columns)

`append`: side a holds the keys below side b's (the partials of an
input clustered by the key); `network`: a the even keys and b the odd
ones. Both sides 63/64 live, as Q18's partials are. Device ms a call is
the mean of LAUNCHES launches behind one `block_until_ready` (under a
millisecond it is the host's launch, not the device: the small
appends); the first call of a capacity holds the program's compile
(JAX's compile cache off; both inputs run the SAME program). Each
call's flag and live count are checked.

    chiprun -- python3 tools/merge_probe.py

The table is in PERF.md section 5 (PR 38). On a CPU the numbers are the
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

from presto_tpu import types as T  # noqa: E402
from presto_tpu.batch import Batch, Column, Schema  # noqa: E402
from presto_tpu.ops.aggregation import AggSpec  # noqa: E402
from presto_tpu.ops.jitcache import merge_states_jit  # noqa: E402

LAUNCHES = 10

STATES = {
    "q18": (AggSpec("sum", 1, T.DOUBLE, "s"),),
    "q21": (AggSpec("min", 1, T.BIGINT, "lo"),
            AggSpec("max", 1, T.BIGINT, "hi")),
}


def state_of(keys: jax.Array, live: int, aggs) -> Batch:
    """A normalized state of one BIGINT key: ``keys`` ascending, the
    first ``live`` lanes live, every state column a function of the
    key."""
    mask = jnp.arange(keys.shape[0]) < live
    fields, cols = [("k", T.BIGINT)], [Column(T.BIGINT, keys, mask, None)]
    for agg in aggs:
        for name, typ in agg.state_types():
            data = (jnp.ones_like(keys) if name.endswith("$cnt")
                    else keys.astype(typ.storage_dtype))
            fields.append((name, typ))
            cols.append(Column(typ, data, mask, None))
    return Batch(Schema(fields), cols, mask)


def launch_ms(call):
    """Mean ms of LAUNCHES calls behind one `block_until_ready`."""
    jax.block_until_ready(call())
    t = time.perf_counter()
    for _ in range(LAUNCHES):
        out = call()
    jax.block_until_ready(out)
    return round(1e3 * (time.perf_counter() - t) / LAUNCHES, 4)


def table(log2_sides):
    rows = []
    for which, aggs in STATES.items():
        for lc in log2_sides:
            cap = 1 << lc
            live = cap - cap // 64
            lane = jnp.arange(cap, dtype=jnp.int64)
            sides = {"append": (lane, lane + cap),
                     "network": (2 * lane, 2 * lane + 1)}
            for how, (ka, kb) in sides.items():
                a, b = (jax.block_until_ready(state_of(k, live, aggs))
                        for k in (ka, kb))
                t = time.perf_counter()
                out, flag = jax.block_until_ready(
                    merge_states_jit(a, b, 1, aggs))
                first_s = time.perf_counter() - t
                flag, groups = int(flag), out.host_count()
                row = {"state": which, "columns": len(out.columns),
                       "side": f"2^{lc}", "input": how,
                       "device_ms": launch_ms(
                           lambda: merge_states_jit(a, b, 1, aggs)),
                       "first_call_s": round(first_s, 2),
                       "flag": flag, "groups": groups,
                       "ok": (flag == (how == "append")
                              and groups == 2 * live)}
                rows.append(row)
                print(json.dumps(row), flush=True)
                del a, b, out
    return rows


def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    print(f"[device] {dev.platform} {dev.device_kind}", flush=True)
    small = dev.platform == "cpu"      # a rehearsal: the shapes cut
    out = {"device": f"{dev.platform} {dev.device_kind}",
           "merges": table((8, 10) if small else range(18, 24))}
    os.makedirs(os.path.join(_REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_REPO, "chiprun_out", "merge_probe.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0 if all(r["ok"] for r in out["merges"]) else 1


if __name__ == "__main__":
    sys.exit(main())
