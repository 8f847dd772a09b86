#!/usr/bin/env python3
"""What a residual semi join's `keyed` probe costs the device, by how a
probe lane reads the payload of its one match.

`ops/join.keyed_match` looks a lane's key up in a direct table (one
int32 gather) and then reads the match's payload: two BIGINT columns
and their validity here, TPC-H Q21's (min, max) by order key, 2^20
probe lanes against a build of 2^24 lanes. Device ms a call (the mean
of 20 launches behind one `block_until_ready`) and ns a probe lane:

  lookup    the table's gather alone (`_point_lookup`)
  columns   + each column's data and validity gathered apart (what
            `lookup_join` does behind its permutation): four gathers
  packed    + ONE gather of a column of uint32 words from the
            [words, lanes] array `pack_sorted_payload` makes (kept)
  rows      + ONE gather of a row of the same words laid out
            [lanes, 8] (the minor dimension padded to 8 words)

    chiprun -- python3 tools/keyed_probe.py

On a CPU the numbers are the CPU's and say nothing of the chip."""
from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

import presto_tpu  # noqa: E402,F401  (64-bit types on before any array)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from presto_tpu import types as T  # noqa: E402
from presto_tpu.batch import Batch, Schema  # noqa: E402
from presto_tpu.ops import join as J  # noqa: E402

LAUNCHES = 20


def launch_ms(call):
    jax.block_until_ready(call())
    t = time.perf_counter()
    for _ in range(LAUNCHES):
        out = call()
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / LAUNCHES


def main(lanes: int = 1 << 20, build_lanes: int = 1 << 24) -> None:
    rng = np.random.default_rng(35)
    n_keys = build_lanes - build_lanes // 10
    keys = rng.permutation(build_lanes)[:n_keys] + 1
    lo = rng.integers(1, 100000, size=n_keys)
    build = Batch.from_arrays(
        Schema([("k", T.BIGINT), ("lo", T.BIGINT), ("hi", T.BIGINT)]),
        [keys, lo, lo + rng.integers(0, 3, size=n_keys)], num_rows=n_keys)
    probe = Batch.from_arrays(
        Schema([("k", T.BIGINT)]),
        [rng.integers(1, build_lanes + 1, size=lanes)], num_rows=lanes)
    prepared = jax.jit(lambda b: J.prepare_direct_keyed(
        b, [0], (1,), (build_lanes,), build_lanes, unique=True))(build)
    packed = jax.jit(lambda b, p: J.pack_sorted_payload(
        b, [1, 2], p, in_order=True))(build, prepared)
    rows = jnp.pad(packed, ((0, 8 - packed.shape[0]), (0, 0))).T
    jax.block_until_ready((prepared, packed, rows))

    def lookup(p, prep):
        q, valid = J._key_arrays(p, [0])
        pos, hit = J._point_lookup(q, prep)
        return pos, hit & valid & p.row_mask

    def columns(p, b, prep):
        pos, hit = lookup(p, prep)
        return [(jnp.take(c.data, pos), jnp.take(c.validity, pos) & hit)
                for c in b.columns[1:]]

    def by_rows(p, prep, r):
        pos, hit = lookup(p, prep)
        return jnp.take(r, pos, axis=0), hit
    forms = {
        "lookup": (jax.jit(lookup), (probe, prepared)),
        "columns": (jax.jit(columns), (probe, build, prepared)),
        "packed": (jax.jit(lambda p, b, prep, pk: J.keyed_match(
            p, b, [0], [1, 2], prep, pk)), (probe, build, prepared, packed)),
        "rows": (jax.jit(by_rows), (probe, prepared, rows)),
    }
    out = {"device": jax.devices()[0].device_kind, "lanes": lanes,
           "build_lanes": build_lanes}
    for name, (fn, args) in forms.items():
        ms = launch_ms(lambda: fn(*args))
        out[name] = {"ms": round(ms, 3), "ns_a_lane": round(1e6 * ms / lanes, 2)}
        print(name, out[name], flush=True)
    # the kept form against NumPy, lane for lane
    cols, match = forms["packed"][0](*forms["packed"][1])
    at = {int(k): i for i, k in enumerate(keys.tolist())} \
        if build_lanes <= (1 << 16) else None
    if at is not None:
        pk = np.asarray(probe.columns[0].data)[:lanes]
        want = np.array([k in at for k in pk.tolist()])
        assert (np.asarray(match)[:lanes] == want).all()
        got = np.asarray(cols[0].data)[:lanes][want]
        assert (got == lo[[at[k] for k in pk[want].tolist()]]).all()
        out["checked"] = True
    print(json.dumps(out))


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:]))
