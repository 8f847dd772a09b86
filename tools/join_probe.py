#!/usr/bin/env python3
"""What a join's point lookup costs the device, by how a probe lane
learns whether its key is in the build (and where).

`ops/join._point_lookup` answers `(pos, hit)` for every probe lane. This
times its forms over 2^20 and 2^21 probe lanes, one BIGINT key and a
two-key tuple: device ms a call (the mean of 30 launches, of `search`
3, behind one `block_until_ready`) and cold compile seconds (JAX's
compile cache off), each membership checked lane for lane against
NumPy.

  gather2   the parent's: `_range_lookup` into a direct table, a gather
            from lo_table AND one from cnt_table, `hit = hi > lo`
  gather1   `_point_lookup` since PR 34: lo_table alone, `hit = lo < n`
            (both over tables of 2^17 and of 2^24 slots)
  compare   `_compare_all` over a sorted build of n = 128, 512, 2048,
            8192 keys: every lane against every key, no gather
            (`member`: the hit alone, a semi join's mask; `lookup`: pos
            and hit, a lookup join's)
  search    `_lex_searchsorted` over the same builds: log2(n) + 1
            rounds of a gather a key column, then one more to compare

`COMPARE_ALL_LIMIT` is the largest n at which `compare` is at least
twice as fast as `gather1`; its comment quotes this table.

The second table (`payload`, PR 36) times what follows the lookup: the
read of a build's payload columns at the matched positions
(`ops/join.read_payload`), in its two forms, over probes of 2^15, 2^18
and 2^20 lanes against builds of 2^17, 2^21 and 2^23 lanes, one and
four BIGINT columns, every cell read checked against NumPy:

  permuted  each column's data and validity gathered through the whole
            permutation (BUILD size), then read at the positions
  composed  the permutation gathered at the positions (probe size), then
            data and validity read from the build as it stands

`ops/join.payload_form` picks between them by gather count; its comment
quotes this table.

    chiprun -- python3 tools/join_probe.py            # both tables
    chiprun -- python3 tools/join_probe.py payload    # or `lookup`: one

The tables are in PERF.md section 5 (PRs 34, 36). On a CPU the numbers
are the CPU's and say nothing of the chip."""
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
from presto_tpu.batch import Batch, Column, Field, Schema  # noqa: E402
from presto_tpu.ops import join as J  # noqa: E402

LAUNCHES = 30
#: `search` takes 0.15 to 0.94 s a call on the v5e: with 30 launches of
#: each of its 16 shapes the table took 11 chip-minutes (PR 34)
SLOW_LAUNCHES = 3


def launch_ms(call, launches):
    """Mean ms of `launches` calls behind one `block_until_ready`."""
    jax.block_until_ready(call())
    t = time.perf_counter()
    for _ in range(launches):
        out = call()
    jax.block_until_ready(out)
    return round(1e3 * (time.perf_counter() - t) / launches, 4)


def timed(fn, *args):
    """(device ms a call, cold compile seconds, the result)."""
    t = time.perf_counter()
    exe = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t
    launches = SLOW_LAUNCHES if fn is search else LAUNCHES
    return launch_ms(lambda: exe(*args), launches), compile_s, exe(*args)


def _batch(cols):
    n = cols[0].shape[0]
    valid = jnp.ones(n, bool)
    return Batch(Schema([Field(f"k{i}", T.BIGINT)
                         for i in range(len(cols))]),
                 [Column(T.BIGINT, jnp.asarray(c, jnp.int64), valid, None)
                  for c in cols], valid)


def _codes(cols, sizes):
    code = np.zeros(cols[0].shape, np.int64)
    for c, size in zip(cols, sizes):
        code = code * size + c
    return code


def gather2(q_ops, prepared):
    lo, hi = J._range_lookup(q_ops, prepared)
    return hi > lo


def gather1(q_ops, prepared):
    return J._point_lookup(q_ops, prepared)[1]


def compare_member(q_ops, prepared):
    return J._compare_all(prepared[0], prepared[1], q_ops)[1] > 0


def compare_lookup(q_ops, prepared):
    lo, cnt = J._compare_all(prepared[0], prepared[1], q_ops)
    return jnp.minimum(lo, prepared[0][0].shape[0] - 1), cnt > 0


def search(q_ops, prepared):
    s_ops, slive, _ = prepared
    pos = jnp.minimum(J._lex_searchsorted(s_ops, q_ops, side="left"),
                      s_ops[0].shape[0] - 1)
    return J._tuple_eq(s_ops, q_ops, pos) & jnp.take(slive, pos, axis=0)


def table(lane_logs, slot_logs, ns):
    rows = []
    rng = np.random.default_rng(34)

    def note(row, fn, q_ops, prepared, want):
        ms, compile_s, got = timed(fn, q_ops, prepared)
        hit = np.asarray(got[1] if isinstance(got, tuple) else got)
        row = dict(row, variant=fn.__name__, device_ms=ms,
                   ns_a_lane=round(1e6 * ms / q_ops[0].shape[0], 3),
                   compile_s=round(compile_s, 2),
                   equal=bool((hit == want).all()))
        rows.append(row)
        print(json.dumps(row), flush=True)

    for arity in (1, 2):
        for ls in slot_logs:
            # the key domain IS the table: [0, 2^ls) in one key, or two
            # keys of half the bits each
            sizes = ((1 << ls,) if arity == 1
                     else (1 << (ls - ls // 2), 1 << (ls // 2)))
            n_build = 1 << min(ls - 1, 20)
            bcols = [rng.integers(0, s, n_build) for s in sizes]
            build = _batch(bcols)
            keys = tuple(range(arity))
            prepared = (
                J.prepare_direct(build, keys, 0, sizes[0]) if arity == 1
                else J.prepare_direct_keyed(build, keys, (0,) * arity,
                                            sizes, 1 << ls))
            jax.block_until_ready(prepared)
            members = np.unique(_codes(bcols, sizes))
            for ll in lane_logs:
                qcols = [rng.integers(0, s, 1 << ll) for s in sizes]
                q_ops = [jnp.asarray(c, jnp.int64) for c in qcols]
                want = np.isin(_codes(qcols, sizes), members)
                row = {"arity": arity, "lanes": f"2^{ll}",
                       "build": f"2^{ls} slots"}
                for fn in (gather2, gather1):
                    note(row, fn, q_ops, prepared, want)
            del prepared
        for n in ns:
            # n distinct keys drawn from a domain 64 times as wide, so
            # one lane in 64 hits
            sizes = (n * 64,) if arity == 1 else (n, 64)
            code = rng.choice(n * 64, n, replace=False)
            bcols = [code] if arity == 1 else [code // 64, code % 64]
            prepared = J.build_sorted(_batch(bcols), tuple(range(arity)))
            jax.block_until_ready(prepared)
            for ll in lane_logs:
                qcols = [rng.integers(0, s, 1 << ll) for s in sizes]
                q_ops = [jnp.asarray(c, jnp.int64) for c in qcols]
                want = np.isin(_codes(qcols, sizes), code)
                row = {"arity": arity, "lanes": f"2^{ll}",
                       "build": f"{n} keys"}
                for fn in (compare_member, compare_lookup, search):
                    note(row, fn, q_ops, prepared, want)
    return rows


def payload_table(lane_logs, build_logs, widths):
    """ms a call and ns a probe lane of `ops/join.read_payload` in each
    form, whatever `payload_form` would pick at the shape (`picked`)."""
    rows = []
    rng = np.random.default_rng(36)
    rule = J.payload_form
    for bl in build_logs:
        n = 1 << bl
        perm_h = rng.permutation(n).astype(np.int32)
        perm = jnp.asarray(perm_h)
        for width in widths:
            data = rng.integers(-2**40, 2**40, (width, n))
            valid = rng.random((width, n)) < 0.9
            build = Batch(
                Schema([Field(f"v{i}", T.BIGINT) for i in range(width)]),
                [Column(T.BIGINT, jnp.asarray(d), jnp.asarray(v), None)
                 for d, v in zip(data, valid)], jnp.ones(n, bool))
            payload = tuple(range(width))
            for ll in lane_logs:
                pos_h = rng.integers(0, n, 1 << ll).astype(np.int32)
                pos = jnp.asarray(pos_h)
                at = perm_h[pos_h]
                picked = rule(1 << ll, n, width)
                for form in ("permuted", "composed"):
                    # a function a form: jit's trace cache knows nothing
                    # of the rule swapped under it
                    def read(build, perm, pos):
                        return J.read_payload(build, payload, perm, pos)

                    J.payload_form = lambda *_: form    # read at the trace
                    try:
                        t = time.perf_counter()
                        exe = jax.jit(read).lower(build, perm, pos).compile()
                        compile_s = time.perf_counter() - t
                    finally:
                        J.payload_form = rule
                    ms = launch_ms(lambda: exe(build, perm, pos),
                                   SLOW_LAUNCHES if form == "permuted"
                                   and bl > 21 else LAUNCHES)
                    got = exe(build, perm, pos)
                    equal = all(
                        (np.asarray(d) == data[i][at]).all()
                        and (np.asarray(v) == valid[i][at]).all()
                        for i, (d, v) in enumerate(got))
                    row = {"lanes": f"2^{ll}", "build": f"2^{bl}",
                           "columns": width, "variant": form,
                           "picked": picked, "device_ms": ms,
                           "ns_a_lane": round(1e6 * ms / (1 << ll), 3),
                           "compile_s": round(compile_s, 2),
                           "equal": bool(equal)}
                    rows.append(row)
                    print(json.dumps(row), flush=True)
            del build
    return rows


def main() -> int:
    jax.config.update("jax_enable_compilation_cache", False)
    dev = jax.devices()[0]
    print(f"[device] {dev.platform} {dev.device_kind}", flush=True)
    small = dev.platform == "cpu"      # a rehearsal: the shapes cut
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    out = {"device": f"{dev.platform} {dev.device_kind}",
           "limit": J.COMPARE_ALL_LIMIT}
    if which in ("all", "lookup"):
        out["lookups"] = (
            table((10, 11), (8, 10), (128, 256)) if small
            else table((20, 21), (17, 24), (128, 512, 2048, 8192)))
    if which in ("all", "payload"):
        out["payloads"] = (
            payload_table((8, 11), (9, 12), (1, 4)) if small
            else payload_table((15, 18, 20), (17, 21, 23), (1, 4)))
    rows = out.get("lookups", []) + out.get("payloads", [])
    os.makedirs(os.path.join(_REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_REPO, "chiprun_out", "join_probe.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0 if rows and all(r["equal"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
