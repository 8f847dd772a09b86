#!/usr/bin/env python3
"""How far the device's DOUBLE division is from IEEE's, where the
engine divides: ``cast(decimal as double)`` is ``unscaled / 10**scale``
(``expr/functions.cast_val``).

    chiprun -- python3 tools/f64_divide_probe.py [ROOT OF ANOTHER CHECKOUT]

For the 10^4 unscaled values 0..9999 at scale 2 it counts the quotients
the device gives that differ from the host's (NumPy, correctly
rounded), and prints the first few, the widest relative gap, the same
for scales 1..6 over 0..99999, and whether a CPU device stands beside
the default one (the planner's constant fold evaluates there). Two
things differ on the TPU v5e, whose f64 is a pair of f32, and the probe
tells them apart: a DOUBLE that is only SENT to the device and read
back (``roundtrip_differ``: what the device can hold), and the device's
quotient against that round trip of the host's (``differ_from_held``:
the division's own error, which is what makes a quotient unequal to
the same value held in a column or a literal). On a CPU every count is
0 (PERF.md section 7)."""
import json
import os
import sys

import numpy as np

# the engine under the probe: this checkout's, or the one whose root
# is given (the parent commit's, unpacked beside it, for `engine_cast`)
ROOT = (sys.argv[1] if len(sys.argv) > 1 else
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import presto_tpu  # noqa: F401  (64-bit types on)
import jax
import jax.numpy as jnp

dev = jax.devices()[0]
out = {"engine": ROOT, "device": [dev.platform, dev.device_kind],
       "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
       "JAX_COMPILATION_CACHE_DIR":
           os.environ.get("JAX_COMPILATION_CACHE_DIR")}
try:
    out["cpu_device"] = str(jax.devices("cpu")[0])
except RuntimeError as e:
    out["cpu_device"] = f"none: {e}"


def differing(n: int, scale: int) -> dict:
    unscaled = np.arange(n, dtype=np.int64)
    want = unscaled.astype(np.float64) / (10.0 ** scale)
    got = np.asarray(jnp.asarray(unscaled).astype(jnp.float64)
                     / (10.0 ** scale))
    held = np.asarray(jnp.asarray(want))     # sent and read back
    bad = np.nonzero(got != want)[0]
    rel = np.abs(got[bad] - want[bad]) / np.abs(want[bad])
    return {"values": n, "scale": scale, "differ": int(bad.size),
            "roundtrip_differ": int((held != want).sum()),
            "differ_from_held": int((got != held).sum()),
            "below_held": int((got < held).sum()),
            "above_held": int((got > held).sum()),
            "first": [[int(i), repr(float(got[i])), repr(float(want[i]))]
                      for i in bad[:5]],
            "widest_rel_gap": float(rel.max()) if bad.size else 0.0}


out["scale2_0_9999"] = differing(10_000, 2)
# TPC-H Q6's bounds, 0.01..0.10: [k, the device's quotient, k/100 held]
k = np.arange(1, 11, dtype=np.int64)
out["q6_bounds"] = [
    [int(i), repr(float(q)), repr(float(h))] for i, q, h in zip(
        k, np.asarray(jnp.asarray(k).astype(jnp.float64) / 100.0),
        np.asarray(jnp.asarray(k / 100.0)))]
out["scales_0_99999"] = [differing(100_000, s) for s in range(1, 7)]


def engine_cast(n: int, scale: int) -> dict:
    """The same count through the engine's own program for
    ``cast(decimal COLUMN as double)`` (a jitted projection, where the
    divisor is a constant of the program)."""
    from presto_tpu import types as T
    from presto_tpu.batch import Batch, Schema
    from presto_tpu.expr import compiler, ir
    unscaled = np.arange(n, dtype=np.int64)
    typ = T.DecimalType(18, scale)
    batch = Batch.from_arrays(Schema([("d", typ)]), [unscaled])
    fn = compiler.ExprCompiler().projection(
        [ir.cast(ir.InputRef(type=typ, index=0), T.DOUBLE)], ["c"],
        batch.schema)
    got = np.asarray(fn(batch).columns[0].data)[:n]
    want = unscaled / 10.0 ** scale
    held = np.asarray(jnp.asarray(want))
    return {"values": n, "scale": scale,
            "differ": int((got != want).sum()),
            "differ_from_held": int((got != held).sum()),
            "below_held": int((got < held).sum()),
            "above_held": int((got > held).sum())}


out["engine_cast"] = [engine_cast(10_000, s) for s in (1, 2, 3, 4)]
# the multiply by a reciprocal, for comparison: not correctly rounded
# anywhere, so no cure
u = np.arange(10_000, dtype=np.int64)
got = np.asarray(jnp.asarray(u).astype(jnp.float64) * (1.0 / 100.0))
out["times_reciprocal_differ"] = int((got != u / 100.0).sum())
out["times_reciprocal_differ_from_held"] = int(
    (got != np.asarray(jnp.asarray(u / 100.0))).sum())
# one residual step, q + (x - q*d)/d: a cure only if it reads 0
x = jnp.asarray(u).astype(jnp.float64)
q = x / 100.0
got = np.asarray(q + (x - q * 100.0) / 100.0)
out["residual_step_differ"] = int((got != u / 100.0).sum())
out["residual_step_differ_from_held"] = int(
    (got != np.asarray(jnp.asarray(u / 100.0))).sum())
print(json.dumps(out))
