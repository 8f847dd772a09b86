"""Pallas TPU scan kernels: sequential-carry cumsum and sorted-run
segment sums.

Why these exist (measured on the v5e, see docs/perf.md):

- ``jax.ops.segment_sum`` over 64-bit elements runs ~8M rows/s on this
  chip (i64 and f64 are both double-wide emulations, and the scatter
  falls off the 32-bit fast path), while i32 scans stream at
  ~690M rows/s. The sort-path group-by (ops/aggregation.py) produces
  group ids as SORTED RUNS, where a segment sum needs no scatter at
  all: one inclusive prefix sum + one gather of per-group boundary
  differences.
- XLA's big-array cumsum lowering also compiles slowly as shapes grow
  (measured 9.9s at 2^26 i32 vs 5.1s for this kernel, and minutes for
  64-bit variants); the Pallas grid re-uses one tile-sized program.

Constraint that shapes this file: Mosaic lowers no 64-bit vector types
("64-bit types are not supported"), so no 64-bit array crosses a
pallas_call boundary. 64-bit segment sums therefore decompose into base-2^w
i32 digit planes OUTSIDE the kernel: i32 prefix sums wrap mod 2^32,
but differences of wrapped prefixes are exact modulo 2^32, so choosing
w with ``w + ceil(log2(max_rows_per_group)) <= 31`` makes every
per-group digit sum exactly recoverable — the same digit algebra as
ops/scatter_agg.py, with the scatter replaced by a linear scan.

The hash-table role: this is the engine's answer to the reference's
MultiChannelGroupByHash/PagesHash hot loops (reference
presto-main/.../operator/MultiChannelGroupByHash.java:1) — on TPU the
"hash table" is sort + segmented reduction, and this kernel is the
reduction's fast path.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

R, L = 64, 128           # grid tile: 64 sublanes x 128 lanes = 8192 rows
TILE = R * L


def _scan_tile(t):
    """Inclusive row-major prefix sum over one [R, L] tile: log-step
    lane scan, then a log-step cross-row scan of row totals (full-width
    operands — width-1 sublane vectors hit Mosaic layout bugs)."""
    for k in (1, 2, 4, 8, 16, 32, 64):
        sh = jnp.concatenate(
            [jnp.zeros((R, k), t.dtype), t[:, :L - k]], axis=1)
        t = t + sh
    rt = jnp.broadcast_to(t[:, L - 1:L], (R, L))
    acc = rt
    k = 1
    while k < R:
        sh = jnp.concatenate(
            [jnp.zeros((k, L), t.dtype), acc[:R - k]], axis=0)
        acc = acc + sh
        k *= 2
    return t + (acc - rt), acc[R - 1:R, 0:1]


def _cumsum_kernel(x_ref, out_ref, carry_ref):
    from jax.experimental import pallas as pl
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        carry_ref[:, :] = jnp.zeros((1, 1), x_ref.dtype)

    t, total = _scan_tile(x_ref[:])
    out_ref[:] = t + carry_ref[0:1, 0:1]
    carry_ref[:, :] = carry_ref[0:1, 0:1] + total


def _imap(i):
    # jax_enable_x64 would make literal indices i64, which Mosaic
    # rejects at func.return — pin them to i32
    return (jnp.asarray(i, jnp.int32), jnp.int32(0))


def _cumsum_tiled(x2d: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    # deliberately NOT jitted here: every engine call site reaches this
    # inside an already-jitted kernel (ops/aggregation.py group-by),
    # where an inner jax.jit is inlined anyway — a raw jit wrapper
    # would only create an executable invisible to ops/jitcache
    # (tracing/raw-jit) for the eager test-only path
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n = x2d.shape[0] // R
    return pl.pallas_call(
        _cumsum_kernel,
        grid=(n,),
        in_specs=[pl.BlockSpec((R, L), _imap)],
        out_specs=pl.BlockSpec((R, L), _imap),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        scratch_shapes=[pltpu.VMEM((1, 1), x2d.dtype)],
        interpret=interpret,
    )(x2d)


#: tests set this to exercise the scan paths on the CPU mesh (pallas
#: runs in interpret mode there); engine call sites otherwise use the
#: scan paths only on real TPU backends
FORCE_SCAN_PATHS = False


def pallas_supported() -> bool:
    """The kernels run on real TPU backends; the CPU test mesh uses the
    interpret path only when explicitly requested (tests), and engine
    call sites fall back to XLA primitives."""
    return FORCE_SCAN_PATHS or jax.default_backend() not in ("cpu",)


def _interpret() -> bool:
    return jax.default_backend() in ("cpu",)


def cumsum_i32(x: jnp.ndarray,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Inclusive prefix sum of a 1-D i32 array (wraps mod 2^32 like any
    i32 sum). Pads to a tile multiple internally."""
    if interpret is None:
        interpret = _interpret()
    n = x.shape[0]
    pad = (-n) % TILE
    if pad:
        x = jnp.concatenate([x, jnp.zeros(pad, jnp.int32)])
    out = _cumsum_tiled(x.reshape(-1, L), interpret=interpret)
    return out.reshape(-1)[:n]


def _digit_plan(max_rows_per_group: int, bits: int = 64):
    """(width, n_digits): per-group digit sums stay within 31 bits so
    wrapped-prefix differences recover them exactly."""
    w = max(31 - max(int(math.ceil(math.log2(max(max_rows_per_group, 2)))),
                     1), 1)
    return w, int(math.ceil(bits / w))


def segment_sum_sorted_i64(
    values: jnp.ndarray,
    starts: jnp.ndarray,
    num_segments: int,
    max_rows_per_group: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Exact i64 segment sums when segment members are CONTIGUOUS RUNS
    (ids sorted ascending; dead rows must carry zero values).

    ``starts[g]`` is the row index of segment g's first row; ABSENT
    segments must carry ``starts[g] == n`` (one past the end) so the
    preceding live segment's run extends to the array end (their own
    results are garbage and callers mask them by the segment liveness
    they already track).
    """
    n = values.shape[0]
    cap = num_segments
    w, nd = _digit_plan(max_rows_per_group or n)
    mask = jnp.int64((1 << w) - 1)
    # prefix[g] = csum at the row BEFORE segment g's start
    prev = jnp.clip(starts - 1, 0, n - 1)
    at_zero = starts <= 0
    ends = jnp.concatenate(
        [jnp.clip(starts[1:] - 1, 0, n - 1),
         jnp.full((1,), n - 1, starts.dtype)])
    total = jnp.zeros(cap, dtype=jnp.int64)
    for d in range(nd):
        digit = ((values >> jnp.int64(d * w)) & mask).astype(jnp.int32)
        csum = cumsum_i32(digit, interpret=interpret)
        hi = jnp.take(csum, ends, axis=0)
        lo = jnp.where(at_zero, 0, jnp.take(csum, prev, axis=0))
        dsum = (hi - lo).astype(jnp.int64) & jnp.int64(0xFFFFFFFF)
        total = total + (dsum << jnp.int64(d * w))
    return total


def segment_count_sorted(
    live: jnp.ndarray,
    starts: jnp.ndarray,
    num_segments: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Per-segment live-row counts over sorted runs: one i32 prefix sum
    + boundary differences (counts < 2^31 by construction)."""
    n = live.shape[0]
    prev = jnp.clip(starts - 1, 0, n - 1)
    at_zero = starts <= 0
    ends = jnp.concatenate(
        [jnp.clip(starts[1:] - 1, 0, n - 1),
         jnp.full((1,), n - 1, starts.dtype)])
    csum = cumsum_i32(live.astype(jnp.int32), interpret=interpret)
    hi = jnp.take(csum, ends, axis=0)
    lo = jnp.where(at_zero, 0, jnp.take(csum, prev, axis=0))
    return (hi - lo).astype(jnp.int64)
