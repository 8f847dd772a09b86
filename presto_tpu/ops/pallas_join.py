"""Pallas TPU probe kernel for direct-address joins: one tiled pass
fusing probe-slot lookup + liveness mask + payload gather.

Why this exists (docs/perf.md round 8): the XLA probe path for a
direct-address join emits one separate gather op per table/payload
column — each materializes its [probe_capacity] output in HBM, so an
N-payload dimension join re-reads the probe-sized index vector N+2
times and re-writes N+2 full-width intermediates per batch. This kernel
makes the probe inner loop ONE grid pass: a [R, L] tile of probe slot
codes is resolved against the VMEM-resident lookup tables (lo/cnt: the
TWO gathers `ops/join.prepare_direct` promises), the match count and a
packed validity bitmask come back with it, and every payload plane is
gathered in the same tile visit — no per-gather HBM round trips. The
ragged-gather shape follows the Ragged Paged Attention exemplar
(PAPERS.md): fixed tile grid over a ragged logical access pattern, with
the page table (here: lo/cnt tables) resident on-chip.

Constraints that shape this file (same as ops/pallas_scan.py):

- NO 64-bit array crosses the ``pallas_call`` boundary: Mosaic lowers
  no 64-bit vector types ("64-bit types are not supported"), so 64-bit
  payloads (bigint, double via IEEE bitcast, int128 limb pairs)
  decompose into two i32 digit planes OUTSIDE the kernel and are
  reassembled from the gathered planes — truncating i64->i32 casts are
  exact mod 2^32, so ``(hi << 32) | (lo & 0xffffffff)`` round-trips
  every value;
- per-column validity masks pack into ONE i32 bit-plane (bit c =
  payload column c), so a join gathers validity for up to 32 payload
  columns in a single extra plane;
- tables and payload planes must fit VMEM (~16MB/core): the dispatch
  gate ``direct_probe_supported`` budgets them and declines above the
  budget — exactly the dimension-table sizes the direct path targets
  fit, fact-table builds never take it.

STATUS (PR 23, JAX 0.9.0 / libtpu 0.0.34): the kernel DOES NOT LOWER for
a TPU v5e. Compiled ahead of time for a described ``v5e:2x2`` at 2^20
probe rows against 2^17-slot tables, the body as written dies in
Mosaic's element-type conversion with a RecursionError when written
with the default fill-mode ``jnp.take`` (``jax_enable_x64`` brings
64-bit index arithmetic into the body); with the index arithmetic
pinned to i32, as it is now, the compiler reaches the gather itself and
refuses it: ``NotImplementedError: Only 2D gather is supported`` — the
only gather Mosaic lowers is ``tpu.dynamic_gather`` with operand,
indices and output of ONE shape (a take_along_axis inside a tile), not
arbitrary indices into a VMEM-resident table. So the
``join_pallas_probe`` session property is OFF by default and the probe
path on the chip is the XLA gather (``ops/join.lookup_join``). Parity
with that path is shown in interpret mode only
(tests/test_join_strategy.py); tests/test_tpu_compile.py holds the
property to its default. With the property switched on, nothing
catches a kernel failure: the query fails with the compiler's message.
Whether the kernel is rewritten around ``dynamic_gather`` or deleted is
for a later issue that starts from a trace (ROADMAP.md).
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..batch import Batch, Column, Schema
from .join import _key_arrays, direct_slot_codes, is_direct_prepared, \
    _split_prepared, permuted_payload

R, L = 8, 128            # probe tile: 8 sublanes x 128 lanes
TILE = R * L

#: VMEM budget for tables + payload planes (out of ~16MB/core); above
#: it the dispatch gate declines and the XLA path runs
VMEM_BUDGET_BYTES = 8 << 20

#: tests set this to exercise the kernel on the CPU mesh (interpret
#: mode); engine call sites otherwise use it only on real TPU backends
FORCE_PALLAS_PROBE = False


def _interpret() -> bool:
    return jax.default_backend() in ("cpu",)


def kernel_enabled() -> bool:
    """Backend the kernel is meant for (or tests forcing interpret
    mode). Whether a join takes it is the ``join_pallas_probe`` session
    property's call (default off, module docstring)."""
    return FORCE_PALLAS_PROBE or jax.default_backend() not in ("cpu",)


def _planes_for(data) -> int:
    if getattr(data, "ndim", 1) == 2:
        return 4
    if data.dtype in (jnp.float64, jnp.int64, jnp.uint64):
        return 2
    return 1


def supports_join(prepared, build: Batch, payload: Sequence[int]) -> bool:
    """Full dispatch gate for one lookup join: direct prepared, packable
    validity bits (<= 31 payload columns), and everything within the
    VMEM budget. Host-static under jit (reads dtypes/shapes only)."""
    if not kernel_enabled() or not is_direct_prepared(prepared):
        return False
    if len(payload) > 31:
        return False
    n_planes = sum(_planes_for(build.columns[ci].data) for ci in payload)
    return direct_probe_supported(prepared, n_planes)


def direct_probe_supported(prepared, n_planes: int) -> bool:
    """VMEM budget gate: both lookup tables, the validity bit-plane and
    every payload plane must be resident on-chip for the fused pass."""
    if not is_direct_prepared(prepared):
        return False
    lo_table = prepared[1] if len(prepared) == 6 else prepared[2]
    s_ops = _split_prepared(prepared)[0]
    n_build = int(s_ops[0].shape[0])
    size = int(lo_table.shape[0])
    if size < L or n_build < L:
        return False            # tables pad to lane width; tiny builds
    bytes_needed = 4 * (2 * size + (1 + n_planes) * n_build + 2 * TILE)
    return bytes_needed <= VMEM_BUDGET_BYTES


# ---------------------------------------------------------------------------
# i32 plane decomposition (outside the kernel; see module docstring)
# ---------------------------------------------------------------------------

_M32 = jnp.int64(0xFFFFFFFF)


def _decompose(data: jnp.ndarray) -> Tuple[str, List[jnp.ndarray]]:
    """(tag, i32 planes) of one payload column's device array."""
    if getattr(data, "ndim", 1) == 2:
        # int128 limb pairs [n, 2] of i64: four digit planes
        planes = []
        for limb in (data[..., 0], data[..., 1]):
            planes.append((limb >> jnp.int64(32)).astype(jnp.int32))
            planes.append((limb & _M32).astype(jnp.int32))
        return "i128", planes
    dt = data.dtype
    if dt == jnp.float64:
        u = jax.lax.bitcast_convert_type(data, jnp.uint64)
        s = u.astype(jnp.int64)
        return "f64", [(s >> jnp.int64(32)).astype(jnp.int32),
                       (s & _M32).astype(jnp.int32)]
    if dt in (jnp.int64, jnp.uint64):
        s = data.astype(jnp.int64)
        tag = "i64" if dt == jnp.int64 else "u64"
        return tag, [(s >> jnp.int64(32)).astype(jnp.int32),
                     (s & _M32).astype(jnp.int32)]
    if dt == jnp.float32:
        return "f32", [jax.lax.bitcast_convert_type(data, jnp.int32)]
    if dt == jnp.bool_:
        return "bool", [data.astype(jnp.int32)]
    # int32 / int16 / int8 / date codes / dictionary codes
    return str(dt), [data.astype(jnp.int32)]


def _reassemble(tag: str, planes: Sequence[jnp.ndarray]) -> jnp.ndarray:
    def to64(hi, lo):
        return ((hi.astype(jnp.int64) << jnp.int64(32))
                | (lo.astype(jnp.int64) & _M32))
    if tag == "i128":
        return jnp.stack([to64(planes[0], planes[1]),
                          to64(planes[2], planes[3])], axis=-1)
    if tag == "f64":
        return jax.lax.bitcast_convert_type(
            to64(planes[0], planes[1]).astype(jnp.uint64), jnp.float64)
    if tag == "i64":
        return to64(planes[0], planes[1])
    if tag == "u64":
        return to64(planes[0], planes[1]).astype(jnp.uint64)
    if tag == "f32":
        return jax.lax.bitcast_convert_type(planes[0], jnp.float32)
    if tag == "bool":
        return planes[0].astype(jnp.bool_)
    return planes[0].astype(jnp.dtype(tag))


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _imap(i):
    # literal indices pinned to i32 (Mosaic rejects i64 at func.return
    # under jax_enable_x64 — same guard as ops/pallas_scan._imap)
    return (jnp.asarray(i, jnp.int32), jnp.int32(0))


def _full(i):
    return (jnp.int32(0), jnp.int32(0))


def _probe_kernel_factory(n_planes: int, n_build: int):
    def kernel(code_ref, lo_ref, cnt_ref, vb_ref, *refs):
        plane_refs = refs[:n_planes]
        cnt_out, vb_out = refs[n_planes], refs[n_planes + 1]
        outs = refs[n_planes + 2:]
        # index arithmetic pinned to i32 and in-bounds takes (mode=clip:
        # every index is already clamped): under jax_enable_x64 the
        # default fill-mode take brings 64-bit conversions into the body
        # that Mosaic dies on before it reaches the gather, and the
        # gather's own refusal is the message worth showing
        zero = jnp.int32(0)
        idx = code_ref[:]                        # [R, L]; -1 = no-lookup
        ok = idx >= zero
        safe = jnp.where(ok, idx, zero)
        take = functools.partial(jnp.take, axis=0, mode="clip")
        lo = take(lo_ref[0, :], safe)
        cnt = jnp.where(ok, take(cnt_ref[0, :], safe), zero)
        cnt_out[:] = cnt
        pos = jnp.clip(lo, zero, jnp.int32(n_build - 1))
        hit = cnt > zero
        vb_out[:] = jnp.where(hit, take(vb_ref[0, :], pos), zero)
        for p in range(n_planes):
            outs[p][:] = take(plane_refs[p][0, :], pos)
    return kernel


def _direct_probe_call(codes2d, lo_t, cnt_t, vbits, planes,
                       interpret: bool):
    from jax.experimental import pallas as pl
    n_planes = len(planes)
    n_build = planes[0].shape[1] if planes else vbits.shape[1]
    rows = codes2d.shape[0]
    tile = pl.BlockSpec((R, L), _imap)
    res = pl.BlockSpec((1, lo_t.shape[1]), _full)
    pres = pl.BlockSpec((1, n_build), _full)
    out_shapes = ([jax.ShapeDtypeStruct(codes2d.shape, jnp.int32)] * 2
                  + [jax.ShapeDtypeStruct(codes2d.shape, jnp.int32)
                     for _ in range(n_planes)])
    out = pl.pallas_call(
        _probe_kernel_factory(n_planes, n_build),
        grid=(rows // R,),
        in_specs=[tile, res, res, pres] + [pres] * n_planes,
        out_specs=[tile, tile] + [tile] * n_planes,
        out_shape=out_shapes,
        interpret=interpret,
    )(codes2d, lo_t, cnt_t, vbits, *planes)
    return out[0], out[1], out[2:]


def direct_probe(codes: jnp.ndarray, lo_table: jnp.ndarray,
                 cnt_table: jnp.ndarray, vbits: jnp.ndarray,
                 planes: Sequence[jnp.ndarray], interpret=None):
    """(cnt, vbits_gathered, payload planes gathered) per probe lane.

    ``codes``: i32[n] slot indices, -1 for lanes that must not match
    (out of domain / NULL key / dead row). ``vbits``/``planes``:
    i32[n_build] arrays in SORTED build order. All i32 in and out — the
    64-bit decomposition happens in the caller (module docstring)."""
    if interpret is None:
        interpret = _interpret()
    n = codes.shape[0]
    pad = (-n) % TILE
    if pad:
        codes = jnp.concatenate(
            [codes, jnp.full(pad, -1, dtype=jnp.int32)])
    codes2d = codes.reshape(-1, L)
    cnt2d, vb2d, out2d = _direct_probe_call(
        codes2d, lo_table.reshape(1, -1), cnt_table.reshape(1, -1),
        vbits.reshape(1, -1), [p.reshape(1, -1) for p in planes],
        interpret)
    unpad = lambda a: a.reshape(-1)[:n]
    return unpad(cnt2d), unpad(vb2d), [unpad(o) for o in out2d]


# ---------------------------------------------------------------------------
# lookup_join on the kernel (the fused probe inner loop)
# ---------------------------------------------------------------------------

def lookup_join_direct(
    probe: Batch,
    build: Batch,
    probe_keys: Sequence[int],
    build_keys: Sequence[int],
    payload: Sequence[int],
    payload_names: Sequence[str],
    join_type: str,
    prepared,
) -> Batch:
    """``ops/join.lookup_join`` semantics on the Pallas probe kernel —
    unique-build inner/left join against a direct prepared. Row-exact
    with the XLA path by construction: the same ``direct_slot_codes``
    addressing, the same clip/mask semantics, only the gather engine
    differs."""
    assert join_type in ("inner", "left")
    assert is_direct_prepared(prepared)
    s_ops, slive, perm = _split_prepared(prepared)
    q_ops, pvalid = _key_arrays(probe, probe_keys)
    slot, inr = direct_slot_codes(q_ops, prepared)
    live = probe.row_mask & pvalid & inr
    codes = jnp.where(live, slot, -1).astype(jnp.int32)

    # sorted-order payload planes + packed validity bits (32 cols/plane)
    tags: List[Tuple[str, int]] = []
    planes: List[jnp.ndarray] = []
    vbits = jnp.zeros(slive.shape, dtype=jnp.int32)
    for c_i, (sdata, svalid) in enumerate(
            permuted_payload(build, payload, perm)):
        tag, ps = _decompose(sdata)
        tags.append((tag, len(ps)))
        planes.extend(ps)
        vbits = vbits | (svalid.astype(jnp.int32) << c_i)

    cnt, vb, gathered = direct_probe(codes, prepared[1] if
                                     len(prepared) == 6 else prepared[2],
                                     prepared[2] if len(prepared) == 6
                                     else prepared[3], vbits, planes)
    match = cnt > 0            # codes already folded row_mask/valid/inr

    out_fields = list(zip(probe.schema.names, probe.schema.types))
    out_cols: List[Column] = list(probe.columns)
    at = 0
    for j, ((tag, k), ci, name) in enumerate(zip(tags, payload,
                                                 payload_names)):
        c = build.columns[ci]
        data = _reassemble(tag, gathered[at:at + k])
        at += k
        valid = (((vb >> j) & 1) > 0) & match
        out_fields.append((name, c.type))
        out_cols.append(Column(c.type, data, valid, c.dictionary))
    mask = match if join_type == "inner" else probe.row_mask
    return Batch(Schema(out_fields), out_cols, mask)
