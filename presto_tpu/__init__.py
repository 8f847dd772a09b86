"""presto_tpu: a TPU-native distributed SQL query engine.

A from-scratch re-design of the capabilities of Presto (reference:
yen-von/presto, Java) for TPU hardware: columnar batches are device-resident
struct-of-arrays with static padded shapes, query expressions compile through
JAX tracing to XLA (the analogue of Presto's runtime bytecode generation,
reference presto-main/.../sql/gen/), relational operators are sort/segment
kernels on the VPU/MXU, and distributed execution is SPMD ``shard_map`` over a
``jax.sharding.Mesh`` with ICI collectives standing in for Presto's HTTP page
shuffle.
"""
import os

import jax

# SQL semantics need real int64/float64 (BIGINT/DOUBLE); enable before any
# array is created anywhere in the package.
jax.config.update("jax_enable_x64", True)


def enable_compile_cache() -> None:
    """Persistent XLA compile cache for every entry point (CLI, both
    servers, benchmarks/, tools/fleet.py, chip_smoke.py): the directory is
    placed from OUTSIDE. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
    reads it itself and no directory is set in code; where it is not,
    the fixed ``<checkout>/.jax_cache`` (the path is part of the cache
    key — a directory that moves never hits). Idempotent; call it any
    time before the first compilation."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


__version__ = "0.1.0"

from . import types  # noqa: E402,F401
from .batch import Batch, Column, Schema, bucket_capacity  # noqa: E402,F401
