#!/usr/bin/env python3
"""What the host pays to launch a program, by the number of arrays in
and out: a jitted add over n arrays (n results), 400 launches each, at
128 rows and at 2^20.

    chiprun -- python3 tools/launch_probe.py

On a TPU v5e (PERF.md section 5, PR 27): 0.19 ms a launch and ~0.036 ms
for each array in or out, whatever its size: count a program's buffers,
not only its launches. On a CPU the numbers are the CPU's."""
import time, json
import jax, jax.numpy as jnp
print(jax.devices())
out = {}
for n in (1, 4, 16, 32, 54, 96):
    for rows in (128, 1 << 20):
        if rows > 128 and n not in (16, 54):
            continue
        f = jax.jit(lambda *xs: tuple(x + 1 for x in xs))
        xs = tuple(jnp.zeros(rows, jnp.int32) + i for i in range(n))
        ys = f(*xs); jax.block_until_ready(ys)
        N = 400
        t = time.perf_counter()
        for _ in range(N):
            ys = f(*ys)
        t1 = time.perf_counter()
        jax.block_until_ready(ys)
        t2 = time.perf_counter()
        out[f"{n}x{rows}"] = (round(1e3 * (t1 - t) / N, 4), round(1e3 * (t2 - t) / N, 4))
        print(n, rows, "host ms a launch", out[f"{n}x{rows}"][0], "with drain", out[f"{n}x{rows}"][1], flush=True)
# results NOT fed back (fresh outputs each call, inputs resident like scan batches)
for n in (16, 54):
    f = jax.jit(lambda *xs: tuple(x + 1 for x in xs))
    xs = tuple(jnp.zeros(128, jnp.int32) + i for i in range(n))
    jax.block_until_ready(f(*xs))
    t = time.perf_counter()
    for _ in range(400):
        ys = f(*xs)
    t1 = time.perf_counter(); jax.block_until_ready(ys)
    print(n, "resident inputs: host ms a launch", round(1e3 * (t1 - t) / 400, 4), flush=True)
print(json.dumps(out))
