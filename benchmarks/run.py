#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that sets the cell up, warms it up, measures for
``--seconds`` and prints one JSON object as the last line of standard
output. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result: it never falls back to the CPU.
"""
import time
T_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, ROOT]
    try:
        import presto_tpu  # noqa: F401  (turns 64-bit types on, first)
    except ImportError as e:
        print(f"benchmark: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 3
    import harness
    cell = harness.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing is measured on a CPU",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} chips, JAX shows "
              f"{len(devices)}", file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
