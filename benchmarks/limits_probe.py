#!/usr/bin/env python3
"""The readings a template's DOUBLE limit is set from, many seeds in one
process (set-up is long: one server, one staging of the scans).

    python3 benchmarks/limits_probe.py --workload <cell> --seeds 101,102,...

For each seed: the cell's bindings drawn as a run draws them, each
statement sent twice through the front door after its warm-up, every
answer held to the float64 reference (the program's reading: the LOWER
end of the limit), and the float32 control held to it the same way (the
UPPER end). It prints one JSON line per seed and a summary; the
benchmark's own runs never call it. Like the command it needs the TPU
and runs the cell at its own scale: a limit is never read off a CPU.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: each statement is sent this many times after its warm-up
REPEAT = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    args = ap.parse_args(argv)

    sys.path[:0] = [HERE, ROOT]
    import presto_tpu  # noqa: F401
    import numpy as np
    import harness
    cell = harness.load_cell(args.workload)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("limits_probe: JAX found no TPU", file=sys.stderr)
        return 2
    t = cell.template
    log = harness.CompileLog()
    door = harness.Door(cell.config)
    program, control = [], []
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            bindings = harness.draw_bindings(t, cell.traffic, seed)
            answers, secs, compile_s, errors = [], [], [], []
            for b in bindings:
                sql = t.SQL.format(**b)
                t0 = time.perf_counter()
                for _ in range(1 + REPEAT):
                    rows, err, s = door.query(sql)
                    answers.append(rows)
                    errors.append(err)
                    secs.append(round(s, 3))
                compile_s.append(round(sum(
                    e[1] for e in log.between(t0, time.perf_counter())), 3))
            t0 = time.perf_counter()
            want = t.reference(cell.data, cell.sf, bindings)
            ctrl = t.reference(cell.data, cell.sf, bindings, np.float32)
            ref_s = time.perf_counter() - t0
            gap = wrong = 0
            for i, rows in enumerate(answers):
                if rows is None:
                    wrong += 1
                    continue
                g, w = harness.compare_rows(
                    t.KINDS, rows, want[i // (1 + REPEAT)])
                gap, wrong = max(gap, g), wrong + w
            cg = [harness.compare_rows(t.KINDS, c, w)
                  for c, w in zip(ctrl, want)]
            program.append(gap)
            control.append(max(g for g, _ in cg))
            print(json.dumps({
                "seed": seed, "bindings": bindings,
                "program_double_rel_gap": gap, "program_exact_wrong": wrong,
                "errors": [e for e in errors if e],
                "control_double_rel_gap": control[-1],
                "control_exact_wrong": sum(w for _, w in cg),
                "query_s": secs, "compile_s_per_binding": compile_s,
                "reference_and_control_s": round(ref_s, 3)}), flush=True)
    finally:
        door.close()
    print(json.dumps({
        "workload": cell.name, "device": [dev.platform, dev.device_kind],
        "seeds": len(program), "lower_reading_max_program": max(program),
        "upper_reading_min_control": min(control),
        "limit": t.DOUBLE_REL_LIMIT}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
