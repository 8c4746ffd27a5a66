"""Readings the correctness limits are set from: the program and its control.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in this one process: set the cell up, run its window at the
cell's own load, and compare with the plain reference twice: once what the
timed path produced (the program's reading), once the control (the
reference itself with the Eq. 12 weights rounded to bfloat16, the single
bf16 MXU pass a kernel would make of an f32 dot), in the program's place.
Each line printed is one seed's numbers beside the limits; the last line
gives, per number, the largest program reading and the smallest control
reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402
import tracing  # noqa: E402


def bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes
    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def readings(config, mix, seed, seconds):
    """(program numbers, control numbers) of one seed."""
    spans = tracing.Spans(annotate=False)
    mod, entry = run.make_entry(config, mix, seed, spans)
    counter = run.CompileCounter()
    run.set_up(entry)
    run.measure(entry, spans, seconds, counter)
    entry.release()
    ref = entry.reference()
    prog = entry.check(ref)
    ctrl = entry.check(ref, program=entry.reference(lut_round=bf16))
    return mod.LIMITS, prog, ctrl


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = {c["name"]: c for c in spec["workloads"]}[args.workload]
    run.chip_devices(cell["chips"])
    run.use_compile_cache()
    config = run.load_json(run.HERE, "configs", cell["config"] + ".json")
    mix = run.load_json(run.HERE, "traffic", cell["traffic"] + ".json")
    worst, least = {}, {}
    for seed in args.seeds:
        limits, prog, ctrl = readings(config, mix, seed, args.seconds)
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          "limits": limits}), flush=True)
        for k in limits:
            worst[k] = max(worst.get(k, prog[k]), prog[k])
            least[k] = min(least.get(k, ctrl[k]), ctrl[k])
    print(json.dumps({"program_max": worst, "control_min": least}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
