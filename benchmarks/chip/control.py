#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's own size.

For each seed, in one process: the program's readings of the checked steps
(the mode's set-up, without a window), then, after the program's state
is freed, the plain reference (float32), the control (the reference in
float8, one precision step below the bfloat16 the configuration states)
and the half-batch fault (every worker's mean over half of its positions),
and the gap of each from the reference by ``check.gaps``.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3 \
        [--sides program control half] [--out readings.jsonl]

Prints one JSON line per seed and side, then the largest sound reading and
the smallest control and fault readings of each number.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run as bench_run  # noqa: E402

SIDES = {"program": None, "control": ("fp8", "none"),
         "half": ("f32", "half")}


def readings_for(bench: dict, workload: str, seed: int, sides) -> list:
    import check
    cell, run, mode = bench_run.make_run(bench, workload, seed, 1.0, False)
    rows = []
    if "program" in sides:
        t0 = time.perf_counter()
        mode.setup()
        prog = mode.readings
        mode.free()
        t_prog = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = mode.reference("f32")
    t_ref = time.perf_counter() - t0
    for side in sides:
        if side == "program":
            got, secs = prog, t_prog
        else:
            t0 = time.perf_counter()
            got = mode.reference(*SIDES[side])
            secs = time.perf_counter() - t0
        rows.append({"workload": workload, "seed": seed, "side": side,
                     "gaps": check.gaps(got, ref), "seconds": secs,
                     "ref_seconds": t_ref, "losses": got["losses"],
                     "ref_losses": ref["losses"],
                     "left_out": check.excluded(ref)})
    return rows


def summary(rows: list) -> dict:
    import check
    out = {}
    for k in check.NUMBERS:
        sound = [r["gaps"][k] for r in rows if r["side"] == "program"]
        out[k] = {"sound_max": max(sound) if sound else None}
        for side in ("control", "half"):
            v = [r["gaps"][k] for r in rows if r["side"] == side]
            out[k][f"{side}_min"] = min(v) if v else None
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sides", nargs="+", default=list(SIDES),
                    choices=list(SIDES))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    cell = bench_run.find(bench, "workloads", args.workload)
    jax = bench_run.setup_jax()
    import counts
    # the reference and the control run on one chip; the program on the
    # cell's chips
    chips = cell["chips"] if "program" in args.sides else 1
    bench_run.check_devices(jax, chips, counts.peaks)
    rows = []
    for seed in args.seeds:
        for r in readings_for(bench, args.workload, seed, args.sides):
            rows.append(r)
            line = json.dumps(r)
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
    print(json.dumps({"summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
