"""Readings that a cell's check limit is set from, in one process on the
chip: for each seed, a short window at the cell's own load and sizes,
then the widest gap of the served tokens below the reference's best
(the program's reading) and the widest gap of the tokens the float8
control puts first (the control's reading).

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 --seconds 4 [--out chiprun_out/cal.jsonl]

The benchmark's own runs never run this. One line of JSON per seed on
standard output (and appended to `--out`), then a summary line: the
largest program reading (the lower reading) and the smallest control
reading (the upper reading).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from benchmarks.chip import run as R

    bench = R.Bench()
    wl = bench.cell(args.workload)
    try:
        R.device_check(wl["chips"])
    except R.NoDevice as e:
        print(f"[calibrate] {e}", file=sys.stderr)
        return 2
    R.enable_cache(bench.root)
    conf, mix, model = bench.conf(wl), bench.mix(wl), bench.model(wl)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = bench.driver(mix).Cell(conf, model, mix)
    cell.setup(seeds[0])
    ref, ctl = model.Reference(conf), model.Reference(conf, control=True)
    rows = []
    for seed in seeds:
        cell.seed = seed
        cell.set_weights(seed)
        window_s = cell.window(args.seconds)
        gap, (cgap,) = cell.gaps(ref, cell.sample(), [ctl])
        row = {"workload": wl["name"], "seed": seed, "program_gap": gap,
               "control_gap": cgap, "window_s": window_s,
               "waves": len(cell.waves), **cell.counts()}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    print(json.dumps({"workload": wl["name"], "seeds": len(rows),
                      "lower": max(r["program_gap"] for r in rows),
                      "upper": min(r["control_gap"] for r in rows)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
