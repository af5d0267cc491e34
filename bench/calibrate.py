#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: the program's number
and its control's, seed by seed, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--out calib.jsonl]

For each seed the cell's driver (``readings`` in
``bench/drivers/<driver>.py``) makes the cell's data or weights, serves
a short window of the cell's own traffic at the cell's own sizes, and
reads:

* ``program``: each number ``bench/run.py`` compares, against the
  plain reference;
* ``control``: the same numbers for the reference itself computed one
  precision below the configuration's (``control`` in the
  configuration's ``.ref.py``), put in the program's place.

The lower reading of a limit is the largest ``program`` over a dozen
seeds or more, the upper the smallest ``control``.  The benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run
    run.use_compile_cache()
    parts = run.cell_parts(run.load_spec(), args.workload)
    try:
        run.devices(int(parts["cell"]["chips"]), True)
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return run.NO_CHIP
    cfg, mix, driver, ref = run.load_cell(parts)
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        r = driver.readings(cfg, mix, seed, args.seconds, ref)
        line = json.dumps({"workload": args.workload, "seed": seed, **r})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
