"""The lower-precision control of a cell, at the cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds 11,12,13 \
        [--outputs N]

For each seed it builds the cell's inputs, computes the plain reference in
the configuration's `control_format` (fewer word and fraction bits than
the configuration states), puts those outputs where the program's go,
and compares them with the reference in the configuration's own format,
exactly as a run's outputs are compared. It prints each compared number
beside its limit; the control has to come out not correct on every seed.
The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--outputs", type=int, default=300,
                    help="frames or requests compared per seed")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import harness
    cell = harness.load_cell(ROOT, args.workload)
    runner = harness.load_runner(ROOT, cell)
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ck = runner.control(cell, seed, args.outputs)
        bad += not ck.correct
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": ck.correct, "failed": ck.failed,
                          "compared": {n: {"value": v, "limit": lim}
                                       for n, v, lim in ck.compared},
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0 if bad == len(args.seeds.split(",")) else 1


if __name__ == "__main__":
    sys.exit(main())
