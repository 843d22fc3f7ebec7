"""The control of a cell's comparison: the reference computed with one
guarantee broken (`reference.control`: non-key values rounded through
bfloat16), put where the served answer would be, and compared as a run
compares. The comparison has to find it wrong; its readings are the upper
ends from which the limits in `run.LIMITS` were set.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

Makes each seed's tables on the device at the cell's own size, as a run
does, and prints one JSON line per seed with the numbers compared. It
needs no chip beyond the generator and is not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import datagen
import reference
import run


def readings(cell: dict, seed: int) -> dict:
    """The numbers the comparison gives the control for one seed."""
    steps = cell["traffic"]["plan"]
    cols = datagen.generate(cell["config"], seed)
    tables = {n: {c: np.asarray(v) for c, v in t.items()}
              for n, t in cols.items()}
    del cols
    expected = reference.Expected(reference.evaluate(steps, tables))
    got = reference.control(steps, tables)
    return expected.compare(got, len(next(iter(got.values()))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    for seed in args.seeds:
        found = readings(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": found,
                          "refused": any(found[k] > run.LIMITS[k]
                                         for k in run.LIMITS)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
