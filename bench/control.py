#!/usr/bin/env python3
"""The control of the correctness check: the reference put in the
program's place, in the nearest precision below the configuration's
(for the WRN family ``high``, three bfloat16 passes, for float32 at
``highest``), judged by the same comparison and limits as a run. The
configuration's family (``bench/families/``) computes it. It has to come
out not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

prints, per seed, the numbers compared with their limits and whether the
control passed, and as its last line a JSON object of them all. It needs
no chip, but its readings at a cell's own size are taken on one. The
benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

from spec import ROOT, load_cell, load_family  # noqa: E402


def control_numbers(cell, seed: int, rounds: int = 0, fault: str = ""):
    """(numbers, limits) of the control against the reference, over the
    cell's checked rounds, from ``seed``: the family's ``control``. With
    ``fault`` the reference at the configuration's precision with that
    fault planted (the family's ``FAULTS``) takes the control's place."""
    rounds = rounds or int(cell.workload["check_rounds"])
    family = load_family(cell.family, ROOT)
    return (family.control(cell.config, cell.traffic, seed, rounds,
                           fault=fault),
            cell.workload["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default="",
                    help="plant this fault (one of the family's FAULTS) in "
                         "the reference and judge it in place of the "
                         "control")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, ROOT)
    faults = load_family(cell.family, ROOT).FAULTS
    if args.fault and args.fault not in faults:
        ap.error(f"--fault must be one of {faults}")
    out = {}
    for seed in args.seeds:
        numbers, limits = control_numbers(cell, seed, fault=args.fault)
        failed = [k for k, lim in limits.items()
                  if not numbers.get(k, float("inf")) <= lim]
        out[str(seed)] = {"numbers": numbers, "over_limit": failed}
        print(f"seed {seed}: control numbers {numbers}; over the limit: "
              f"{failed or 'NONE (the control passed)'}", flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
