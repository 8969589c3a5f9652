#!/usr/bin/env python3
"""The control of the correctness check: the reference put in the
program's place, in the nearest precision below the configuration's
(``high``, three bfloat16 passes, for float32 at ``highest``), judged by
the same comparison and limits as a run. It has to come out not correct.

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

import build  # noqa: E402
import reference as R  # noqa: E402
from spec import ROOT, load_cell  # noqa: E402


CONTROL_PRECISION = "high"


def control_numbers(cell, seed: int, rounds: int = 0, fault: str = ""):
    """(numbers, limits) of the reference at ``high`` precision against
    the one at the configuration's, over the cell's checked rounds, from
    ``seed``. With ``fault`` the reference at the configuration's precision
    with that fault planted (``reference.FAULTS``) takes the control's
    place."""
    config, traffic = cell.config, cell.traffic
    rounds = rounds or int(cell.workload["check_rounds"])
    arch32 = R.arch_from(config)
    arch16 = R.arch_from(config, CONTROL_PRECISION)
    fl = build.fl_settings(config, traffic)
    clients = build.make_traffic(traffic, arch32, seed)
    w0 = R.to_host(build.make_weights(arch32, seed))
    key = build.sim_key(seed)
    low = R.run_rounds(arch32 if fault else arch16, fl, clients, w0, key,
                       rounds, fault=fault)
    ref = R.run_rounds(arch32, fl, clients, w0, key, rounds, follow=low)
    return R.compare(low, ref, w0), cell.workload["limits"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default="", choices=("",) + R.FAULTS,
                    help="plant this fault in the reference and judge it "
                         "in place of the control")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, ROOT)
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
