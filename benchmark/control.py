"""Read the numbers ``correct`` compares, in one process on the chip: the
cell at its own size on several seeds, sound and with each planted fault
(``lib/faults.py``; the control is ``bind_one_node``). The limits in
``lib/reference.py`` were set from these readings (PERF.md §2). The
benchmark's own runs never call this.

    python3 benchmark/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 --faults none,bind_one_node,solve_nothing

One JSON line per run: the fault, the seed, ``correct`` and every count.
Runs with a fault shorten the drain to ``--fault-drain`` seconds, since
their jobs never start.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from lib import faults, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="none")
    ap.add_argument("--fault-drain", type=float, default=15.0)
    args = ap.parse_args(argv)
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            cell = harness.load_cell(args.workload)
            if fault != "none":
                cell.traffic = dict(cell.traffic, drain_s=args.fault_drain)
            t0 = time.monotonic()
            if fault == "none":
                out = harness.run_cell(args.workload, seed, args.seconds,
                                       False, t_proc0=t0, cell=cell)
            else:
                with faults.planted(fault) as plant:
                    out = harness.run_cell(args.workload, seed, args.seconds,
                                           False, t_proc0=t0, cell=cell,
                                           plant=plant)
            print(json.dumps({
                "workload": args.workload, "fault": fault, "seed": seed,
                "correct": out["correct"], "attempted": out["attempted"],
                "failed": out["failed"],
                "checks": {k: v["value"] for k, v in out["checks"].items()},
                "metrics": {k: v["value"] for k, v in out["metrics"].items()},
                "errors": out["notes"]["errors"][:2]}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
