"""Read ``correct`` with each fault planted under the node-sharded solve
(``lib/mesh_faults.py``), in one process on the chip: ``control.py``'s
faults patch ``decode_compact``, which the sharded path never calls. The
benchmark's own runs never call this.

    python3 benchmark/mesh_control.py --workload mesh100k-burst \\
        --seconds 1 --seeds 7 --faults solve_nothing,solve_to_node0

One JSON line per run: the fault, the seed, ``correct`` and every count.
Each run's drain is cut to ``--fault-drain`` seconds, since its jobs never
start; at 100k pods a wave's first solving turn comes 10-20 s after its
submission, so keep the drain above that. ``--chips`` runs the cell on
fewer chips than it asks for: the sharded solve whose result the faults
patch runs on a mesh of one device too.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from lib import harness, mesh_faults  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(mesh_faults.FAULTS))
    ap.add_argument("--fault-drain", type=float, default=30.0)
    ap.add_argument("--chips", type=int, default=0)
    args = ap.parse_args(argv)
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            cell = harness.load_cell(args.workload)
            cell.traffic = dict(cell.traffic, drain_s=args.fault_drain)
            if args.chips:
                cell.chips = args.chips
            t0 = time.monotonic()
            with mesh_faults.planted(fault):
                out = harness.run_cell(args.workload, seed, args.seconds,
                                       False, t_proc0=t0, cell=cell)
            print(json.dumps({
                "workload": args.workload, "fault": fault, "seed": seed,
                "chips": cell.chips, "correct": out["correct"],
                "attempted": out["attempted"], "failed": out["failed"],
                "solved_cycles": out["notes"]["solved_cycles"],
                "checks": {k: v["value"] for k, v in out["checks"].items()},
                "errors": out["notes"]["errors"][:2]}), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
