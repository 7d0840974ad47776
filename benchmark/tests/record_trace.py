"""Record the small chip trace that ``test_trace.py`` checks the reduction
against: ``basic5k-burst`` cut to 200 nodes and waves of 100 jobs, traced
for 3 seconds, saved gzipped to ``data/burst_small.xplane.pb.gz`` with the
reduction's numbers beside it (``data/burst_small.json``).

Run on the chip: ``python3 benchmark/tests/record_trace.py [OUT_DIR]``.
"""

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

from lib import harness  # noqa: E402


def main() -> int:
    t0 = time.monotonic()
    cell = harness.load_cell("basic5k-burst")
    cell.config = dict(cell.config, nodes=dict(cell.config["nodes"],
                                               count=200))
    cell.traffic = dict(cell.traffic, wave_jobs=100)
    data = sys.argv[1] if len(sys.argv) > 1 \
        else os.path.join(os.path.dirname(HERE), "data")
    os.makedirs(data, exist_ok=True)
    out = harness.run_cell(cell.name, 7, 3.0, True, t_proc0=t0, cell=cell,
                           keep_trace=os.path.join(
                               data, "burst_small.xplane.pb.gz"))
    with open(os.path.join(data, "burst_small.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
