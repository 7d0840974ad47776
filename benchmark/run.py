"""Run one benchmark cell once and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by name
from ``BENCHMARK.json`` (see ``lib/harness.py``).

The run re-executes itself once with ``PYTHONHASHSEED`` set from the seed,
before anything imports JAX: some of the scheduler's tie orders follow
string hashing, and the seed has to fix the decisions. It exits non-zero,
and prints no result, where JAX finds no TPU or fewer chips than the cell
asks for, or where the program or a file of the cell is missing.
"""

from __future__ import annotations

import time

T_PROC0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_T0_ENV = "PERFBENCH_T0"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def hash_seed(seed: int) -> str:
    return str(seed % (2 ** 32))


def reexec_pinned(argv, seed: int) -> None:
    """Replace this process with one whose ``PYTHONHASHSEED`` follows the
    seed (no-op when it already does)."""
    want = hash_seed(seed)
    if os.environ.get("PYTHONHASHSEED") == want:
        return
    env = dict(os.environ, PYTHONHASHSEED=want)
    env[_T0_ENV] = repr(T_PROC0)
    sys.stdout.flush()
    sys.stderr.flush()
    os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)]
              + list(argv), env)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    args = parse(argv)
    reexec_pinned(argv, args.seed)
    t0 = float(os.environ.pop(_T0_ENV, T_PROC0))
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(1, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from lib import harness

    try:
        import volcano_tpu  # noqa: F401 — the system under test
    except ImportError as e:
        print(f"run.py: the program is not here: {e}", file=sys.stderr)
        return 2
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_proc0=t0)
    except harness.CellError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: live daemon threads (controller watches,
    # the XLA runtime) can abort it after the result is already out
    os._exit(rc)
