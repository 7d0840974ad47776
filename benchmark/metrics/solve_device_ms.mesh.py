"""Node-sharded allocate solve on the device (ms per 1,000 traffic pods
bound in the window): device time of the solve_allocate_sharded*
executables in the trace, which sums every device's plane, divided by the
mesh width the program counted (``mesh_devices``, added once per sharded
solve)."""

from lib.layers import module_ms


def read(run):
    solves = [t.timing["mesh_devices"] for t in run.turns
              if "mesh_devices" in t.timing]
    ms = module_ms(run, "solve_allocate_sharded")
    if ms is None or not solves or not run.binds:
        return None
    width = sum(solves) / len(solves)
    return ms / width / (run.binds / 1000.0)
