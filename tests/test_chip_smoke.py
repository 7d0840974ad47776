"""chip_smoke.py's phases at a tiny size on the CPU, so the script cannot
rot between chip runs. The platform check lives only in ``main``; here it
must refuse the CPU.

Also the evict twin of the JAX 0.9 shard_map failure: the sharded evict
kernel jitted over committed ``NamedSharding`` inputs."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_main_path_tiny():
    out = chip_smoke.phase_main(n_nodes=40, n_jobs=20, tpj=10)
    assert out["bound"] == 200
    assert out["sharded_device_cache"] is None


def test_preempt_wave_tiny():
    out = chip_smoke.phase_preempt(n_nodes=20, n_running=200, n_claim=100)
    assert out["placed"] == 100
    # ten victims a node, three claimers fit each node's idle: two more
    # need four evictions a node (BASELINE config 4 at a tenth)
    assert out["evictions"] == 80


def test_exactness_tiny():
    out = chip_smoke.phase_exactness(n_nodes=12, n_jobs=20, tpj=4)
    assert out["binds"] > 0


def test_four_chip_phase_on_cpu_mesh():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device CPU mesh")
    out = chip_smoke.phase_four_chips(n_nodes=40, n_jobs=20, tpj=10)
    assert out["devices"] == len(jax.devices()[:8])


def test_checks_catch_a_host_fallback():
    with pytest.raises(chip_smoke.SmokeFailure, match="host_fallback"):
        chip_smoke.check_device_cycles(
            [{"flatten_ms": 1.0, "arena_mode": "packed",
              "dispatch_ms": 1.0, "readback_ms": 1.0,
              "host_fallback": 1.0}], "packed")


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform=cpu" in out


def test_sharded_evict_jit_with_committed_named_sharding():
    """R1's evict twin: every array committed to the mesh with the
    kernel's own partition specs before the jitted call (JAX 0.9 refuses
    an outer-scope tracer inside the manual shard_map context)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from volcano_tpu.api import JobInfo, NodeInfo, TaskInfo, TaskStatus
    from volcano_tpu.api.types import POD_GROUP_ANNOTATION
    from volcano_tpu.models import Node, Pod, PodGroup, PodGroupSpec
    from volcano_tpu.ops import flatten_snapshot
    from volcano_tpu.ops.evict import (
        decode_evict_compact, pack_victim_arrays, solve_evict_uniform,
    )
    from volcano_tpu.parallel import make_mesh
    from volcano_tpu.parallel.sharded_evict import (
        _solve_sharded, shard_victims,
    )

    from test_solver import params_dict

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device CPU mesh")
    mesh = make_mesh(jax.devices()[:8])
    n_nodes, n_victims, n_claim = 16, 160, 80
    nodes = {}
    for i in range(n_nodes):
        rl = {"cpu": "16", "memory": "64Gi", "pods": 110}
        nodes[f"n{i}"] = NodeInfo(Node(name=f"n{i}", allocatable=rl,
                                       capacity=dict(rl)))
    low = JobInfo("ns/low", PodGroup(name="low", namespace="ns",
                                     spec=PodGroupSpec(min_member=1)))
    victims = []
    for i in range(n_victims):
        pod = Pod(name=f"low-{i}", namespace="ns",
                  node_name=f"n{i % n_nodes}", phase="Running",
                  annotations={POD_GROUP_ANNOTATION: "low"},
                  containers=[{"requests": {"cpu": "1", "memory": "2Gi"}}])
        t = TaskInfo(pod)
        t.status = TaskStatus.RUNNING
        low.add_task_info(t)
        nodes[f"n{i % n_nodes}"].add_task(t)
        victims.append(t)
    hi = JobInfo("ns/hi", PodGroup(name="hi", namespace="ns",
                                   spec=PodGroupSpec(min_member=n_claim)))
    claimers = []
    for i in range(n_claim):
        pod = Pod(name=f"hi-{i}", namespace="ns",
                  annotations={POD_GROUP_ANNOTATION: "hi"},
                  containers=[{"requests": {"cpu": "2", "memory": "4Gi"}}])
        t = TaskInfo(pod)
        hi.add_task_info(t)
        claimers.append(t)
    arr = flatten_snapshot({hi.uid: hi}, nodes, claimers)
    params = params_dict(arr, least_req_weight=1.0)
    varrays = pack_victim_arrays(arr, victims, n_claim)

    node_keys = {"node_idle", "node_extra_future", "node_used",
                 "node_alloc", "node_valid"}
    rep, along_n = NamedSharding(mesh, P()), NamedSharding(mesh, P("n"))
    cols = NamedSharding(mesh, P(None, "n"))

    def commit(k, v, keys, col_keys):
        sh = cols if k in col_keys else along_n if k in keys else rep
        return jax.device_put(np.asarray(v), sh)

    a = {k: commit(k, v, node_keys, {"sig_masks"})
         for k, v in arr.device_dict().items()}
    sharded, perm = shard_victims(varrays, arr.N, 8)
    v = {k: commit(k, val, {"v_req", "v_node", "v_valid"}, {"elig"})
         for k, val in sharded.items()}
    sp = {k: commit(k, val, {"node_static"}, ()) for k, val in
          params.items()}
    assigned, evby_s, _ = _solve_sharded(a, v, sp, mesh, ("kube",),
                                         False, True)
    evby_s = np.asarray(evby_s)

    r1 = solve_evict_uniform(arr.device_dict(), varrays, params)
    _, e1 = decode_evict_compact(r1.compact, arr.T)
    assert int((np.asarray(assigned)[:n_claim] >= 0).sum()) == n_claim
    assert int((evby_s[perm >= 0] >= 0).sum()) == int((e1 >= 0).sum())


def test_last_line_is_the_device_json(monkeypatch, capsys):
    """On a (faked) TPU, main's last stdout line is exactly the result
    object, with the device as JAX reports it."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_info", lambda: dev)
    for name in ("phase_main", "phase_preempt", "phase_exactness"):
        monkeypatch.setattr(chip_smoke, name, lambda **kw: {})
    from volcano_tpu.ops import precompile
    monkeypatch.setattr(precompile, "configure_compilation_cache",
                        lambda *a, **kw: None)
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": dev}
