"""The main path's kernels compiled for a described TPU v5e.

Nothing runs: the TPU compiler, installed here, compiles for a 2x2 v5e
that is described, not attached. That refuses what interpret mode
accepts — a Mosaic block off the (8, 128) tiling, too much VMEM, a
program that cannot be partitioned — at no chip time. The solvers ask
``pallas_kernels.platform()`` which kernel path to trace; each test
steers that one function to "tpu" (the process itself stays on the CPU).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the suite runs in
several workers (on-chip-measurement guide §2).
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the north-star bucket (BASELINE config 3: 10k pods / 2k nodes)
T_NS, N_NS = 10240, 2048


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile.
    # jit caches are cleared on both sides so no interpret-mode trace of
    # the same shapes is reused, and none of these leaks to later tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    yield desc
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    from volcano_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "platform", lambda: "tpu")


def _shapes(tree, sharding_of):
    import jax

    return {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                    sharding=sharding_of(k))
            for k, v in tree.items()}


def _north_star():
    from __graft_entry__ import _params
    from bench import _synth_snapshot

    arr = _synth_snapshot(T_NS, N_NS, tasks_per_job=10)
    return arr.device_dict(), _params(arr)


def test_fused_choice_10k_2k(topo, on_tpu):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from volcano_tpu.ops.pallas_kernels import fused_choice

    one = SingleDeviceSharding(topo.devices[0])
    R = 2

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    f32 = jnp.float32
    args = (sds((T_NS, R), f32), sds((N_NS, R), f32), sds((N_NS, R), f32),
            sds((N_NS, R), f32), sds((N_NS,), f32), sds((T_NS,), f32),
            sds((N_NS,), f32), sds((T_NS, N_NS), jnp.int8),
            sds((5 + R,), f32))
    compiled = fused_choice.lower(*args, families=("binpack",)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("drf", [False, True], ids=["static", "drf"])
def test_solve_allocate_10k_2k(topo, on_tpu, drf):
    """The single-device solve at the north-star bucket; ``drf`` is the
    live DRF ordering chip_smoke.py's main phase runs."""
    from jax.sharding import SingleDeviceSharding

    from volcano_tpu.ops.solver import solve_allocate

    one = SingleDeviceSharding(topo.devices[0])
    a, p = _north_star()
    compiled = solve_allocate.lower(
        _shapes(a, lambda k: one), _shapes(p, lambda k: one),
        herd_mode="pack", score_families=("binpack",),
        use_drf_order=drf, fused="auto").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_solve_evict_uniform_config4(topo, on_tpu):
    """The preempt wave's evict kernel at BASELINE config 4's size (2k
    running pods on 200 nodes, one 1k-pod gang). It is plain XLA (no
    Pallas kernel), so what is checked is that the chip's compiler takes
    it and that it fits the chip's memory."""
    from jax.sharding import SingleDeviceSharding

    from __graft_entry__ import _params
    from bench import _synth_snapshot
    from volcano_tpu.ops.evict import solve_evict_uniform

    one = SingleDeviceSharding(topo.devices[0])
    arr = _synth_snapshot(1024, 200, tasks_per_job=1024)
    V, J, R = 2048, arr.job_min.shape[0], 2
    victims = {"v_req": np.zeros((V, R), np.float32),
               "v_node": np.zeros(V, np.int32),
               "v_valid": np.zeros(V, bool),
               "elig": np.zeros((J, V), bool),
               "job_need": np.zeros(J, np.int32),
               "job_req": np.zeros((J, R), np.float32),
               "job_acct": np.zeros((J, R), np.float32),
               "job_count": np.zeros(J, np.int32)}
    compiled = solve_evict_uniform.lower(
        _shapes(arr.device_dict(), lambda k: one),
        _shapes(victims, lambda k: one),
        _shapes(_params(arr), lambda k: one)).compile()
    mem = compiled.memory_analysis()
    assert mem is None or mem.temp_size_in_bytes < 16 * (1 << 30)
    assert "HloModule" in compiled.as_text()


@pytest.mark.parametrize("drf", [False, True], ids=["static", "drf"])
def test_solve_allocate_sharded_four_chips(topo, on_tpu, drf):
    """The node-axis sharded solve over the 4 described chips: each
    shard's 512-node width takes the Pallas kernel, and the cross-shard
    choice is an all-gather."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from volcano_tpu.parallel import solve_allocate_sharded

    mesh = Mesh(np.array(topo.devices), ("n",))
    assert mesh.devices.size == 4
    a, p = _north_star()
    node_rows = {"node_idle", "node_extra_future", "node_used",
                 "node_alloc", "node_npods", "node_max_pods", "node_valid"}

    def spec(k):
        if k == "sig_masks":
            return NamedSharding(mesh, P(None, "n"))
        if k in node_rows or k == "node_static":
            return NamedSharding(mesh, P("n"))
        return NamedSharding(mesh, P())

    compiled = solve_allocate_sharded.lower(
        _shapes(a, spec), _shapes(p, spec), mesh, herd_mode="pack",
        score_families=("binpack",), use_drf_order=drf,
        fused="auto").compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" in hlo
