"""The fleet engine's Pallas kernels compile for a TPU v5e at real widths.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these tests need no chip: they catch what
interpret mode cannot (block shapes the tiling refuses, more VMEM or SMEM
than a kernel may use, operand layouts Mosaic rejects).  The topology is
described inside a fixture, never at import, because only one process at a
time may load the TPU library; where it cannot be described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import topology
from repro.kernels.mixing import ops as mixing_ops
from repro.kernels.trigger import ops as trigger_ops

# the m=16384 fleet at the svm's published width: dim=784, 10 classes
FLEET_M, FLEET_D = 16384, 7850
PAPER_M = 10
# mix_pallas tiles its contraction over m, so P is never whole in VMEM and
# m is bounded by HBM: P alone is 1 GiB in f32 here
DENSE_MAX_M = 16384


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU lib"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in there
    return compiled


def test_trigger_kernel_compiles_at_fleet_width(one_chip):
    w = jax.ShapeDtypeStruct((FLEET_M, FLEET_D), jnp.float32,
                             sharding=one_chip)
    _compile(lambda a, b: trigger_ops.trigger_sq(a, b, interpret=False), w, w)


@pytest.mark.parametrize("m", [PAPER_M, DENSE_MAX_M])
def test_dense_mix_kernel_compiles(one_chip, m):
    p = jax.ShapeDtypeStruct((m, m), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((m, FLEET_D), jnp.float32, sharding=one_chip)
    _compile(lambda a, b: mixing_ops.mix(a, b, interpret=False), p, w)


@pytest.mark.parametrize("d_max", [45, 46])
def test_gather_mix_kernel_compiles_at_fleet_width(one_chip, d_max):
    """d_max 45 is the m=16384 fleet rgg's neighbor-list width (46 at
    m=131072): the flat slot tables must still fill whole SMEM tiles."""
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    _compile(lambda i, pd, po, w: mixing_ops.mix_sparse(
                 i, pd, po, w, interpret=False),
             sd((FLEET_M, d_max), jnp.int32), sd((FLEET_M,)),
             sd((FLEET_M, d_max)), sd((FLEET_M, FLEET_D)))


def _sharded_engine_temp_bytes(topo, monkeypatch, m: int, shards: int) -> int:
    """Per-chip temporaries of the sharded engine compiled for ``shards``
    described v5e chips: the scan carry and everything the step makes."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.fl import sharded
    from repro.fl.simulator import SimConfig

    mesh = Mesh(np.array(topo.devices[:shards]), ("fl",),
                axis_types=(AxisType.Auto,))
    monkeypatch.setattr(sharded, "make_fleet_mesh", lambda s: mesh)
    sim = SimConfig(m=m, model="svm", dim=784, iters=4, trace="summary",
                    mix_impl="sharded", shards=shards)
    g = topology.make_process(m, "rgg", radius=topology.fleet_radius(m),
                              time_varying="edge_dropout", drop=0.3, seed=0)
    x = np.zeros((4 * m, 784), np.float32)
    eng, _, _ = sharded.make_sharded_engine(
        sim, g, T=sim.iters, eval_every=sim.iters, x=x,
        y=np.zeros(4 * m, np.int32))
    rep = NamedSharding(mesh, PartitionSpec())
    args = (jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((sim.iters, m, sim.batch), jnp.int32,
                                 sharding=rep))
    compiled = jax.jit(eng).lower(*args).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def test_sharded_engine_splits_the_fleet_over_four_chips(topo, monkeypatch):
    """On four chips each chip's compiled plan holds its own rows: at least
    w and w_hat for m/4 devices, and under a third of what one chip holds
    for the whole fleet.  The runtime's peak_bytes_in_use leaves these
    temporaries out, so this is where the split is seen."""
    m, d = 4096, 7850
    one = _sharded_engine_temp_bytes(topo, monkeypatch, m, 1)
    four = _sharded_engine_temp_bytes(topo, monkeypatch, m, 4)
    assert four >= 2 * (m // 4) * d * 4, (four, one)
    assert 3 * four < one, (four, one)


def test_fleet_neighbor_list_width_matches_compiled_shape():
    """The compiled d_max above is what the m=16384 fleet really has."""
    g = topology.make_process(FLEET_M, "rgg",
                              radius=topology.fleet_radius(FLEET_M),
                              time_varying="edge_dropout", drop=0.3, seed=0)
    assert g.neighbors().d_max == 45
