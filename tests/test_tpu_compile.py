"""AOT compiles of the served kernels for a described TPU v5e.

Nothing runs here: each test lowers a kernel with ``interpret=False`` for
a v5e topology that is described, not attached, and checks that the
chip's compiler accepts it and emits the Mosaic call (or, for the ring,
the collective).  Interpret mode, which every other kernel test uses,
cannot see a block the chip's tiling refuses or a kernel that needs more
fast memory than it may use; this file can.  Widths are those of the
paper's largest grid tuple: n = 16,384 points, features padded to 128
lanes, k = 8 centroids.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from repro.core import dbscan
from repro.core.distributed import make_ring_degree, make_ring_expand
from repro.kernels.distance.distance import assign_clusters_kernel
from repro.kernels.distance.fused import fused_step_kernel
from repro.kernels.neighbor.neighbor import degree_kernel, expand_kernel

N = 16_384
D_PAD = 128
K_PAD = 8
FEATURES = 4
EPS = 2.0          # DBSCAN paper default at 4 features


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _aval(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_assign_kernel_compiles_for_v5e(one_chip):
    x = _aval((N, D_PAD), jnp.float32, one_chip)
    c = _aval((K_PAD, D_PAD), jnp.float32, one_chip)
    _assert_mosaic(assign_clusters_kernel.lower(
        x, c, block_n=512, block_k=K_PAD, interpret=False).compile())


def test_fused_step_kernel_compiles_for_v5e(one_chip):
    x = _aval((N, D_PAD), jnp.float32, one_chip)
    c = _aval((K_PAD, D_PAD), jnp.float32, one_chip)
    w = _aval((N, 1), jnp.float32, one_chip)
    _assert_mosaic(fused_step_kernel.lower(
        x, c, w, block_n=512, interpret=False).compile())


def test_degree_kernel_compiles_for_v5e(one_chip):
    x = _aval((N, D_PAD), jnp.float32, one_chip)
    eps2 = _aval((), jnp.float32, one_chip)
    _assert_mosaic(degree_kernel.lower(x, eps2, interpret=False).compile())


def test_expand_kernel_compiles_for_v5e(one_chip):
    x = _aval((N, D_PAD), jnp.float32, one_chip)
    front = _aval((N, 1), jnp.float32, one_chip)
    eps2 = _aval((), jnp.float32, one_chip)
    _assert_mosaic(expand_kernel.lower(
        x, front, eps2, interpret=False).compile())


def test_dbscan_expansion_loop_compiles_for_v5e(one_chip, monkeypatch):
    """The Pallas expansion kernel survives inside the host loop's
    while-loop program."""
    from repro.kernels.neighbor import ops

    # the program asks the default backend, the CPU here, whether to
    # interpret its kernels; the described chip compiles them to Mosaic
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    x = _aval((N, FEATURES), jnp.float32, one_chip)
    flags = _aval((N,), jnp.bool_, one_chip)
    count = _aval((), jnp.int32, one_chip)
    carry = (_aval((N,), jnp.int32, one_chip), flags, flags, flags, count,
             count)
    cfg = dbscan.DBSCANConfig(eps=EPS, min_pts=10 * FEATURES)
    _assert_mosaic(dbscan._expand_steps.lower(
        x, flags, carry, count, cfg=cfg).compile())


@pytest.mark.parametrize("which", ["expand", "degree"])
def test_ring_compiles_on_four_v5e_chips(topo, which):
    mesh = Mesh(np.asarray(topo.devices), ("data",))
    assert mesh.devices.size == 4
    x = _aval((N, FEATURES), jnp.float32,
              NamedSharding(mesh, P("data", None)))
    if which == "expand":
        front = _aval((N,), jnp.bool_, NamedSharding(mesh, P("data")))
        compiled = make_ring_expand(mesh, EPS).lower(x, front).compile()
    else:
        compiled = make_ring_degree(mesh, EPS).lower(x).compile()
    # column shards rotate around the ring: one permute per step
    assert "collective-permute" in compiled.as_text()
