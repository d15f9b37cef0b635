"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: each case lowers and compiles a program for a `v5e:2x2`
topology that is described, not attached, so what the chip's compiler
would refuse fails here first, on a host without a TPU.

Cases: the fused tree traversal that serves flushes, at the chip
smoke's bank size and at a larger one; every distinct op kind of
MobileNetV2 (width 1.0, 224×224) as the profiler builds it; and the
row-sharded fused flush on a four-device mesh.

The topology is described inside a module-scoped fixture, never while
a module is imported, and all cases stay in this one file: only one
process may load the TPU library, and it keeps it until it exits.
"""
import os
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core.executor import build_op_fn
from repro.core.realworld import mobilenet_v2
from repro.kernels.tree_gather import DeviceBank, _fused, _fused_core

# GBDT at the chip smoke's hyperparameters (150 stages, depth 4) over
# ~16 features, and a larger bank; (rows, trees, nodes, features, depth).
BANKS = {"smoke_bank": (8192, 150, 150 * 31, 16, 4),
         "large_bank": (65536, 400, 400 * 63, 24, 6)}

MNV2 = mobilenet_v2(1.0, 224)


def _kind(node) -> str:
    p = node.params_dict
    return "_".join(str(v) for v in (node.op_type, p.get("kernel_h"),
                                     p.get("stride"), p.get("act"),
                                     p.get("n_inputs", 1)))


MNV2_KINDS = {}
for _node in MNV2.nodes:
    MNV2_KINDS.setdefault(_kind(_node), _node)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _bank_shapes(sharding, rows, trees, nodes, features):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    bank = (s((nodes,), jnp.int32), s((nodes,), jnp.float32),
            s((nodes,), jnp.int32), s((nodes,), jnp.int32),
            s((nodes,), jnp.float32), s((trees,), jnp.int32),
            s((), jnp.float32), s((), jnp.float32))
    return bank, s


@pytest.mark.parametrize("bank", sorted(BANKS))
def test_fused_traversal_compiles(one_chip, bank):
    rows, trees, nodes, features, depth = BANKS[bank]
    args, s = _bank_shapes(one_chip, rows, trees, nodes, features)
    x = s((rows, features), jnp.float32)
    compiled = _fused.lower(*args, x, depth=depth, kind="sum").compile()
    assert compiled.out_info.shape == (rows,)


@pytest.mark.parametrize("kind", sorted(MNV2_KINDS))
def test_mobilenet_v2_op_compiles(one_chip, kind):
    node = MNV2_KINDS[kind]
    fn, in_ids = build_op_fn(MNV2, node)
    args = [jax.ShapeDtypeStruct(MNV2.tensor(t).shape, jnp.float32,
                                 sharding=one_chip) for t in in_ids]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.out_info.shape == tuple(MNV2.tensor(node.outputs[0]).shape)


def test_sharded_fused_flush_compiles(topo):
    mesh = Mesh(np.array(topo.devices[:4]), ("rows",))
    rows, trees, nodes, features, depth = BANKS["large_bank"]
    repl = NamedSharding(mesh, jax.sharding.PartitionSpec())
    args, _ = _bank_shapes(repl, rows, trees, nodes, features)
    x = jax.ShapeDtypeStruct((rows, features), jnp.float32,
                             sharding=NamedSharding(
                                 mesh, jax.sharding.PartitionSpec("rows")))
    db = DeviceBank()
    db.mesh = mesh
    fn = db._sharded_fn(("fused", depth, "sum"),
                        partial(_fused_core, depth=depth, kind="sum"),
                        out_rank2=False)
    compiled = fn.lower(*args, x).compile()
    assert compiled.out_info.shape == (rows,)
    assert compiled.out_info.sharding.spec == jax.sharding.PartitionSpec("rows")
