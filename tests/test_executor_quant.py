"""Executor modes (op-by-op / fused / whole-jit) and the int8 path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import (GraphExecutor, JitOp, build_op_fn, make_array,
                                 winograd_conv2d, winograd_transform_weights)
from repro.core.nas_space import NASSpaceConfig, sample_architecture
from repro.core.realworld import REALWORLD
from repro.quant.int8 import ACT_SCALE, dequantize, quantize_symmetric, rescale_int8


def test_modes_numerically_equivalent():
    g = sample_architecture(1, NASSpaceConfig(resolution=16))
    outs = {}
    for mode in ("op_by_op", "fused_groups", "whole_jit"):
        ex = GraphExecutor(g, mode)
        outs[mode] = np.asarray(ex(*ex.example_inputs())[0])
    np.testing.assert_allclose(outs["op_by_op"], outs["fused_groups"],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(outs["op_by_op"], outs["whole_jit"],
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("op_type", ["conv2d", "fully_connected", "split"])
def test_op_program_takes_weights_as_arguments(op_type):
    g = sample_architecture(1, NASSpaceConfig(resolution=16))
    node = next(n for n in g.nodes if n.op_type == op_type)
    fn, in_ids = build_op_fn(g, node)
    xs = [jnp.asarray(make_array(g.tensor(t).shape, "float32", 3 + i, 1.0))
          for i, t in enumerate(in_ids)]
    op = JitOp(fn)
    for a, b in zip(jax.tree.leaves(op(*xs)), jax.tree.leaves(fn(*xs))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # The weights are inputs of the compiled program, not constants in
    # it, so executables do not grow with the model.
    weights = op._run.args[0]
    assert bool(weights) == (op_type != "split")
    compiled = op._run.func.lower(weights, *xs).compile()
    assert compiled.memory_analysis().argument_size_in_bytes >= sum(
        a.nbytes for a in [*weights, *xs])


def test_fused_mode_reduces_kernel_count():
    g = REALWORLD.get("resnet18")(0.25, 16)
    ex_op = GraphExecutor(g, "op_by_op")
    ex_f = GraphExecutor(g, "fused_groups")
    assert ex_f.kernel_count() < ex_op.kernel_count()


@pytest.mark.parametrize("seed", [0, 2, 5])
def test_nas_architectures_execute(seed):
    g = sample_architecture(seed, NASSpaceConfig(resolution=16))
    ex = GraphExecutor(g, "op_by_op")
    (out,) = ex(*ex.example_inputs())
    assert out.shape == (1, 1000)
    assert np.isfinite(np.asarray(out)).all()


def test_int8_execution_shapes_and_finiteness():
    g = sample_architecture(3, NASSpaceConfig(resolution=16))
    ex = GraphExecutor(g, "op_by_op", dtype="int8")
    (out,) = ex(*ex.example_inputs())
    assert out.dtype == jnp.int8
    assert out.shape == (1, 1000)


def test_quantize_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((64,)) * 2,
                    jnp.float32)
    q = quantize_symmetric(x, ACT_SCALE)
    x2 = dequantize(q, ACT_SCALE)
    # |err| bounded by scale/2 except clipped values
    mask = np.abs(np.asarray(x)) < 4.0
    assert float(jnp.abs(x2 - x)[mask].max()) <= ACT_SCALE / 2 + 1e-6


def test_rescale_int8_is_scale_conversion():
    q = jnp.asarray([-100, -5, 0, 5, 100], jnp.int8)
    r = rescale_int8(q, 0.1, 0.2)
    np.testing.assert_array_equal(np.asarray(r), [-50, -2, 0, 2, 50])


def test_winograd_matches_direct_conv():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 16, 8)) * 0.1, jnp.float32)
    from jax import lax
    ref = lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                   dimension_numbers=("NHWC", "HWIO", "NHWC"))
    got = winograd_conv2d(x, winograd_transform_weights(w), 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-4)
