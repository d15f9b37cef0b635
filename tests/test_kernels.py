"""Per-kernel shape/dtype sweeps: Pallas (interpret) and jax kernels vs oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ops as ops
import repro.kernels.ref as ref

RNG = np.random.default_rng(42)


def _randn(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("b,s,h,hd", [
        (1, 128, 1, 32), (2, 256, 2, 64), (1, 512, 4, 128), (1, 384, 2, 64),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, b, s, h, hd, causal):
        q, k, v = (_randn((b, s, h, hd)) for _ in range(3))
        got = ops.flash_attention(q, k, v, causal=causal,
                                  block_q=128, block_kv=128)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        q, k, v = (_randn((1, 256, 2, 64), jnp.bfloat16) for _ in range(3))
        got = ops.flash_attention(q, k, v, block_q=128, block_kv=128)
        want = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_block_size_invariance(self):
        q, k, v = (_randn((1, 512, 1, 64)) for _ in range(3))
        a = ops.flash_attention(q, k, v, block_q=128, block_kv=128)
        b = ops.flash_attention(q, k, v, block_q=256, block_kv=512)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


class TestInt8Matmul:
    @pytest.mark.parametrize("m,k,n", [(64, 128, 64), (128, 256, 192), (256, 512, 128)])
    def test_exact_vs_ref(self, m, k, n):
        a = jnp.asarray(RNG.integers(-127, 128, (m, k)), jnp.int8)
        b = jnp.asarray(RNG.integers(-127, 128, (k, n)), jnp.int8)
        got = ops.int8_matmul(a, b, 0.02, 0.05, block_m=64, block_n=64, block_k=128)
        want = ref.int8_matmul_ref(a, b, 0.02, 0.05)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


class TestSSDScan:
    @pytest.mark.parametrize("nc,b,h,p,n", [(4, 1, 2, 8, 4), (8, 2, 4, 16, 8),
                                            (16, 1, 8, 32, 16)])
    def test_matches_sequential_ref(self, nc, b, h, p, n):
        s_chunk = _randn((nc, b, h, p, n))
        decay = jnp.asarray(RNG.uniform(0.3, 1.0, (nc, b, h)), jnp.float32)
        hp, hf = ops.ssd_scan(s_chunk, decay, block_bh=min(4, b * h))
        hp_r, hf_r = ref.ssd_scan_ref(s_chunk, decay)
        np.testing.assert_allclose(np.asarray(hp), np.asarray(hp_r), atol=1e-5)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(hf_r), atol=1e-5)


class TestMoeGMM:
    @pytest.mark.parametrize("e,c,d,f", [(2, 32, 64, 32), (4, 64, 128, 96),
                                         (8, 128, 64, 64)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_einsum(self, e, c, d, f, dtype):
        x = _randn((e, c, d), dtype)
        w = _randn((e, d, f), dtype, 0.1)
        got = ops.moe_gmm(x, w, block_c=32, block_f=32, block_d=64)
        want = ref.moe_gmm_ref(x, w)
        tol = 1e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


class TestWinogradConv:
    @pytest.mark.parametrize("b,hw,c,k", [(1, 8, 16, 16), (2, 12, 64, 64),
                                          (1, 16, 32, 48), (1, 7, 16, 16)])
    def test_matches_direct_conv(self, b, hw, c, k):
        x = _randn((b, hw, hw, c))
        w = _randn((3, 3, c, k), scale=0.1)
        got = ops.winograd_conv2d(x, w, block_t=32, block_k=16)
        want = ref.winograd_conv_ref(x, w)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-3)

    def test_tile_roundtrip(self):
        x = _randn((2, 10, 10, 4))
        t = ref.extract_winograd_tiles(x)
        assert t.shape == (2 * 5 * 5, 4, 4, 4)


class TestTreeGather:
    """jax tree gather (`repro.kernels.tree_gather`) vs the numpy oracle.

    Shapes reach the traversal's edges: depth 1 (the peeled root level
    alone, no loop step), single-row and single-tree banks, and row
    counts that are not a multiple of anything.
    """

    @pytest.mark.parametrize("rows,trees,depth", [
        (1, 1, 1), (7, 3, 2), (64, 10, 3), (257, 20, 4), (300, 130, 2),
    ])
    def test_matches_numpy_oracle(self, rows, trees, depth):
        from repro.core.predictors import GBDTPredictor

        rng = np.random.default_rng(rows + trees)
        x = np.abs(rng.standard_normal((120, 5))) * np.linspace(1, 20, 5)
        y = x @ rng.random(5) + 0.1
        m = GBDTPredictor(n_stages=trees, max_depth=depth).fit(x, y)
        q = np.abs(rng.standard_normal((rows, 5))) * np.linspace(1, 20, 5)
        flat = m.flat()
        xs = m.scaler.transform(q)
        want = flat.predict_trees(xs, backend="numpy")
        got = flat.predict_trees(xs, backend="jax")
        assert got.shape == want.shape == (rows, trees)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
