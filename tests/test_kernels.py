"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

The kernels execute in interpret mode on CPU — the exact TPU program body
runs in Python per grid step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests need hypothesis; the oracle sweeps below do not
    from hypothesis import given, settings, strategies as st
except ImportError:  # minimal envs: skip just the property tests
    from conftest import given, settings, st

from repro.kernels import ops, ref


SHAPES = [(1, 128), (7, 256), (8, 512), (16, 1024), (33, 4096), (3, 130)]
DTYPES = [jnp.float32, jnp.bfloat16]


class TestBlockTopK:
    @pytest.mark.parametrize("shape", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("r", [1, 4, 8])
    def test_matches_oracle(self, shape, dtype, r):
        n, bs = shape
        r = min(r, bs)
        x = jax.random.normal(jax.random.PRNGKey(n * bs + r), shape, dtype)
        v, i = ops.block_topk(x, r)
        vr, ir = ref.block_topk_ref(x, r)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
        np.testing.assert_allclose(np.asarray(v, np.float32),
                                   np.asarray(vr, np.float32), rtol=1e-6)

    def test_tie_break_lowest_index(self):
        x = jnp.array([[1.0, -1.0, 1.0, 0.5]])
        v, i = ops.block_topk(x, 2)
        # |1.0| three-way tie -> indices 0 then 1
        assert np.asarray(i).tolist() == [[0, 1]]
        assert np.asarray(v).tolist() == [[1.0, -1.0]]

    def test_values_keep_sign(self):
        x = jnp.array([[-5.0, 1.0, 2.0, -3.0]])
        v, i = ops.block_topk(x, 2)
        assert np.asarray(v).tolist() == [[-5.0, -3.0]]

    @given(n=st.integers(1, 20), bs=st.sampled_from([128, 256]),
           r=st.integers(1, 6), seed=st.integers(0, 2**16))
    @settings(max_examples=20, deadline=None)
    def test_property_sweep(self, n, bs, r, seed):
        x = jax.random.normal(jax.random.PRNGKey(seed), (n, bs))
        v, i = ops.block_topk(x, r)
        vr, ir = ref.block_topk_ref(x, r)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
        np.testing.assert_allclose(np.asarray(v), np.asarray(vr), rtol=1e-6)


class TestEfSparsify:
    @pytest.mark.parametrize("d", [100, 1024, 5000, 70000])
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_oracle(self, d, dtype):
        g = jax.random.normal(jax.random.PRNGKey(d), (d,), dtype)
        e = jax.random.normal(jax.random.PRNGKey(d + 1), (d,), jnp.float32)
        for lr, thr in [(0.1, 0.5), (1.0, 0.0), (0.01, 2.0)]:
            sel, res = ops.ef_accum_sparsify(g, e, lr, thr)
            selr, resr = ref.ef_accum_sparsify_ref(g, e, lr, thr)
            np.testing.assert_allclose(np.asarray(sel), np.asarray(selr),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(res), np.asarray(resr),
                                       rtol=1e-6, atol=1e-6)

    def test_selected_plus_residual_is_acc(self, rng):
        """The fused kernel preserves Algorithm 1's exact EF split."""
        d = 3000
        g = jax.random.normal(rng, (d,))
        e = jax.random.normal(jax.random.fold_in(rng, 1), (d,))
        sel, res = ops.ef_accum_sparsify(g, e, 0.3, 0.7)
        acc = np.asarray(e) + 0.3 * np.asarray(g)
        np.testing.assert_allclose(np.asarray(sel) + np.asarray(res), acc,
                                   rtol=1e-6, atol=1e-6)

    def test_threshold_semantics(self, rng):
        d = 500
        g = jax.random.normal(rng, (d,))
        e = jnp.zeros((d,))
        sel, _ = ops.ef_accum_sparsify(g, e, 1.0, 1.5)
        sel = np.asarray(sel)
        gv = np.asarray(g)
        assert ((np.abs(gv) >= 1.5) == (sel != 0)).all()


class TestEfSelectPack:
    """Fused select -> residual-update -> payload-pack kernel vs the
    pure-jnp oracle, bit for bit.  The wrapper rounds ``lr*g`` before the
    kernel adds it (interpret-mode Pallas would contract ``e + lr*g``
    into one fma, 1 ulp off XLA's separate mul+add); the exchanges pass
    pre-scaled updates at lr=1, where nothing is scaled."""

    @pytest.mark.parametrize("shape", [
        (1, 64), (7, 256), (8, 512), (3, 130),
        # (rows, cols, block_size, k, lr, fill) at the TPU's geometries
        pytest.param((1, 4096, 4096, 5, 1.0, "normal"), id="norm-leaf"),
        pytest.param((13, 4096, 4096, 5, 1.0, "normal"), id="rows13"),
        pytest.param((40, 4096, 4096, 1, 1.0, "ties"), id="rows40-k1"),
        pytest.param((20, 4096, 4096, 4, 0.3, "zeros"), id="k4-lr"),
        pytest.param((9, 4096, 4096, 32, 1.0, "ties"), id="k32"),
        pytest.param((320, 128, 4096, 5, 1.0, "zeros"), id="lanes128"),
        pytest.param((72, 128, 1024, 4, 0.3, "ties"), id="lanes128-lr"),
        pytest.param((16, 1792, 512, 3, 1.0, "ties"), id="rows-of-3.5"),
        pytest.param((10, 640, 256, 5, 1.0, "zeros"), id="rows-of-2.5"),
    ])
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("thr", [None, 0.5])
    def test_bitwise_oracle_at_unit_lr(self, shape, dtype, thr):
        """``shape`` is (rows, bs) with k = bs/8, or a full case: a
        (rows, cols) view cut into blocks of ``block_size`` (rows that do
        not fill the kernel's tiles; 128-lane rows and rows of several
        blocks or of a block and a half read in place), with planted ties
        of equal magnitude and opposite sign, or all-zero rows."""
        n, cols, bs, k, lr, fill = (
            shape if len(shape) == 6
            else (*shape, shape[1], max(1, shape[1] // 8), 1.0, "normal"))
        g = jax.random.normal(jax.random.PRNGKey(n * cols), (n, cols), dtype)
        e = jax.random.normal(jax.random.PRNGKey(n * cols + 1), (n, cols),
                              jnp.float32)
        if fill == "ties":
            # 2.5 and -2.5 (exact in bf16) tie at the top of every block
            g = g.at[:, 3].set(2.5).at[:, 7].set(-2.5).at[:, 9].set(-2.5)
            e = e.at[:, 3].set(0.0).at[:, 7].set(0.0).at[:, 9].set(0.0)
        elif fill == "zeros":
            g = g.at[: n // 2].set(0.0)
            e = e.at[: n // 2].set(0.0)
        v, i, r = ops.ef_select_pack_rows(g, e, lr, thr, k, block_size=bs)
        vr, ir, rr = ref.ef_select_pack_ref(g.reshape(-1, bs),
                                            e.reshape(-1, bs), lr, thr, k)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(vr))
        np.testing.assert_array_equal(np.asarray(r).reshape(-1, bs),
                                      np.asarray(rr))

    def test_nonunit_lr_allclose(self, rng):
        g = jax.random.normal(rng, (5, 256))
        e = jax.random.normal(jax.random.fold_in(rng, 1), (5, 256))
        v, i, r = ops.ef_select_pack_rows(g, e, 0.3, None, 16)
        vr, ir, rr = ref.ef_select_pack_ref(g, e, 0.3, None, 16)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
        np.testing.assert_allclose(np.asarray(v), np.asarray(vr), atol=1e-5)
        np.testing.assert_allclose(np.asarray(r), np.asarray(rr), atol=1e-5)

    def test_ef_invariant(self, rng):
        """scatter(vals, idx) + residual == e + lr*g, exactly."""
        g = jax.random.normal(rng, (4, 128))
        e = jax.random.normal(jax.random.fold_in(rng, 1), (4, 128))
        v, i, r = ops.ef_select_pack_rows(g, e, 1.0, None, 8)
        acc = np.asarray(e) + np.asarray(g)
        recon = np.asarray(r).copy()
        for row in range(4):
            np.add.at(recon[row], np.asarray(i)[row], np.asarray(v)[row])
        np.testing.assert_array_equal(recon, acc)

    def test_block_pack_matches_xla_topk_block_bitwise(self, rng):
        """ef_block_pack == the XLA topk_block path on acc = e + u:
        same values, indices, AND residual, bit for bit."""
        from repro.core import compressors as C
        d, k, bs = 2000, 64, 512
        u = jax.random.normal(rng, (d,))
        e = 0.1 * jax.random.normal(jax.random.fold_in(rng, 1), (d,))
        v, i, r = ops.ef_block_pack(u, e, 1.0, k, block_size=bs)
        acc = e + u
        vx, ix = C.topk_block_compress(acc, k, block_size=bs)
        rx = acc - C.decompress(vx, ix, d)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ix))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(vx))
        np.testing.assert_array_equal(np.asarray(r), np.asarray(rx))

    def test_hier_pack_small_d_degenerates_exact(self, rng):
        """d <= block_size: the fused hier pack IS exact fused top-k,
        bitwise equal to topk_exact on acc."""
        from repro.core import compressors as C
        d, k = 100, 10
        u = jax.random.normal(rng, (d,))
        e = 0.1 * jax.random.normal(jax.random.fold_in(rng, 2), (d,))
        v, i, r = ops.ef_hier_pack(u, e, 1.0, k, block_size=4096)
        acc = e + u
        vx, ix = C.topk_exact_compress(acc, k)
        rx = acc - C.decompress(vx, ix, d)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ix))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(vx))
        np.testing.assert_array_equal(np.asarray(r), np.asarray(rx))

    def test_hier_pack_large_d_ef_invariant_and_budget(self, rng):
        """Multi-block hier path: EF invariant holds exactly; selection
        bias (<= r per block, threshold ties) stays in the residual."""
        d, k, bs, r_cand = 10000, 100, 1024, 8
        u = jax.random.normal(rng, (d,))
        e = 0.1 * jax.random.normal(jax.random.fold_in(rng, 3), (d,))
        v, i, r = ops.ef_hier_pack(u, e, 1.0, k, block_size=bs, r=r_cand)
        i_np, v_np = np.asarray(i), np.asarray(v)
        assert (i_np >= 0).all() and (i_np < d).all()
        acc = np.asarray(e + u)
        recon = np.asarray(r).copy()
        np.add.at(recon, i_np, v_np)
        np.testing.assert_array_equal(recon, acc)
        assert (v_np != 0).sum() <= -(-d // bs) * r_cand

    def test_hier_pack_short_tail_block_indices_in_range(self, rng):
        """Regression: padded tail block (d = 1026, bs = 1024) must not
        emit candidate/selected indices >= d."""
        d = 1026
        u = jax.random.normal(rng, (d,))
        e = jnp.zeros((d,))
        _, i, _ = ops.ef_hier_pack(u, e, 1.0, 32, block_size=1024, r=8)
        i_np = np.asarray(i)
        assert (i_np >= 0).all() and (i_np < d).all()


class TestHierThreshold:
    def test_threshold_reproduces_topk_count(self, rng):
        """thr from the candidate set keeps <= k elements (never more)."""
        x = jax.random.normal(rng, (20000,))
        for k in [10, 100, 1000]:
            thr, _ = ops.hier_topk_threshold(x, k, block_size=1024, r=8)
            kept = int((np.abs(np.asarray(x)) >= float(thr)).sum())
            assert kept <= k + 8  # ties at thr may add a few

    def test_short_tail_block_candidates_in_range(self, rng):
        """Regression: with a padded tail block (d=1026, bs=1024) the
        candidate indices used to run past d (base + local of the -inf
        padding lanes); they must be clamped into range."""
        x = jax.random.normal(rng, (1026,))
        _, (_, cand_idx) = ops.hier_topk_threshold(x, 32, block_size=1024,
                                                   r=8)
        ci = np.asarray(cand_idx)
        assert (ci >= 0).all() and (ci < 1026).all()

    def test_kernel_and_jnp_hier_identical(self, rng):
        from repro.core import compressors as C
        x = jax.random.normal(rng, (8192,))
        v1, i1 = C.topk_hier_compress(x, 64, block_size=512, r=8,
                                      use_kernel=True)
        v2, i2 = C.topk_hier_compress(x, 64, block_size=512, r=8,
                                      use_kernel=False)
        assert set(np.asarray(i1).tolist()) == set(np.asarray(i2).tolist())

    def test_kernel_and_jnp_block_identical(self, rng):
        from repro.core import compressors as C
        x = jax.random.normal(rng, (8192,))
        v1, i1 = C.topk_block_compress(x, 64, block_size=512,
                                       use_kernel=True)
        v2, i2 = C.topk_block_compress(x, 64, block_size=512,
                                       use_kernel=False)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6)
