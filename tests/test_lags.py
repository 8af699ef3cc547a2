"""Exchange-strategy semantics: Algorithm 1 invariants, baselines parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compressors as C
from repro.core import lags


P_WORKERS = 4


def _tree(key, p=P_WORKERS):
    """Per-worker update pytree with leading (P,) axis (simulation layout)."""
    ks = jax.random.split(key, 3)
    return {
        "w1": jax.random.normal(ks[0], (p, 8, 16)),
        "w2": jax.random.normal(ks[1], (p, 50)),
        "b": jax.random.normal(ks[2], (p, 3)),
    }


def _unstacked(tree):
    return jax.tree.map(lambda x: x[0], tree)


class TestDenseExchange:
    def test_mean(self, rng):
        u = _tree(rng)
        exch = lags.DenseExchange()
        mean, _ = exch.exchange(u, exch.init(u), None)
        np.testing.assert_allclose(np.asarray(mean["w1"]),
                                   np.asarray(u["w1"].mean(0)), rtol=1e-6)


class TestLAGSAlgorithm1:
    def _exch(self, u, ratio):
        ks = lags.ks_from_ratio(_unstacked(u), ratio)
        return lags.LAGSExchange(ks=ks)

    def test_c1_equals_dense(self, rng):
        """Compression ratio 1 (k = d): LAGS reduces to Dense-SGD exactly."""
        u = _tree(rng)
        exch = self._exch(u, 1.0)
        mean, resid = exch.exchange(u, exch.init(u), None)
        dense, _ = lags.DenseExchange().exchange(u, (), None)
        for a, b in zip(jax.tree.leaves(mean), jax.tree.leaves(dense)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        for r in jax.tree.leaves(resid):
            np.testing.assert_allclose(np.asarray(r), 0.0, atol=1e-6)

    def test_error_feedback_invariant(self, rng):
        """acc = selected + residual per worker per leaf (lines 7-8)."""
        u = _tree(rng)
        exch = self._exch(u, 5.0)
        ef0 = jax.tree.map(
            lambda x: jax.random.normal(jax.random.fold_in(rng, x.size),
                                        x.shape), u)
        _, new_ef = exch.exchange(u, ef0, None)
        # recompute selected = acc - new_resid and check it has the top-k
        # support of acc
        for leaf_u, leaf_e, leaf_ne, k in zip(
                jax.tree.leaves(u), jax.tree.leaves(ef0),
                jax.tree.leaves(new_ef), jax.tree.leaves(exch.ks)):
            acc = np.asarray(leaf_e + leaf_u)
            sel = acc - np.asarray(leaf_ne)
            for p in range(P_WORKERS):
                a, s = acc[p].ravel(), sel[p].ravel()
                nz = s != 0
                assert nz.sum() <= k
                np.testing.assert_allclose(s[nz], a[nz], rtol=1e-6)
                if nz.any() and (~nz).any():
                    assert np.abs(a[nz]).min() >= np.abs(a[~nz]).max() - 1e-6

    def test_aggregation_is_scatter_mean(self, rng):
        """g_t = (1/P) sum_p TopK(acc_p, k) (lines 9-10)."""
        u = _tree(rng)
        exch = self._exch(u, 4.0)
        ef0 = exch.init(u)
        mean, new_ef = exch.exchange(u, ef0, None)
        for leaf_u, leaf_ne, leaf_m, k in zip(
                jax.tree.leaves(u), jax.tree.leaves(new_ef),
                jax.tree.leaves(mean), jax.tree.leaves(exch.ks)):
            acc = np.asarray(leaf_u)          # ef0 = 0
            sel = acc - np.asarray(leaf_ne)   # per-worker TopK(acc)
            expect = sel.mean(0)
            np.testing.assert_allclose(np.asarray(leaf_m), expect,
                                       rtol=1e-5, atol=1e-7)

    def test_residual_mass_decreases_information_loss(self, rng):
        """Second exchange with residuals shrinks the cumulative error: what
        was dropped at t is a candidate at t+1 (error feedback)."""
        u = _tree(rng)
        exch = self._exch(u, 10.0)
        ef0 = exch.init(u)
        _, ef1 = exch.exchange(u, ef0, None)
        zero_u = jax.tree.map(jnp.zeros_like, u)
        _, ef2 = exch.exchange(zero_u, ef1, None)
        n1 = sum(float(jnp.sum(e ** 2)) for e in jax.tree.leaves(ef1))
        n2 = sum(float(jnp.sum(e ** 2)) for e in jax.tree.leaves(ef2))
        assert n2 < n1  # feeding zero updates drains the residual


class TestBlockLAGS:
    def test_matches_leafwise_with_block_compressor(self, rng):
        """BlockLAGSExchange == LAGSExchange(topk_block) semantics."""
        u = _tree(rng)
        ks = lags.ks_from_ratio(_unstacked(u), 4.0)
        bsize = 32
        ex_block = lags.BlockLAGSExchange(ks=ks, block_size=bsize)
        ex_leaf = lags.LAGSExchange(
            ks=ks, compressor_name="topk_block",
            compressor_kwargs=(("block_size", bsize),))
        m1, e1 = ex_block.exchange(u, ex_block.init(u), None)
        m2, e2 = ex_leaf.exchange(u, ex_leaf.init(u), None)
        for a, b in zip(jax.tree.leaves(m1), jax.tree.leaves(m2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
        for a, b in zip(jax.tree.leaves(e1), jax.tree.leaves(e2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)

    def test_c1_equals_dense(self, rng):
        u = _tree(rng)
        ks = lags.ks_from_ratio(_unstacked(u), 1.0)
        exch = lags.BlockLAGSExchange(ks=ks, block_size=16)
        mean, resid = exch.exchange(u, exch.init(u), None)
        dense, _ = lags.DenseExchange().exchange(u, (), None)
        for a, b in zip(jax.tree.leaves(mean), jax.tree.leaves(dense)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


class TestSLGS:
    def test_global_topk_crosses_layers(self, rng):
        """SLGS budget concentrates on the leaf with large magnitudes —
        the structural difference from LAGS."""
        p = 2
        u = {"big": jnp.ones((p, 10)) * 100.0, "small": jnp.ones((p, 10))}
        exch = lags.SLGSExchange(k_total=10)
        mean, _ = exch.exchange(u, exch.init(u), None)
        assert float(jnp.abs(mean["big"]).sum()) > 0
        np.testing.assert_allclose(np.asarray(mean["small"]), 0.0)

    def test_single_leaf_equals_lags(self, rng):
        """With one layer, SLGS == LAGS by construction."""
        u = {"w": jax.random.normal(rng, (3, 40))}
        k = 8
        slgs = lags.SLGSExchange(k_total=k)
        lag = lags.LAGSExchange(ks={"w": k})
        m1, e1 = slgs.exchange(u, slgs.init(u), None)
        m2, e2 = lag.exchange(u, lag.init(u), None)
        np.testing.assert_allclose(np.asarray(m1["w"]), np.asarray(m2["w"]),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(e1["w"]), np.asarray(e2["w"]),
                                   rtol=1e-6)


class TestHierLAGS:
    def test_no_axes_is_local_topk(self, rng):
        u = _unstacked(_tree(rng))
        ks = lags.ks_from_ratio(u, 5.0)
        exch = lags.HierLAGSExchange(ks=ks, inner_axes=(), outer_axes=())
        mean, resid = exch.exchange(u, exch.init(u), None)
        for m, r, x in zip(jax.tree.leaves(mean), jax.tree.leaves(resid),
                           jax.tree.leaves(u)):
            np.testing.assert_allclose(np.asarray(m + r), np.asarray(x),
                                       rtol=1e-5, atol=1e-7)


class TestSparseHierLAGS:
    """Exchange-level checks for the two-level sparse hierarchy; the
    degeneracy parity family lives in test_distributed.py and the
    hypothesis battery in test_hier2_properties.py."""

    def test_c1_both_tiers_equals_dense(self, rng):
        u = _tree(rng)   # P=4 -> 2 pods x 2 inner workers
        ks = lags.ks_from_ratio(_unstacked(u), 1.0)
        exch = lags.SparseHierLAGSExchange(ks=ks, ks_inner=ks, n_inner=2)
        mean, resid = exch.exchange(u, exch.init(u), None)
        dense, _ = lags.DenseExchange().exchange(u, (), None)
        for a, b in zip(jax.tree.leaves(mean), jax.tree.leaves(dense)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        for tier in ("inner", "outer"):
            for r in jax.tree.leaves(resid[tier]):
                np.testing.assert_allclose(np.asarray(r), 0.0, atol=1e-6)

    def test_state_is_one_residual_tree_per_tier(self, rng):
        u = _tree(rng)
        ks = lags.ks_from_ratio(_unstacked(u), 4.0)
        exch = lags.SparseHierLAGSExchange(ks=ks, ks_inner=ks, n_inner=2)
        state = exch.init(u)
        assert set(state) == {"inner", "outer"}
        for tier in ("inner", "outer"):
            for e, x in zip(jax.tree.leaves(state[tier]),
                            jax.tree.leaves(u)):
                assert e.shape == x.shape and e.dtype == jnp.float32

    def test_bad_pod_factorization_raises(self, rng):
        u = _tree(rng)   # P=4
        ks = lags.ks_from_ratio(_unstacked(u), 2.0)
        exch = lags.SparseHierLAGSExchange(ks=ks, ks_inner=ks, n_inner=3)
        with pytest.raises(ValueError, match="n_inner"):
            exch.exchange(u, exch.init(u), None)


class TestKernelBackendParity:
    """selection_backend='kernel' exchange-level contract: with
    materialized (u, e) operands the kernel-backed compressors reproduce
    their XLA siblings BITWISE — values, indices (hence means), and EF
    residuals.  (Inside a larger jitted program XLA may contract u's
    producer into the accumulate — a 1-ulp drift that even makes the XLA
    path disagree with its own eager execution; the parity contract is
    pinned here, at the exchange boundary.)"""

    def _assert_bitwise(self, pair_a, pair_b):
        (m1, e1), (m2, e2) = pair_a, pair_b
        for a, b in zip(jax.tree.leaves(m1), jax.tree.leaves(m2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(e1), jax.tree.leaves(e2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("xla_name,kernel_name", [
        ("topk_exact", "topk_hier_ef_kernel"),   # small-d exact degeneracy
        ("topk_hier", "topk_hier_kernel"),
        ("topk_block", "topk_block_ef_kernel"),
    ])
    def test_lags_exchange_bitwise(self, rng, xla_name, kernel_name):
        u = _tree(rng)
        ks = lags.ks_from_ratio(_unstacked(u), 4.0)
        kw = (("block_size", 32),) if "block" in xla_name else ()
        ex_x = lags.LAGSExchange(ks=ks, compressor_name=xla_name,
                                 compressor_kwargs=kw)
        ex_k = lags.LAGSExchange(ks=ks, compressor_name=kernel_name,
                                 compressor_kwargs=kw)
        # seed both with the same nonzero residual state
        ef0 = jax.tree.map(
            lambda x: 0.1 * jax.random.normal(
                jax.random.fold_in(rng, x.size), x.shape), u)
        self._assert_bitwise(ex_x.exchange(u, ef0, None),
                             ex_k.exchange(u, ef0, None))
        # and under jit (materialized operands: parity must survive)
        jx = jax.jit(lambda uu, ee: ex_x.exchange(uu, ee, None))
        jk = jax.jit(lambda uu, ee: ex_k.exchange(uu, ee, None))
        self._assert_bitwise(jx(u, ef0), jk(u, ef0))

    def test_lags_exchange_bitwise_bf16_leaves(self, rng):
        u = jax.tree.map(lambda x: x.astype(jnp.bfloat16), _tree(rng))
        ks = lags.ks_from_ratio(_unstacked(u), 4.0)
        ex_x = lags.LAGSExchange(ks=ks, compressor_name="topk_exact")
        ex_k = lags.LAGSExchange(ks=ks,
                                 compressor_name="topk_hier_ef_kernel")
        ef0 = ex_x.init(u)   # f32 residuals regardless of update dtype
        self._assert_bitwise(ex_x.exchange(u, ef0, None),
                             ex_k.exchange(u, ef0, None))

    def test_wave_grouping_bitwise(self, rng):
        """Multi-wave exchange_bucket with a fused kernel compressor ==
        the monolithic exchange, leaf for leaf, bit for bit."""
        u = _tree(rng)
        ks = lags.ks_from_ratio(_unstacked(u), 4.0)
        exch = lags.LAGSExchange(ks=ks,
                                 compressor_name="topk_hier_ef_kernel")
        ef0 = exch.init(u)
        mean_mono, ef_mono = exch.exchange(u, ef0, None)
        flat_u, treedef = jax.tree.flatten(u)
        flat_e = jax.tree.leaves(ef0)
        waves = [(0, 2), (1,)]   # split + reordered leaf grouping
        means = [None] * len(flat_u)
        efs = [None] * len(flat_u)
        for wave in waves:
            ms, es = exch.exchange_bucket(
                wave, [flat_u[i] for i in wave],
                [flat_e[i] for i in wave], None)
            for j, i in enumerate(wave):
                means[i], efs[i] = ms[j], es[j]
        self._assert_bitwise(
            (mean_mono, ef_mono),
            (jax.tree.unflatten(treedef, means),
             jax.tree.unflatten(treedef, efs)))

    @pytest.mark.parametrize("surface,ratio,on_tpu,use_kernel,fused,cut", [
        ("sim", 4.0, False, True, True, None),   # selection_backend="kernel"
        ("manual", 16.0, True, False, True, None),  # TPU default: k_b 16
        ("manual", 2.0, True, False, True, None),   # k_b 128: lax.top_k in XLA
        ("manual", 1.0, True, False, False, 128),   # k_b 256 above the cut
        ("sim", 16.0, True, False, False, None),  # leading-P simulation path
        ("manual", 16.0, False, False, False, None),  # the CPU
    ])
    def test_block_lags_use_kernel_bitwise(self, rng, monkeypatch, surface,
                                           ratio, on_tpu, use_kernel,
                                           fused, cut):
        """The block exchange selects through the fused pass under
        ``use_kernel``, and by default on a TPU's manual path for k_b <=
        ``FUSED_MAX_KB``; wherever it does, means and residuals are bit
        for bit the XLA branch's.  The manual leaves take the in-place views (rows
        that are blocks, 128-lane rows, rows of 2.5 blocks) and the
        padded block view."""
        if surface == "sim":
            u, bs, axes = _tree(rng), 32, None
            ks = lags.ks_from_ratio(_unstacked(u), ratio)
        else:
            keys = jax.random.split(rng, 4)
            u = {"rows": jax.random.normal(keys[0], (6, 256)),
                 "lanes": jax.random.normal(keys[1], (4, 4, 128)),
                 "wide": jax.random.normal(keys[2], (8, 640)),
                 "odd": jax.random.normal(keys[3], (5, 300))}
            bs, axes = 256, ()
            ks = lags.ks_from_ratio(u, ratio)
        ef0 = jax.tree.map(
            lambda x: 0.5 * jax.random.normal(jax.random.fold_in(rng, x.size),
                                              x.shape), u)
        ex_x = lags.BlockLAGSExchange(ks=ks, block_size=bs)
        want = ex_x.exchange(u, ef0, axes)
        calls = []
        pack = lags.kops._ef.ef_select_pack_pallas

        def spy(u, e, thr=None, *, interpret, **kw):
            # the program the backend gets, the kernel in interpret mode
            calls.append(kw.get("block_size"))
            return pack(u, e, thr, interpret=True, **kw)

        monkeypatch.setattr(lags.kops._ef, "ef_select_pack_pallas", spy)
        monkeypatch.setattr(lags.kops, "on_tpu", lambda: on_tpu)
        if cut is not None:
            monkeypatch.setattr(lags, "FUSED_MAX_KB", cut)
        ex_k = lags.BlockLAGSExchange(ks=ks, block_size=bs,
                                      use_kernel=use_kernel)
        self._assert_bitwise(want, ex_k.exchange(u, ef0, axes))
        assert bool(calls) == fused
        if fused and surface == "manual":
            # in place: "rows", "lanes" and "wide"; block view: "odd"
            assert sorted(calls, key=str) == [256, 256, 256, None]

    def test_slgs_kernel_bitwise(self, rng):
        u = _tree(rng)
        ex_x = lags.SLGSExchange(k_total=40)
        ex_k = lags.SLGSExchange(k_total=40,
                                 compressor_name="topk_hier_ef_kernel")
        ef0 = ex_x.init(u)
        self._assert_bitwise(ex_x.exchange(u, ef0, None),
                             ex_k.exchange(u, ef0, None))

    def test_hier2_kernel_inner_tier_bitwise(self, rng):
        """Block-parallel (kernel) inner tier == the XLA inner tier on
        the sim surface, both tiers' residuals included."""
        u = _tree(rng)   # P=4 -> 2 pods x 2
        ks = lags.ks_from_ratio(_unstacked(u), 8.0)
        ks_in = lags.ks_from_ratio(_unstacked(u), 2.0)
        ex_x = lags.SparseHierLAGSExchange(ks=ks, ks_inner=ks_in, n_inner=2)
        ex_k = lags.SparseHierLAGSExchange(
            ks=ks, ks_inner=ks_in, n_inner=2,
            compressor_name="topk_hier_ef_kernel",
            inner_compressor_name="topk_hier_ef_kernel")
        ef0 = ex_x.init(u)
        self._assert_bitwise(ex_x.exchange(u, ef0, None),
                             ex_k.exchange(u, ef0, None))


class TestKBookkeeping:
    def test_ks_from_ratio(self):
        tree = {"a": jnp.zeros((10, 10)), "b": jnp.zeros((7,))}
        ks = lags.ks_from_ratio(tree, 10.0)
        assert ks == {"a": 10, "b": 1}

    def test_ks_floor_one(self):
        tree = {"tiny": jnp.zeros((3,))}
        assert lags.ks_from_ratio(tree, 1000.0) == {"tiny": 1}
