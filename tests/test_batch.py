"""Shape-bucketed batched backend, compile-once padding, and the batched
Davidson update: equality with the list backend block-for-block, retrace
accounting, and the zero-fill / error paths of the block-gemm packer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import run_dmrg
from repro.core.davidson import davidson
from repro.core.models import heisenberg_j1j2_terms
from repro.core.siteops import spin_half_space
from repro.dist import ContractionEngine, PlanCache
from repro.dist.batch import (
    bucket_dim,
    matricize_lhs,
    matricize_rhs,
    pad_block_sparse,
    pad_index,
    unpad_block_sparse,
)
from repro.kernels.block_gemm.ops import block_sparse_matmul, pack_pairs
from repro.tensor import BlockSparseTensor, Index, OUT, contract

from test_dist import AX, rand_index, rand_pair


class TestBatchedBackend:
    """Batched == list block-for-block across random charge structures."""

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 10_000), nq=st.integers(1, 2))
    def test_property_equals_list(self, seed, nq):
        A, B = rand_pair(seed, nq=nq)
        eng = ContractionEngine(backend="batched", cache=PlanCache())
        got, ref = eng(A, B, AX), contract(A, B, AX)
        assert set(got.blocks) == set(ref.blocks)
        for k in ref.blocks:
            np.testing.assert_allclose(
                np.asarray(got.blocks[k]), np.asarray(ref.blocks[k]), atol=1e-13
            )

    def test_higher_order_and_jit(self):
        rng = np.random.default_rng(3)
        i1, i2, i3 = (rand_index(rng) for _ in range(3))
        A = BlockSparseTensor.random([i1, i2, i3], key=jax.random.PRNGKey(0))
        B = BlockSparseTensor.random(
            [i2.dual(), i3.dual(), i1], key=jax.random.PRNGKey(1)
        )
        ax = ((1, 2), (0, 1))
        ref = contract(A, B, axes=ax).to_dense()
        eng = ContractionEngine(backend="batched", cache=PlanCache())
        np.testing.assert_allclose(
            np.asarray(eng(A, B, ax).to_dense()), np.asarray(ref), atol=1e-12
        )
        jf = jax.jit(lambda a, b: eng(a, b, ax))
        np.testing.assert_allclose(
            np.asarray(jf(A, B).to_dense()), np.asarray(ref), atol=1e-12
        )

    def test_bucket_table_covers_pairs(self):
        from repro.dist.plan import ContractionPlan

        A, B = rand_pair(11)
        plan = ContractionPlan.build(A, B, AX)
        L = plan.batched
        total = sum(len(b.oi) for b in L.buckets)
        assert total == plan.num_pairs
        # every bucket's blocks matricize to exactly the bucket shape
        for b in L.buckets:
            for ka in b.a_keys:
                r, c = matricize_lhs(A, plan.keep_a, plan.ax_a)[ka].shape
                assert (r, c) == (b.m, b.k)
            for kb in b.b_keys:
                r, c = matricize_rhs(B, plan.keep_b, plan.ax_b)[kb].shape
                assert (r, c) == (b.k, b.n)
            assert list(b.oi) == sorted(b.oi)

    def test_precomputed_mats_match_live(self):
        A, B = rand_pair(5)
        eng = ContractionEngine(backend="batched", cache=PlanCache())
        plan = eng.cache.get(A, B, AX)
        mats_a = matricize_lhs(A, plan.keep_a, plan.ax_a)
        mats_b = matricize_rhs(B, plan.keep_b, plan.ax_b)
        got = eng(A, B, AX, a_mats=mats_a, b_mats=mats_b)
        ref = eng(A, B, AX)
        for k in ref.blocks:
            np.testing.assert_allclose(
                np.asarray(got.blocks[k]), np.asarray(ref.blocks[k]), atol=0
            )


class TestPadding:
    def test_bucket_dim_powers_of_two(self):
        assert [bucket_dim(d) for d in (1, 2, 3, 4, 5, 9, 17)] == [
            1, 2, 4, 4, 8, 16, 32,
        ]

    def test_pad_unpad_roundtrip(self):
        A, _ = rand_pair(7)
        padded = pad_block_sparse(A)
        padded.check()
        back = unpad_block_sparse(padded, A.indices)
        assert back.indices == A.indices
        assert set(back.blocks) == set(A.blocks)
        for k in A.blocks:
            np.testing.assert_allclose(
                np.asarray(back.blocks[k]), np.asarray(A.blocks[k]), atol=0
            )

    def test_dims_differing_within_bucket_pad_equal(self):
        """The compile-once property: structures that differ only by a
        sector dim inside one bucket become identical after padding."""
        ix13 = Index((((0,), 13), ((2,), 5)), OUT)
        ix14 = Index((((0,), 14), ((2,), 6)), OUT)
        assert pad_index(ix13) == pad_index(ix14)  # both -> ((0,),16),((2,),8)

    def test_padded_contraction_equals_padding_of_contraction(self):
        A, B = rand_pair(9)
        ref = contract(A, B, AX)
        Ap, Bp = pad_block_sparse(A), pad_block_sparse(B)
        got = unpad_block_sparse(contract(Ap, Bp, AX), ref.indices)
        assert set(got.blocks) == set(ref.blocks)
        for k in ref.blocks:
            np.testing.assert_allclose(
                np.asarray(got.blocks[k]), np.asarray(ref.blocks[k]), atol=1e-13
            )


class TestCompileOnceMatvec:
    def _system(self):
        sp = spin_half_space()
        terms = heisenberg_j1j2_terms(3, 2, 1.0, 0.5, cylinder=False)
        return sp, terms

    @pytest.mark.x64
    def test_batched_energy_equals_seed(self):
        sp, terms = self._system()
        kw = dict(bond_schedule=(8, 16), sweeps_per_bond=2, davidson_iters=6)
        seed = run_dmrg(sp, terms, 6, algo="list_unplanned", **kw)
        batched = run_dmrg(sp, terms, 6, algo="batched", **kw)
        assert abs(seed.energy - batched.energy) < 1e-10

    @pytest.mark.x64
    def test_batched_jit_pad_energy_equals_seed(self):
        sp, terms = self._system()
        kw = dict(bond_schedule=(8,), sweeps_per_bond=2, davidson_iters=4)
        seed = run_dmrg(sp, terms, 6, algo="list_unplanned", **kw)
        jit = run_dmrg(sp, terms, 6, algo="batched", jit_matvec=True, **kw)
        assert abs(seed.energy - jit.energy) < 1e-10

    def test_matvec_stops_retracing_after_warmup(self):
        """The bucketed jitted matvec compiles during warmup sweeps and then
        replays: once the block structure reaches steady state, a whole
        sweep triggers zero retraces."""
        from repro.core.mpo import build_mpo, compress_mpo
        from repro.core.mps import neel_states, product_state_mps
        from repro.core.sweep import DMRGEngine

        sp, terms = self._system()
        mpo = compress_mpo(build_mpo(sp, terms, 6), cutoff=1e-13)
        mps = product_state_mps(sp, neel_states(sp, 6))
        eng = DMRGEngine(mps, mpo, algo="batched", jit_matvec=True,
                         davidson_iters=2)
        for _ in range(4):
            eng.sweep(max_bond=8)
        assert eng.contract_fn.jit_retraces > 0  # it did compile
        before = eng.contract_fn.jit_retraces
        eng.sweep(max_bond=8)
        assert eng.contract_fn.jit_retraces == before  # compile-once reached


class TestEngineStats:
    def test_per_backend_counters(self):
        A, B = rand_pair(2)
        eng = ContractionEngine(backend="batched", cache=PlanCache())
        eng(A, B, AX)
        st_ = eng.stats()
        assert st_["backend_counts"]["batched"] == 1
        assert st_["backend_flops"]["batched"] > 0
        assert st_["jit_retraces"] == 0
        assert st_["backend_counts"]["list"] == 0

    def test_auto_includes_batched_candidate(self):
        A, B = rand_pair(2)
        eng = ContractionEngine(backend="auto", cache=PlanCache())
        plan = eng.cache.get(A, B, AX)
        assert eng.choose_backend(plan) in ("list", "dense", "batched")
        # with free dispatch, exact-flop backends win; with huge dispatch
        # cost, the bucketed backend must beat per-pair list dispatch
        expensive = ContractionEngine(
            backend="auto", cache=PlanCache(), pair_overhead=1e12
        )
        choice = expensive.choose_backend(plan)
        L = plan.batched
        if plan.num_pairs > 0.5 * L.num_unique + 2 * L.num_buckets + 0.25 * L.num_out_slots:
            assert choice != "list"


class TestDevIdxPerMesh:
    def test_dev_idx_keyed_per_policy_mesh(self):
        from repro.dist import BlockShardPolicy, make_block_mesh

        A, B = rand_pair(4)
        cache = PlanCache()
        eng = ContractionEngine(backend="batched", cache=cache)
        eng(A, B, AX)
        plan = cache.get(A, B, AX)
        assert set(plan.batched.dev_idx) == {None}
        policy = BlockShardPolicy(make_block_mesh(devices=jax.devices()[:1]))
        eng.policy = policy
        eng(A, B, AX)
        assert set(plan.batched.dev_idx) == {None, policy.mesh}


class TestPackPairsZeroFill:
    def test_out_of_range_raises(self):
        with pytest.raises(ValueError, match="output ids"):
            pack_pairs([(0, 0, 3)], 2)
        with pytest.raises(ValueError, match="empty"):
            pack_pairs([], 1)

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_uncovered_outputs_zero_filled(self, use_kernel):
        # 3 output slots, slot 1 has no contributing pair
        li, ri, oi = pack_pairs([(0, 0, 0), (1, 1, 2), (0, 1, 2)], 3)
        rng = np.random.default_rng(0)
        lhs = jnp.asarray(rng.normal(size=(3, 4, 5)))
        rhs = jnp.asarray(rng.normal(size=(3, 5, 6)))
        out = block_sparse_matmul(
            lhs[li], rhs[ri], oi, 3, use_kernel=use_kernel, interpret=True
        )
        assert out.shape == (3, 4, 6)
        np.testing.assert_allclose(np.asarray(out[1]), 0.0, atol=0)
        np.testing.assert_allclose(
            np.asarray(out[0]), np.asarray(lhs[0] @ rhs[0]), atol=1e-12
        )
        np.testing.assert_allclose(
            np.asarray(out[2]),
            np.asarray(lhs[1] @ rhs[1] + lhs[0] @ rhs[1]),
            atol=1e-12,
        )


class TestBatchedSubspaceDavidson:
    @pytest.mark.x64
    def test_matches_dense_eigensolver(self):
        """Gram-identity residual + fused column fetch reproduce the seed
        Davidson behavior: converges to the exact smallest eigenvalue."""
        ix = Index((((0,), 8),), OUT)  # single charge sector, dim 8
        H = BlockSparseTensor.random(
            [ix, ix.dual()], key=jax.random.PRNGKey(0)
        )
        blk = H.blocks[(0, 0)]
        H_sym = BlockSparseTensor(
            H.indices, {(0, 0): 0.5 * (blk + blk.T)}, H.charge
        )

        def mv(x):
            return contract(H_sym, x, ((1,), (0,)))

        x0 = BlockSparseTensor.random([ix], key=jax.random.PRNGKey(7))
        # with 8 iterations the subspace spans the whole 8-dim space
        lam, x, info = davidson(mv, x0, n_iter=8, tol=1e-12)
        evals = np.linalg.eigvalsh(np.asarray(H_sym.to_dense()))
        assert abs(lam - evals[0]) < 1e-8
        # returned vector is normalized and satisfies the eigen equation
        r = mv(x) - x.scale(lam)
        assert float(np.asarray(r.norm())) < 1e-6
        assert abs(float(np.asarray(x.norm())) - 1.0) < 1e-12

    def test_zero_iterations(self):
        ix = rand_index(np.random.default_rng(2))
        H = BlockSparseTensor.random([ix, ix.dual()], key=jax.random.PRNGKey(1))

        def mv(x):
            return contract(H, x, ((1,), (0,)))

        x0 = BlockSparseTensor.random([ix], key=jax.random.PRNGKey(3))
        lam, x, _ = davidson(mv, x0, n_iter=0)
        xn = x0.scale(1.0 / x0.norm())
        want = float(np.real(np.asarray(xn.inner(mv(xn)))))
        assert abs(lam - want) < 1e-12
