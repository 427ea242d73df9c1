"""Decomposition engine: planned batched SVD vs the seed per-sector loop
(block-for-block up to sign gauge, gauge-invariant products exactly),
truncation-error accounting, absorb gauge agreement, deterministic exact-tie
truncation, the randomized path, and plan-cache / retrace semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import run_dmrg
from repro.core.models import heisenberg_j1j2_terms
from repro.core.siteops import spin_half_space
from repro.dist import ContractionEngine, DecompositionEngine, DecompPlanCache
from repro.dist.decomp import svd_split_planned
from repro.dist.plan import DecompositionPlan, decomp_signature
from repro.tensor import (
    BlockSparseTensor,
    IN,
    Index,
    OUT,
    contract,
    svd_split,
    svd_split_unplanned,
)

from test_dist import rand_index


def rand_theta(seed, nq=1, n_modes=4, n_row_modes=2):
    """Random 4-mode theta with a bra-like first mode, as in a DMRG pair."""
    for s in range(seed, seed + 50):
        rng = np.random.default_rng(s)
        flows = (IN,) + (OUT,) * (n_modes - 1)
        ixs = [rand_index(rng, nq=nq, flow=f) for f in flows]
        t = BlockSparseTensor.random(ixs, key=jax.random.PRNGKey(s))
        if t.num_blocks > 1:
            return t
    raise RuntimeError("no non-trivial theta found")


def recon(U, V, n_row_modes=2):
    """Dense U·V product over the bond — the gauge-invariant part of a split."""
    return np.asarray(
        contract(U, V, axes=((n_row_modes,), (0,))).to_dense()
    )


def align_sign_gauge(U_ref, V_ref, U, V):
    """Flip U columns / V rows of (U, V) so the bond gauge matches the
    reference split.  LAPACK's singular-vector sign choice is unspecified,
    so two numerically different-but-equal computations may differ by a
    diag(±1) on the bond; this removes exactly that freedom."""
    bond_ax = U.ndim - 1
    bond = U.indices[bond_ax]
    u_blocks, v_blocks = dict(U.blocks), dict(V.blocks)
    for s in range(bond.num_sectors):
        m = bond.sector_dim(s)
        dots = np.zeros(m)
        for k, b in U.blocks.items():
            if k[bond_ax] != s or k not in U_ref.blocks:
                continue
            dots += np.sum(
                np.asarray(U_ref.blocks[k]).reshape(-1, m)
                * np.asarray(b).reshape(-1, m),
                axis=0,
            )
        flip = np.where(dots < 0, -1.0, 1.0)
        for k in list(u_blocks):
            if k[bond_ax] == s:
                u_blocks[k] = u_blocks[k] * flip
        for k in list(v_blocks):
            if k[0] == s:
                v_blocks[k] = v_blocks[k] * flip.reshape((-1,) + (1,) * (V.ndim - 1))
    return (
        BlockSparseTensor(U.indices, u_blocks, U.charge),
        BlockSparseTensor(V.indices, v_blocks, V.charge),
    )


class TestDecompPlan:
    def test_cache_hit_miss_semantics(self):
        theta = rand_theta(0)
        cache = DecompPlanCache()
        p1 = cache.get(theta, 2)
        assert cache.stats() == {"hits": 0, "misses": 1, "evictions": 0, "size": 1, "builds": 1}
        # same structure, different numbers -> hit (signature is structural)
        theta2 = BlockSparseTensor(
            theta.indices, {k: 2.0 * b for k, b in theta.blocks.items()}, theta.charge
        )
        assert cache.get(theta2, 2) is p1
        assert cache.stats()["hits"] == 1
        # a different split point is a different plan
        cache.get(theta, 1)
        assert cache.misses == 2
        assert decomp_signature(theta, 1) != decomp_signature(theta, 2)

    def test_gather_tables_reproduce_seed_assembly(self):
        """The plan's single-gather assembly must produce exactly the padded
        embedding of the sector matrices the seed builds block-by-block."""
        theta = rand_theta(3)
        plan = DecompositionPlan.build(theta, 2)
        flat = np.concatenate(
            [np.asarray(theta.blocks[k]).reshape(-1) for k in plan.block_order]
            + [np.zeros(1)]
        )
        for bucket in plan.buckets:
            mats = flat[bucket.gather]
            for slot, si in enumerate(bucket.sectors):
                sec = plan.sectors[si]
                # rebuild the seed's [R, C] sector matrix
                ref = np.zeros((sec.R, sec.C))
                import repro.tensor.qn as qn

                for k in theta.blocks:
                    rk, ck = k[:2], k[2:]
                    if rk not in sec.row_keys or ck not in sec.col_keys:
                        continue
                    # only blocks whose fused row charge is this sector
                    qk = qn.qzero(theta.indices[0].nq)
                    for ix, sct in zip(theta.indices[:2], rk):
                        qk = qn.qadd(qk, qn.qscale(ix.charge(sct), ix.flow))
                    if qk != sec.q:
                        continue
                    ri = sec.row_keys.index(rk)
                    ci = sec.col_keys.index(ck)
                    ref[
                        sec.roffs[ri] : sec.roffs[ri] + sec.rdims[ri],
                        sec.coffs[ci] : sec.coffs[ci] + sec.cdims[ci],
                    ] = np.asarray(theta.blocks[k]).reshape(
                        sec.rdims[ri], sec.cdims[ci]
                    )
                got = mats[slot]
                np.testing.assert_allclose(got[: sec.R, : sec.C], ref, atol=0)
                # padding region is exactly zero
                assert np.all(got[sec.R :, :] == 0) and np.all(got[:, sec.C :] == 0)

    def test_every_sector_in_exactly_one_bucket_slot(self):
        theta = rand_theta(7)
        plan = DecompositionPlan.build(theta, 2)
        seen = sorted(si for b in plan.buckets for si in b.sectors)
        assert seen == list(range(plan.num_sectors))
        for si, sec in enumerate(plan.sectors):
            b = plan.buckets[sec.bucket]
            assert b.sectors[sec.slot] == si
            assert b.rp >= sec.R and b.cp >= sec.C


@pytest.mark.x64
class TestPlannedEqualsUnplanned:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000), max_bond=st.integers(1, 12))
    def test_property_block_for_block_up_to_gauge(self, seed, max_bond):
        theta = rand_theta(seed)
        ref = svd_split_unplanned(theta, 2, max_bond=max_bond, cutoff=0.0)
        got = svd_split(theta, 2, max_bond=max_bond, cutoff=0.0)
        U_r, V_r, sv_r, err_r = ref
        U_p, V_p, sv_p, err_p = got
        # identical bond structure, block keys and singular values
        assert U_p.indices == U_r.indices and V_p.indices == V_r.indices
        assert set(U_p.blocks) == set(U_r.blocks)
        assert set(V_p.blocks) == set(V_r.blocks)
        assert set(sv_p) == set(sv_r)
        for q in sv_r:
            np.testing.assert_allclose(
                np.asarray(sv_p[q]), np.asarray(sv_r[q]), atol=1e-10
            )
        assert abs(err_p - err_r) < 1e-10
        # block-for-block after removing the singular-vector sign freedom
        U_a, V_a = align_sign_gauge(U_r, V_r, U_p, V_p)
        for k in U_r.blocks:
            np.testing.assert_allclose(
                np.asarray(U_a.blocks[k]), np.asarray(U_r.blocks[k]), atol=1e-10
            )
        for k in V_r.blocks:
            np.testing.assert_allclose(
                np.asarray(V_a.blocks[k]), np.asarray(V_r.blocks[k]), atol=1e-10
            )

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000), max_bond=st.integers(1, 10))
    def test_property_trunc_err_is_squared_reconstruction_error(
        self, seed, max_bond
    ):
        theta = rand_theta(seed)
        dense = np.asarray(theta.to_dense())
        for split in (svd_split, svd_split_unplanned):
            U, V, _, err = split(theta, 2, max_bond=max_bond, cutoff=0.0)
            actual = float(np.sum(np.abs(recon(U, V) - dense) ** 2))
            np.testing.assert_allclose(actual, err, rtol=1e-8, atol=1e-12)

    def test_absorb_left_right_agree_up_to_gauge(self):
        theta = rand_theta(5)
        U_r, V_r, sv_r, err_r = svd_split(theta, 2, max_bond=6, absorb="right")
        U_l, V_l, sv_l, err_l = svd_split(theta, 2, max_bond=6, absorb="left")
        # the absorbed product, the retained sectors, the singular values and
        # the truncation error are all gauge-invariant and must agree
        np.testing.assert_allclose(recon(U_r, V_r), recon(U_l, V_l), atol=1e-11)
        assert U_r.indices[-1] == U_l.indices[-1]
        assert err_r == err_l
        for q in sv_r:
            np.testing.assert_allclose(
                np.asarray(sv_r[q]), np.asarray(sv_l[q]), atol=1e-12
            )
        # and each side is isometric on its unabsorbed factor
        gram = contract(U_l.conj(), U_l, axes=((0, 1), (0, 1))).to_dense()
        s_sq = np.sort(np.diag(np.asarray(gram)))  # U_l carries s: diag = s^2
        all_s = np.sort(np.concatenate([np.asarray(v) for v in sv_l.values()]))
        np.testing.assert_allclose(s_sq, all_s**2, atol=1e-11)

    def test_no_absorb_returns_isometries(self):
        theta = rand_theta(9)
        U, V, _, _ = svd_split(theta, 2, max_bond=8, absorb="none")
        gram_u = np.asarray(
            contract(U.conj(), U, axes=((0, 1), (0, 1))).to_dense()
        )
        gram_v = np.asarray(contract(V, V.conj(), axes=((1, 2), (1, 2))).to_dense())
        np.testing.assert_allclose(gram_u, np.eye(len(gram_u)), atol=1e-11)
        np.testing.assert_allclose(gram_v, np.eye(len(gram_v)), atol=1e-11)


class TestTieBreak:
    def _tied_theta(self):
        """Two charge sectors whose sector matrices have identical spectra
        {1.0, 0.5} — every singular value is exactly tied across sectors."""
        row = Index((((0,), 2), ((1,), 2)), IN)
        col = Index((((0,), 2), ((1,), 2)), OUT)
        d = jnp.asarray(np.diag([1.0, 0.5]))
        return BlockSparseTensor([row, col], {(0, 0): d, (1, 1): d})

    def test_planned_exact_ties_keep_at_most_max_bond(self):
        theta = self._tied_theta()
        U, V, svals, _ = svd_split(theta, 1, max_bond=3, cutoff=0.0)
        assert U.indices[-1].dim == 3  # deterministic: 2 from sector 0, 1 from 1
        kept = {q: len(np.asarray(v)) for q, v in svals.items()}
        assert sum(kept.values()) == 3

    def test_seed_exact_ties_can_exceed_max_bond(self):
        """Documents the seed semantics the planned path fixes: every value
        tied at the threshold is kept, overshooting max_bond."""
        theta = self._tied_theta()
        U, _, _, _ = svd_split_unplanned(theta, 1, max_bond=3, cutoff=0.0)
        assert U.indices[-1].dim == 4

    def test_tie_break_is_deterministic(self):
        theta = self._tied_theta()
        a = svd_split(theta, 1, max_bond=3, cutoff=0.0)
        b = svd_split(theta, 1, max_bond=3, cutoff=0.0)
        for k in a[0].blocks:
            np.testing.assert_allclose(
                np.asarray(a[0].blocks[k]), np.asarray(b[0].blocks[k]), atol=0
            )


class TestRandomizedPath:
    def _decaying_theta(self, R=96, C=80):
        """Single-sector matrix with an exponentially decaying spectrum (the
        regime where a sketch captures the top of the spectrum accurately)."""
        rng = np.random.default_rng(0)
        u, _ = np.linalg.qr(rng.normal(size=(R, R)))
        v, _ = np.linalg.qr(rng.normal(size=(C, C)))
        s = 2.0 ** -np.arange(min(R, C), dtype=np.float64)
        dense = (u[:, : len(s)] * s) @ v[: len(s), :]
        row = Index((((0,), R),), IN)
        col = Index((((0,), C),), OUT)
        return BlockSparseTensor([row, col], {(0, 0): jnp.asarray(dense)})

    @pytest.mark.x64
    def test_randomized_matches_exact_top_of_spectrum(self):
        theta = self._decaying_theta()
        exact = DecompositionEngine(cache=DecompPlanCache(), method="svd")
        rand = DecompositionEngine(cache=DecompPlanCache(), method="randomized")
        max_bond = 8
        _, _, sv_e, err_e = exact.svd_split(theta, 1, max_bond, cutoff=0.0)
        _, _, sv_r, err_r = rand.svd_split(theta, 1, max_bond, cutoff=0.0)
        assert rand.rsvd_buckets == 1 and exact.rsvd_buckets == 0
        np.testing.assert_allclose(
            np.asarray(sv_r[(0,)]), np.asarray(sv_e[(0,)]), rtol=1e-8
        )
        # the sketch only sees the top of the spectrum, so its trunc_err is a
        # lower bound on the exact discarded weight
        assert err_r <= err_e + 1e-12

    def test_randomized_falls_back_to_exact_when_sketch_covers_rank(self):
        theta = rand_theta(4)  # tiny sectors: sketch >= min(R, C) everywhere
        eng = DecompositionEngine(cache=DecompPlanCache(), method="randomized")
        U, V, _, err = eng.svd_split(theta, 2, max_bond=8, cutoff=0.0)
        assert eng.rsvd_buckets == 0
        ref = svd_split_unplanned(theta, 2, max_bond=8, cutoff=0.0)
        np.testing.assert_allclose(recon(U, V), recon(ref[0], ref[1]), atol=1e-10)
        assert abs(err - ref[3]) < 1e-10

    def test_auto_cost_model_prefers_rsvd_only_on_large_buckets(self):
        eng = DecompositionEngine(cache=DecompPlanCache(), method="auto")
        small = eng.cache.get(rand_theta(4), 2)
        methods_small, _ = eng._bucket_methods(small, 8)
        assert set(methods_small) == {"svd"}
        big = eng.cache.get(self._decaying_theta(512, 512), 1)
        methods_big, sketch = eng._bucket_methods(big, 8)
        assert "rsvd" in methods_big and sketch == 8 + eng.rsvd_oversample


class TestEngineIntegration:
    def test_contraction_engine_svd_split_and_stats(self):
        theta = rand_theta(2)
        eng = ContractionEngine(backend="batched")
        eng.decomp = DecompositionEngine(cache=DecompPlanCache())
        U, V, _, _ = eng.svd_split(theta, 2, max_bond=8)
        st_ = eng.stats()["decomp"]
        assert st_["svd_calls"] == 1
        assert st_["svd_flops"] > 0
        assert st_["sectors"] >= st_["buckets"] >= 1
        assert st_["plan_cache"]["misses"] == 1

    def test_compile_once_no_retrace_on_same_structure(self):
        theta = rand_theta(6)
        eng = DecompositionEngine(cache=DecompPlanCache())
        eng.svd_split(theta, 2, max_bond=8)
        traces = eng.jit_retraces  # SVD core + output-slice core compiled
        assert traces >= 1
        theta2 = BlockSparseTensor(
            theta.indices,
            {k: 1.5 * b for k, b in theta.blocks.items()},
            theta.charge,
        )
        eng.svd_split(theta2, 2, max_bond=8)  # same structure: cached compile
        assert eng.jit_retraces == traces
        assert eng.cache.stats() == {"hits": 1, "misses": 1, "evictions": 0, "size": 1, "builds": 1}

    def test_tracer_input_raises(self):
        theta = rand_theta(1)
        eng = DecompositionEngine(cache=DecompPlanCache())

        def f(t):
            return eng.svd_split(t, 2, max_bond=4)[3]

        with pytest.raises(TypeError, match="concrete"):
            jax.jit(f)(theta)

    @pytest.mark.x64
    def test_dmrg_planned_svd_energy_equals_full_seed(self):
        sp = spin_half_space()
        terms = heisenberg_j1j2_terms(3, 2, 1.0, 0.5, cylinder=False)
        kw = dict(bond_schedule=(8,), sweeps_per_bond=2, davidson_iters=4)
        seed = run_dmrg(
            sp, terms, 6, algo="list_unplanned", svd_method="unplanned", **kw
        )
        planned = run_dmrg(sp, terms, 6, algo="batched", **kw)
        auto = run_dmrg(sp, terms, 6, algo="batched", svd_method="auto", **kw)
        assert abs(seed.energy - planned.energy) < 1e-10
        assert abs(seed.energy - auto.energy) < 1e-10
        # the sweep reports the decomposition stage separately
        assert planned.sweep_stats[-1].svd_seconds > 0

    def test_svd_method_rejected_for_bare_contractors(self):
        sp = spin_half_space()
        terms = heisenberg_j1j2_terms(3, 2, 1.0, 0.5, cylinder=False)
        with pytest.raises(ValueError, match="svd_method"):
            run_dmrg(
                sp, terms, 6, algo="list_unplanned", svd_method="svd",
                bond_schedule=(8,), sweeps_per_bond=1, davidson_iters=2,
            )
