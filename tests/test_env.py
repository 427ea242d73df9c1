"""Environment engine: the fused plan-cached env updates of dist/envcore.py
vs the seed extend_left/extend_right, compile-once retrace accounting, plan
cache semantics, and the sweep/dmrg ``jit_env`` knob."""
import jax
import numpy as np
import pytest

from repro.core import run_dmrg
from repro.core.env import extend_left, extend_right, left_edge, right_edge
from repro.core.models import heisenberg_j1j2_terms
from repro.core.mpo import build_mpo, compress_mpo
from repro.core.mps import neel_states, product_state_mps
from repro.core.siteops import spin_half_space
from repro.core.sweep import DMRGEngine
from repro.dist import EnvironmentEngine, EnvPlanCache
from repro.dist.envcore import env_out_indices
from repro.tensor.blocksparse import contract

# block-for-block equality bound: the fused core runs the same pair tables
# in the same order, so the only slack is padded-space accumulation noise
TOL = 1e-10 if jax.config.jax_enable_x64 else 2e-4


def _converged_system(n=6, m=8, sweeps=2, algo="list"):
    sp = spin_half_space()
    terms = heisenberg_j1j2_terms(n // 2, 2, 1.0, 0.5, cylinder=False)
    mpo = compress_mpo(build_mpo(sp, terms, n), cutoff=1e-13)
    mps = product_state_mps(sp, neel_states(sp, n))
    eng = DMRGEngine(mps, mpo, davidson_iters=2, algo=algo, jit_env=False)
    for _ in range(sweeps):
        eng.sweep(max_bond=m)
    return eng


def _assert_env_equal(got, ref, tol=TOL):
    assert got.indices == ref.indices
    assert got.charge == ref.charge
    assert set(got.blocks) == set(ref.blocks)
    for k in ref.blocks:
        np.testing.assert_allclose(
            np.asarray(got.blocks[k]), np.asarray(ref.blocks[k]), atol=tol
        )


class TestFusedEqualsSeed:
    """Planned fused updates == seed extend_left/extend_right block-for-block
    across all engine backends (the fused core is backend-independent; the
    parametrization exercises the ContractionEngine threading)."""

    @pytest.mark.parametrize(
        "backend", ["list", "dense", "batched", "csr_ref", "auto"]
    )
    def test_left_and_right_passes(self, backend):
        n = 6
        eng = _converged_system(n=n, algo=backend)
        T, W = eng.mps.tensors, eng.mpo
        ceng = eng.contract_fn

        A_ref = A_got = left_edge(T[0], W[0])
        for j in range(n - 1):
            A_ref = extend_left(A_ref, T[j], W[j], contract)
            A_got = ceng.env_update_left(A_got, T[j], W[j])
            _assert_env_equal(A_got, A_ref)

        B_ref = B_got = right_edge(T[n - 1], W[n - 1])
        for j in range(n - 1, 0, -1):
            B_ref = extend_right(B_ref, T[j], W[j], contract)
            B_got = ceng.env_update_right(B_got, T[j], W[j])
            _assert_env_equal(B_got, B_ref)

    def test_unpadded_core_matches_too(self):
        """pad=False runs the same fused body on the raw structures."""
        n = 6
        eng = _converged_system(n=n)
        T, W = eng.mps.tensors, eng.mpo
        ee = EnvironmentEngine(cache=EnvPlanCache(), pad=False)
        A_ref = A_got = left_edge(T[0], W[0])
        for j in range(n - 1):
            A_ref = extend_left(A_ref, T[j], W[j], contract)
            A_got = ee.update_left(A_got, T[j], W[j])
            _assert_env_equal(A_got, A_ref)

    def test_out_indices_match_seed_structure(self):
        n = 6
        eng = _converged_system(n=n)
        T, W = eng.mps.tensors, eng.mpo
        A = left_edge(T[0], W[0])
        ref = extend_left(A, T[0], W[0], contract)
        assert env_out_indices(T[0], W[0], "left") == ref.indices
        B = right_edge(T[n - 1], W[n - 1])
        ref = extend_right(B, T[n - 1], W[n - 1], contract)
        assert env_out_indices(T[n - 1], W[n - 1], "right") == ref.indices

    def test_init_envs_match_seed_path(self):
        """_init_envs as a planned right-to-left pass == the seed rebuild."""
        n = 6
        sp = spin_half_space()
        terms = heisenberg_j1j2_terms(n // 2, 2, 1.0, 0.5, cylinder=False)
        mpo = compress_mpo(build_mpo(sp, terms, n), cutoff=1e-13)
        on = DMRGEngine(
            product_state_mps(sp, neel_states(sp, n)), mpo,
            davidson_iters=2, algo="list", jit_env=True,
        )
        off = DMRGEngine(
            product_state_mps(sp, neel_states(sp, n)), mpo,
            davidson_iters=2, algo="list", jit_env=False,
        )
        for e_on, e_off in zip(on.right_envs, off.right_envs):
            if e_on is None or e_off is None:
                assert e_on is e_off
                continue
            _assert_env_equal(e_on, e_off)


class TestCompileOnceEnv:
    def test_retraces_stop_growing_at_steady_state(self):
        """The padded fused core compiles during warmup and then replays:
        at structural steady state two further sweeps trigger zero new
        retraces (the compile-once contract of the env stage)."""
        eng = _converged_system(n=6, m=8, sweeps=0)
        eng.jit_env = True  # fused updates from here on
        # private plan cache: compiled cores live on the (normally global)
        # plans, so a shared cache warmed by earlier tests would hide the
        # compile this test wants to observe
        eng.contract_fn.env.cache = EnvPlanCache()
        for _ in range(4):
            eng.sweep(max_bond=8)
        env_eng = eng.contract_fn.env
        assert env_eng.jit_retraces > 0  # it did compile
        before = env_eng.jit_retraces
        for _ in range(2):
            eng.sweep(max_bond=8)
        assert env_eng.jit_retraces == before

    def test_plan_cache_hit_on_equal_structure(self):
        n = 6
        eng = _converged_system(n=n)
        T, W = eng.mps.tensors, eng.mpo
        ee = EnvironmentEngine(cache=EnvPlanCache())
        A = left_edge(T[0], W[0])
        ee.update_left(A, T[0], W[0])
        assert ee.cache.stats() == {"hits": 0, "misses": 1, "evictions": 0, "size": 1, "builds": 1}
        rt = ee.jit_retraces
        ee.update_left(A, T[0], W[0])
        assert ee.cache.stats() == {"hits": 1, "misses": 1, "evictions": 0, "size": 1, "builds": 1}
        assert ee.jit_retraces == rt  # compiled core reused, not retraced

    def test_left_and_right_have_distinct_plans(self):
        """Sweep direction is part of the composite signature."""
        n = 6
        eng = _converged_system(n=n)
        T, W = eng.mps.tensors, eng.mpo
        ee = EnvironmentEngine(cache=EnvPlanCache())
        # an env structure that is valid for both directions only exists at
        # the edges; check the two signatures never collide in the cache
        ee.update_left(left_edge(T[0], W[0]), T[0], W[0])
        ee.update_right(right_edge(T[n - 1], W[n - 1]), T[n - 1], W[n - 1])
        assert ee.cache.stats()["misses"] == 2
        assert ee.cache.stats()["size"] == 2


class TestSweepIntegration:
    @pytest.mark.x64
    def test_jit_env_energy_equals_seed(self):
        sp = spin_half_space()
        terms = heisenberg_j1j2_terms(3, 2, 1.0, 0.5, cylinder=False)
        kw = dict(bond_schedule=(8, 16), sweeps_per_bond=2, davidson_iters=6)
        seed = run_dmrg(sp, terms, 6, algo="list_unplanned", **kw)
        fused = run_dmrg(sp, terms, 6, algo="list", jit_env=True, **kw)
        assert abs(seed.energy - fused.energy) < 1e-10

    @pytest.mark.x64
    def test_jit_env_on_off_agree(self):
        sp = spin_half_space()
        terms = heisenberg_j1j2_terms(3, 2, 1.0, 0.5, cylinder=False)
        kw = dict(bond_schedule=(8,), sweeps_per_bond=2, davidson_iters=4)
        on = run_dmrg(sp, terms, 6, algo="batched", jit_env=True, **kw)
        off = run_dmrg(sp, terms, 6, algo="batched", jit_env=False, **kw)
        assert abs(on.energy - off.energy) < 1e-10

    def test_env_seconds_stage_split_populated(self):
        eng = _converged_system(n=6, sweeps=0)
        eng.jit_env = True
        s = eng.sweep(max_bond=8)
        assert s.env_seconds > 0
        assert s.env_seconds < s.seconds
        ledger = eng.contract_fn.stats()["env"]
        # one update per pair optimization: 2 * (n - 1) per sweep
        assert ledger["env_updates"] == 2 * (6 - 1)
        assert ledger["env_flops"] > 0

    def test_jit_env_rejected_for_bare_contractors(self):
        sp = spin_half_space()
        terms = heisenberg_j1j2_terms(3, 2, 1.0, 0.5, cylinder=False)
        mpo = compress_mpo(build_mpo(sp, terms, 6), cutoff=1e-13)
        mps = product_state_mps(sp, neel_states(sp, 6))
        with pytest.raises(ValueError, match="jit_env requires"):
            DMRGEngine(mps, mpo, algo="list_unplanned", jit_env=True)
        # and default resolves to off (no error, seed path) for bare algos
        eng = DMRGEngine(mps, mpo, algo="list_unplanned")
        assert eng.jit_env is False
